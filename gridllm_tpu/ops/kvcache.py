"""Paged KV cache.

The reference has no KV cache (Ollama owns it externally; SURVEY.md §0). This
is the TPU-native replacement per SURVEY.md §5.7/§7 step 5: a single static
page pool shared by all batch slots, so HBM is sized by total live tokens
rather than slots × max_seq_len, and shapes stay static under jit.

Layout (per model):
  k/v: [num_layers, num_pages, page_size, num_kv_heads, head_dim]
  (a LATENT pool, MLA: k alone, one cache head whose row is the latent and
  its RoPE key, and v is None — same pages, tables, allocator and keys)
  page_table: [max_slots, max_pages_per_slot] int32 page ids (-1 = unmapped)
  lengths: [max_slots] int32 tokens stored per slot
  rec: a second kind of cache beside the pages (RecurrentState: a hybrid
  family's linear-attention layers keep one state a slot, not a row a
  token; the pool's leading axis is then the layers that DO own pages,
  fewer than the model's)

Page *allocation* is host-side Python (engine/scheduling concern, cheap,
O(pages)); device ops only read/scatter through the tables. Page 0 is a real,
usable page — unmapped entries are -1; the write paths remap them (and
inactive slots) to index `num_pages`, which is out of bounds so scatter
mode="drop" actually drops them (a raw -1 would WRAP to the last page —
jax negative indexing applies in scatter too).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
from collections import OrderedDict
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.analysis import numcheck
from gridllm_tpu.obs import default_registry
from gridllm_tpu.utils.config import env_str

# Which implementation each traced program took: "pallas" (kernel) or
# "jnp" (fallback scatter/reference). Incremented at TRACE time — once per
# compiled program, not per step — so a nonzero jnp count for an op that
# should run the kernel path is the silent-fallback tripwire (the
# pre-fb61f50 d=64 fallback would have been one dashboard cell, not a
# bisect). ops/attention.py records through this too.
_KERNEL_DISPATCH = default_registry().counter(
    "gridllm_kernel_dispatch_total",
    "Compiled programs by op and implementation path (pallas kernel vs "
    "jnp fallback). Counted per trace/compile, not per step.",
    ("op", "path"),
)


def record_kernel_path(op: str, kernel: bool) -> None:
    _KERNEL_DISPATCH.inc(op=op, path="pallas" if kernel else "jnp")


# Automatic prefix caching (ISSUE 3): page-granular reuse accounting.
# hit/miss are counted in PAGES of the prompt at admission time (a hit page
# is prefill compute skipped, a miss page is prefill compute paid), so
# hits / (hits + misses) is the prompt-page hit rate the engine exports as
# gridllm_prefix_cache_hit_rate. evictions = cached pages reclaimed for
# fresh allocations; cow_copies = tail pages that WERE cached but had to be
# privately rebuilt because the request writes into them (the last-token /
# partial-tail copy-on-write, realized as recompute-into-a-fresh-page).
_PREFIX_HITS = default_registry().counter(
    "gridllm_prefix_cache_hits_total",
    "Prompt pages served from the prefix cache (prefill skipped), by model.",
    ("model",),
)
_PREFIX_MISSES = default_registry().counter(
    "gridllm_prefix_cache_misses_total",
    "Prompt pages not found in the prefix cache (prefill paid), by model.",
    ("model",),
)
_PREFIX_EVICTIONS = default_registry().counter(
    "gridllm_prefix_cache_evictions_total",
    "Cached prefix pages evicted (LRU) to satisfy fresh allocations, "
    "by model.",
    ("model",),
)
_PREFIX_COW = default_registry().counter(
    "gridllm_prefix_cache_cow_copies_total",
    "Cached tail pages privately rebuilt because the request writes into "
    "them (copy-on-write of the partial tail page), by model.",
    ("model",),
)


# A hybrid family's recurrent state beside the pages (PR 42). A prefix found
# in the page cache can be admitted only from a boundary whose STATE is
# held too (a snapshot): `hit` = restored at the end of the page match,
# `short` = at an earlier boundary, `miss` = pages matched and no snapshot
# (cold). replay tokens = prompt tokens run through the model again
# although their pages were found.
_STATE_PREFIX = default_registry().counter(
    "gridllm_state_prefix_total",
    "Admissions whose prompt matched cached pages, by where the recurrent "
    "state could be restored: hit (at the match), short (an earlier "
    "boundary), miss (no snapshot: cold).",
    ("model", "outcome"),
)
_STATE_REPLAY = default_registry().counter(
    "gridllm_state_replay_tokens_total",
    "Prompt tokens run through the model again although their pages were "
    "cached, for want of a state snapshot at the match, by model.",
    ("model",),
)
_STATE_SNAPSHOTS = default_registry().counter(
    "gridllm_state_snapshots_total",
    "Recurrent-state snapshots saved by a chunk launch, restored into a "
    "slot at admission, evicted from the snapshot pool.",
    ("model", "event"),
)


@functools.cache
def _env_mode() -> tuple[bool, bool]:
    """(use_kernels, interpret) from the environment, resolved once.
    Shared policy for the attention kernels (ops/attention.py imports it)
    and the KV-write kernels below: env `GRIDLLM_PALLAS` = "auto"
    (default: kernels on TPU backends only), "1" (force on), "0" (force
    off), "interpret" (kernels in interpreter mode — CPU testing)."""
    raw = env_str("GRIDLLM_PALLAS").lower()
    if raw in ("0", "off", "false"):
        return False, False
    if raw in ("1", "on", "true"):
        return True, False
    if raw == "interpret":
        return True, True
    return jax.default_backend() == "tpu", False


def _pallas_mode(use_pallas: bool | None) -> tuple[bool, bool]:
    """`use_pallas` is the per-call override (threaded from
    ModelConfig.use_pallas by the model code); None defers to the env
    policy. pallas_call has no GSPMD partitioning rule, so under a mesh
    the dispatch layers wrap the kernel in a full-manual shard_map
    (`kernel_mesh_axis` below) instead of letting GSPMD see it."""
    use, interpret = _env_mode()
    if use_pallas is not None:
        use = use_pallas
    return use, interpret


def kernel_mesh_axis(mesh, kvh: int, h: int | None = None):
    """(mode, axis) for running Pallas kernels under `mesh`.

    pallas_call has no GSPMD partitioning rule: inside an auto-partitioned
    jit it either fails to partition or forces full replication. The fix
    (VERDICT r04 #2) is a FULL-manual shard_map at the kernel boundary —
    attention and KV-writes are embarrassingly parallel over kv-heads, so
    each tp shard runs the existing kernel on its head slice with no
    collectives. This helper decides the layout:

    - ("direct", None): no mesh — call the kernel directly.
    - ("wrap", "tp"): kv-heads (and q-heads) divide by the tp axis —
      shard head dims over "tp", matching parallel/sharding.py's Megatron
      specs, so the shard_map boundary is a no-op resharding.
    - ("wrap", None): mesh present but heads don't divide (tiny test
      configs) — the wrapper still isolates the kernel from GSPMD, with
      head dims replicated (matches sharding._fit's fallback).
    - ("ref", None): the wrapper can't express the operands' sharding —
      pp > 1 shards the pool's layer axis, and a spec that doesn't
      mention pp would silently all-gather the whole pool. Callers must
      take their jnp reference path (GSPMD-safe). The pipeline module
      pins use_pallas=False anyway; this is the belt to that suspender.

    Unmentioned mesh axes (dp/ep/sp) mean "replicated" in a full-manual
    shard_map — exactly how those axes see attention operands.
    """
    if mesh is None:
        return "direct", None
    if mesh.shape.get("pp", 1) > 1:
        return "ref", None
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and kvh % tp == 0 and (h is None or h % tp == 0):
        return "wrap", "tp"
    return "wrap", None


def _shard_map_kernel(mesh, body, in_specs, out_specs):
    """jax.shard_map for a kernel body: full-manual (all axes), with vma
    checking off — pallas_call can't annotate how outputs vary across
    mesh axes, and the bodies here have no collectives to get wrong."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _write_lane_gate(k_pages, interpret: bool) -> bool:
    """Mosaic lane-alignment gate for the pool-write kernels: a head dim
    of whole 128-lane tiles (a narrower head is stored lane-padded where
    kernels compile: `lane_pad_dim`), or interpreted."""
    return interpret or k_pages.shape[-1] % 128 == 0


def lane_pad_dim(d: int) -> int:
    """Head dim rounded up to the 128-lane tile. The engine allocates the
    page pool at this width when kernels are on (d=64 models: qwen2.5
    class) so Mosaic's alignment constraint is met and decode/writes keep
    the kernel path; the attention/write dispatchers pad q/K/V to the
    pool's width and slice outputs back (exact — see
    ops.attention._lane_pad_qkv). Costs 2x KV memory on d=64
    models, which are the smallest ones served."""
    return -(-d // 128) * 128


def _pad_new_lanes(k_pages, k_new, v_new):
    """Zero-pad fresh K/V rows to a lane-padded pool's head dim."""
    dpool, d = k_pages.shape[-1], k_new.shape[-1]
    if dpool == d:
        return k_new, v_new
    pad = [(0, 0)] * (k_new.ndim - 1) + [(0, dpool - d)]
    return jnp.pad(k_new, pad), (None if v_new is None
                                 else jnp.pad(v_new, pad))


def _write_latent_rows(k_pages, rows, page_idx, offset):
    """Row writes of a LATENT pool (decode and verify): one XLA scatter
    of whole rows into the pool viewed flat, [L * P * ps, D].at[rows]. Not
    a kernel's fallback: it has no kernel. A latent page is [ps, D] with no
    head axis under the row, so one bfloat16 row is half a sublane tile,
    which Mosaic cannot slice for a DMA (compiled for v5e, PR 36); and the
    flat view is what keeps XLA in place: the same scatter written
    `pool.at[:, page, row]` copies the whole pool twice a launch (2.7 GB of
    temporaries at 10 layers x 1,024 pages). Counted apart
    (`path="xla"`), so the `jnp` tripwire stays a kernel's alone.

    rows [L, n, 1, D]; page_idx [n] with the out-of-bounds sentinel
    `num_pages` = drop; offset [n]."""
    n_layers, num_pages, ps, _, d = k_pages.shape
    _KERNEL_DISPATCH.inc(op="write_latent_rows", path="xla")
    at = (jnp.arange(n_layers, dtype=jnp.int32)[:, None] * num_pages
          + page_idx[None]) * ps + offset[None]
    at = jnp.where(page_idx[None] < num_pages, at, n_layers * num_pages * ps)
    flat = k_pages.reshape(-1, d).at[at.reshape(-1)].set(
        rows.reshape(-1, d), mode="drop")
    return flat.reshape(k_pages.shape)


def _wrap_write_kernel(mesh, ax, kernel, scalar_specs):
    """Shared meshed wrapper for the two pool-write kernels: pools + new
    rows split on `ax` over kv-heads, trailing host-computed operands
    (page_idx/offset or table_row/start/length) per `scalar_specs`."""
    from jax.sharding import PartitionSpec as P

    pool = P(None, None, None, ax, None)
    new = P(None, None, ax, None)
    return _shard_map_kernel(
        mesh, kernel,
        in_specs=(pool, pool, new, new, *scalar_specs),
        out_specs=(pool, pool),
    )


def _nbytes(a) -> int:
    """An array's bytes, or those of its shape alone (jax.eval_shape)."""
    return math.prod(a.shape) * a.dtype.itemsize


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["state", "conv", "pend_x", "pend_k", "pend_v", "pend_b",
                 "pend_g", "pend_n", "snap_state", "snap_conv"],
    meta_fields=[],
)
@dataclasses.dataclass
class RecurrentState:
    """What a slot holds of its past in the linear-attention layers (a
    gated delta rule, ops/linear_attn.py): the state and the
    convolution's last rows. Both LAG: the last step launch's rows are
    `pend_*`, of which `pend_n` a slot count (a decode step's one row, or
    as many of a verify launch's K+1 as speculation accepted), and the
    next launch commits them before its own. So speculation's commit
    moves no state: it sets `pend_n`. A chunk launch (a prompt) writes its
    slot's state outright and leaves it nothing pending.

    `snap_*` is the prefix cache's pool of snapshots: the state at a page
    boundary of some prompt, found by that page's chain key
    (PageAllocator) and copied into a slot that is admitted from the pages
    up to there."""

    state: jnp.ndarray       # [Ll, S, dk, H*dv] float32 (heads packed)
    # rows of C channels side by side on the minor axis (a [.., 3, C]
    # array is stored at 16 rows, five times the bytes)
    conv: jnp.ndarray        # [Ll, S, (K-1)*C]: rows before the next one
    pend_x: jnp.ndarray      # [Ll, S, T*C] the rows before the convolution
    # keys a head, or a GROUP of heads where a family says so (`groups`:
    # a state-space layer's B, [.., T, 1, dk]; C*, the convolution's
    # channels, is then 2 G dk + H dv)
    pend_k: jnp.ndarray      # [Ll, S, T, H, dk] float32, normalised
    pend_v: jnp.ndarray      # [Ll, S, T, H, dv] float32 ([Ll, S, T*H*dv],
    #                          rows and heads side by side on the lanes as
    #                          pend_x lies, with `groups`: a [.., 5, 64, 64]
    #                          tail is tiled at 8 rows and 128 lanes, three
    #                          times the bytes, and XLA relays it to put
    #                          the slots under the rows, twice a launch)
    pend_b: jnp.ndarray      # [Ll, S, T, H] float32 beta ([.., 0] where
    #                          the rule has no delta)
    pend_g: jnp.ndarray      # [Ll, S, T, H] float32 log decay ([.., H, dk]
    #                          where it is a value a key channel)
    pend_n: jnp.ndarray      # [S] int32 pending rows that count
    snap_state: jnp.ndarray  # [Ll, N, dk, H*dv]
    snap_conv: jnp.ndarray   # [Ll, N, (K-1)*C]

    @staticmethod
    def create(layers: int, slots: int, heads: int, dk: int, dv: int,
               conv_kernel: int, step_rows: int, snapshots: int,
               dtype=jnp.bfloat16,
               channel_decay: bool = False,
               groups: int | None = None) -> "RecurrentState":
        """`groups`: keys and queries one a group of heads and no delta
        (a state-space scan); None: one a head, with a beta."""
        kq = heads if groups is None else groups
        c = 2 * kq * dk + heads * dv
        f32 = jnp.float32
        decay = (dk,) if channel_decay else ()
        return RecurrentState(
            state=jnp.zeros((layers, slots, dk, heads * dv), f32),
            conv=jnp.zeros((layers, slots, (conv_kernel - 1) * c), dtype),
            pend_x=jnp.zeros((layers, slots, step_rows * c), dtype),
            pend_k=jnp.zeros((layers, slots, step_rows, kq, dk), f32),
            pend_v=jnp.zeros(
                (layers, slots, *((step_rows, heads, dv) if groups is None
                                  else (step_rows * heads * dv,))), f32),
            pend_b=jnp.zeros(
                (layers, slots, step_rows, heads if groups is None else 0),
                f32),
            pend_g=jnp.zeros((layers, slots, step_rows, heads, *decay), f32),
            pend_n=jnp.zeros((slots,), jnp.int32),
            snap_state=jnp.zeros((layers, snapshots, dk, heads * dv), f32),
            snap_conv=jnp.zeros((layers, snapshots, (conv_kernel - 1) * c),
                                dtype),
        )

    @property
    def step_rows(self) -> int:
        return self.pend_k.shape[2]

    @property
    def slot_nbytes(self) -> int:
        """Bytes every slot's state takes: all but the snapshot pool."""
        return sum(map(_nbytes, jax.tree.leaves(self))) - self.snap_nbytes

    @property
    def snap_nbytes(self) -> int:
        return _nbytes(self.snap_state) + _nbytes(self.snap_conv)

    def restore(self, slot, snap) -> "RecurrentState":
        """Snapshot `snap` copied into `slot`, nothing pending."""
        return dataclasses.replace(
            self,
            state=self.state.at[:, slot].set(self.snap_state[:, snap]),
            conv=self.conv.at[:, slot].set(self.snap_conv[:, snap]),
            pend_n=self.pend_n.at[slot].set(0))


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v"],
    meta_fields=["ring_pages", "snap_pages", "slots"],
)
@dataclasses.dataclass
class WindowRing:
    """What a slot holds of its past in the WINDOW layers of a family
    whose layers are stacked by kind (ModelConfig.ring_layers): a ring of
    `ring_pages` pages a slot and layer, whatever the context's length.
    Logical page p of slot s stands at pool page `s * ring_pages + p %
    ring_pages`: `table()` is a page table like any other, and every read
    and write of the paged kernels takes it as it takes the one table.
    What the ring has overwritten lies below the window of every later
    query (ModelConfig.ring_pages: the window, a launch's rows and a page)
    and is masked by position like any row outside a window.

    Behind the rings, in the same arrays, the prefix cache's pool of
    snapshots: the `snap_pages` pages that end at a page boundary of some
    prompt, found by that page's chain key (PageAllocator) and copied into
    the ring of a slot that is admitted from the pages up to there."""

    k: jnp.ndarray   # [Lw, S * ring_pages + N * snap_pages, ps, KVH, D]
    v: jnp.ndarray
    ring_pages: int = 1
    snap_pages: int = 1
    slots: int = 1

    @staticmethod
    def create(layers: int, slots: int, ring_pages: int, snap_pages: int,
               snapshots: int, page_size: int, kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16) -> "WindowRing":
        shape = (layers, slots * ring_pages + snapshots * snap_pages,
                 page_size, kv_heads, head_dim)
        return WindowRing(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                          ring_pages, snap_pages, slots)

    @property
    def page_nbytes(self) -> int:
        """Bytes of one pool page over every window layer, K and V."""
        return 2 * _nbytes(self.k) // self.k.shape[1]

    @property
    def slot_nbytes(self) -> int:
        """Bytes every slot's ring takes: all but the snapshot pool."""
        return self.slots * self.ring_pages * self.page_nbytes

    @property
    def snap_nbytes(self) -> int:
        return 2 * _nbytes(self.k) - self.slot_nbytes

    def table(self, max_pages: int) -> jnp.ndarray:
        """[S, max_pages] int32: the rings as a page table."""
        p = jnp.arange(max_pages, dtype=jnp.int32) % self.ring_pages
        return (jnp.arange(self.slots, dtype=jnp.int32)[:, None]
                * self.ring_pages + p[None])

    def row(self, slot, max_pages: int) -> jnp.ndarray:
        """[max_pages] int32: one slot's ring as a page-table row."""
        return self.table(max_pages)[slot]

    def _copy(self, src, dst, ok) -> "WindowRing":
        """Pool pages `src` copied over pages `dst` where `ok`."""
        dst = jnp.where(ok, dst, self.k.shape[1])
        src = jnp.clip(src, 0, self.k.shape[1] - 1)
        return dataclasses.replace(
            self, k=self.k.at[:, dst].set(self.k[:, src], mode="drop"),
            v=self.v.at[:, dst].set(self.v[:, src], mode="drop"))

    def _span(self, slot, at, entry, page_size: int):
        """(ring pages, snapshot pages, which exist) of the `snap_pages`
        logical pages of `slot` that end at position `at`."""
        j = jnp.arange(self.snap_pages, dtype=jnp.int32)
        logical = at[..., None] // page_size - self.snap_pages + j
        ring = slot * self.ring_pages + logical % self.ring_pages
        snap = (self.slots * self.ring_pages
                + entry[..., None] * self.snap_pages + j)
        return ring, snap, (logical >= 0) & (entry[..., None] >= 0)

    def save(self, slot, at, entry, page_size: int) -> "WindowRing":
        """The slot's last rows before each boundary `at` [n] (pages the
        ring still holds: the launch that passed it has just written)
        into snapshot entries `entry` [n] (-1: none)."""
        ring, snap, ok = self._span(slot, at, entry, page_size)
        return self._copy(ring.reshape(-1), snap.reshape(-1), ok.reshape(-1))

    def restore(self, slot, entry, at, page_size: int) -> "WindowRing":
        """Snapshot `entry`, taken at position `at`, into `slot`'s ring."""
        ring, snap, ok = self._span(slot, at, entry, page_size)
        return self._copy(snap, ring, ok)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "page_table", "lengths", "rec", "win"],
    meta_fields=["page_size"],
)
@dataclasses.dataclass
class PagedKVCache:
    k: jnp.ndarray           # [L, P, page_size, KVH, D]
    v: jnp.ndarray | None    # [L, P, page_size, KVH, D]; None: latent pool
    page_table: jnp.ndarray  # [S, max_pages] int32
    lengths: jnp.ndarray     # [S] int32
    page_size: int = 128
    rec: RecurrentState | None = None   # a hybrid family's second cache
    win: WindowRing | None = None       # the window layers' rings

    @staticmethod
    def create(
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        max_slots: int,
        max_pages_per_slot: int,
        dtype=jnp.bfloat16,
        latent: bool = False,
    ) -> "PagedKVCache":
        """`latent`: one array of rows that are key and value at once
        (MLA: num_kv_heads 1, head_dim the latent and its RoPE key); no V
        array is made."""
        shape = (num_layers, num_pages, page_size, num_kv_heads, head_dim)
        return PagedKVCache(
            k=jnp.zeros(shape, dtype),
            v=None if latent else jnp.zeros(shape, dtype),
            page_table=jnp.full((max_slots, max_pages_per_slot), -1, jnp.int32),
            lengths=jnp.zeros((max_slots,), jnp.int32),
            page_size=page_size,
        )

    @property
    def num_layers(self) -> int:
        """Layers that own pages: fewer than the model's where `rec`
        holds the others' state."""
        return self.k.shape[0]

    @property
    def pool_nbytes(self) -> int:
        """Bytes of the page pool: K and V, or the latent rows alone."""
        return self.k.nbytes + (0 if self.v is None else self.v.nbytes)

    @property
    def max_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def max_context(self) -> int:
        return self.page_table.shape[1] * self.page_size


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data", "scale"],
    meta_fields=[],
)
@dataclasses.dataclass
class QuantPages:
    """int8 KV page pool + per-row dequant scales (ISSUE 11,
    ``GRIDLLM_KV_INT8``): ``data`` holds the quantized values, ``scale``
    one float32 symmetric scale per (layer, page, row) — a token row
    [KVH, D] is the quantization granule, so incremental decode/verify
    writes quantize independently without ever re-scaling a page. The
    engine stores a QuantPages where ``PagedKVCache.k``/``.v`` would hold
    a raw array; model code passes it through opaquely (same pytree
    flow/donation), and the ops dispatchers here and in ops/attention.py
    unwrap it: writes quantize at the boundary, reads dequantize — the
    ragged Pallas kernel in its flat-row page load (dequant epilogue),
    every jnp fallback via :func:`gather_kv`/``take``. Halves resident
    KV HBM at a bounded accuracy cost (per-row worst case scale/2 ≈
    amax/254 absolute error per element)."""

    data: jnp.ndarray   # int8 [L, P, ps, KVH, D] (or one layer: 4-dim)
    scale: jnp.ndarray  # f32  [L, P, ps]         (or one layer: [P, ps])

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return self.data.nbytes + self.scale.nbytes

    def layer(self, li) -> "QuantPages":
        """One layer's pool slice (dynamic index — from inside a scan)."""
        return QuantPages(
            jax.lax.dynamic_index_in_dim(self.data, li, keepdims=False),
            jax.lax.dynamic_index_in_dim(self.scale, li, keepdims=False),
        )

    def take(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Dequantized float32 pages gathered along the page axis of a
        single-layer (4-dim) pool: data[rows] * scale[rows] broadcast
        over each row's [KVH, D]."""
        return (self.data[rows].astype(jnp.float32)
                * self.scale[rows][..., None, None])


def quantize_kv_rows(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row symmetric int8 quantization of fresh K/V:
    x [..., KVH, D] float → (int8 values, float32 scales [...]). A row's
    scale is amax/127 (all-zero rows keep 1.0 so dequant is exact)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _safe_page_idx(
    lookup,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    page_size: int,
    max_pages: int,
    num_pages: int,
) -> jnp.ndarray:
    """Page index for each write position, with every hazard masked to the
    out-of-bounds sentinel `num_pages` so scatter mode="drop" really drops:

    - invalid positions (padding / inactive slot) — caller's `valid` mask
    - past-capacity positions: jax gather CLAMPS out-of-range lookups to
      the row's last entry (a real page), so mask before looking up
    - unmapped table entries (-1): negative indices WRAP in jax scatter

    `lookup(page_no)` maps in-range page numbers to page ids.
    """
    in_cap = positions < max_pages * page_size
    mapped = lookup(jnp.minimum(positions // page_size, max_pages - 1))
    return jnp.where(valid & in_cap & (mapped >= 0), mapped, num_pages)


def write_prefill(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    table_row: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    page_size: int,
    use_pallas: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a prefill chunk for ONE slot into the (single-layer) page pool.

    k_pages/v_pages: [P, page_size, KVH, D] — one layer's pool.
    k_new/v_new: [T, KVH, D] (T = padded bucket length).
    table_row: [max_pages] page ids for this slot.
    start: scalar — absolute position of k_new[0] (0 for fresh prompts,
    cached length for chunked prefill). length: scalar — valid tokens in
    k_new; positions >= length are dropped.

    Single-layer scatter form (CPU/mesh fallback and tests); the hot path
    writes all layers at once AFTER the layer scan via write_prefill_all —
    per-layer writes inside a scan defeat XLA's in-place buffer aliasing.
    """
    del use_pallas  # single-layer form is always scatter; see _all variant
    if isinstance(k_pages, QuantPages):
        # only pp routes through the single-layer forms, and the engine
        # pins int8 off under any mesh — reaching here is a wiring bug
        raise TypeError("int8 KV pools are not supported on the "
                        "single-layer write path")
    t = jnp.arange(k_new.shape[0], dtype=jnp.int32)
    pos = start + t
    page_idx = _safe_page_idx(
        lambda p: table_row[p], pos, t < length, page_size,
        table_row.shape[0], k_pages.shape[0],
    )
    offset = pos % page_size
    k_pages = k_pages.at[page_idx, offset].set(k_new, mode="drop")
    v_pages = v_pages.at[page_idx, offset].set(v_new, mode="drop")
    return k_pages, v_pages


def write_decode(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    active: jnp.ndarray,
    page_size: int,
    use_pallas: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one new token per slot into the (single-layer) page pool.

    k_new/v_new: [S, KVH, D]; positions: [S] absolute write position per
    slot; active: [S] bool — inactive slots are dropped.

    Single-layer scatter form (CPU/mesh fallback and tests); the hot path
    is write_decode_all (all layers, once per step, after the layer scan).
    """
    del use_pallas
    if isinstance(k_pages, QuantPages):
        raise TypeError("int8 KV pools are not supported on the "
                        "single-layer write path")
    s = jnp.arange(page_table.shape[0], dtype=jnp.int32)
    page_idx = _safe_page_idx(
        lambda p: page_table[s, p], positions, active, page_size,
        page_table.shape[1], k_pages.shape[0],
    )
    offset = positions % page_size
    k_pages = k_pages.at[page_idx, offset].set(k_new, mode="drop")
    v_pages = v_pages.at[page_idx, offset].set(v_new, mode="drop")
    return k_pages, v_pages


def write_decode_all(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    active: jnp.ndarray,
    page_size: int,
    use_pallas: bool | None = None,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write one token per slot across ALL layers at once.

    k_pages/v_pages: [L, P, ps, KVH, D] (the full pool); k_new/v_new:
    [L, S, KVH, D]. Runs once per decode step at jit top level, where
    donation makes the update truly in place (TPU: DMA kernel; otherwise
    one batched scatter). Under `mesh` the kernel runs inside a
    full-manual shard_map with kv-heads split over tp (writes are
    shard-local — no collectives; see kernel_mesh_axis).

    int8 pools (QuantPages, ISSUE 11) quantize the fresh rows per row at
    this boundary and scatter values + scales; the write KERNEL path is
    deliberately skipped there (the int8 scatter is O(S) rows — tiny
    next to attention — and Mosaic's int8 sublane tiling on sub-lane-row
    DMA destinations is unproven on real hardware).
    """
    # numerics sanitizer: a NaN/Inf KV row poisons every later read of
    # its page — trip at the write boundary, not in a garbled stream
    numcheck.check_finite(
        "kv.write", *(x for x in (k_new, v_new) if x is not None))
    if isinstance(k_pages, QuantPages):
        k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
        s = jnp.arange(page_table.shape[0], dtype=jnp.int32)
        page_idx = _safe_page_idx(
            lambda p: page_table[s, p], positions, active, page_size,
            page_table.shape[1], k_pages.data.shape[1],
        )
        offset = positions % page_size
        record_kernel_path("write_decode", False)
        kq, ksc = quantize_kv_rows(k_new)   # [L, S, KVH, D] / [L, S]
        vq, vsc = quantize_kv_rows(v_new)
        return (
            QuantPages(
                k_pages.data.at[:, page_idx, offset].set(kq, mode="drop"),
                k_pages.scale.at[:, page_idx, offset].set(ksc, mode="drop"),
            ),
            QuantPages(
                v_pages.data.at[:, page_idx, offset].set(vq, mode="drop"),
                v_pages.scale.at[:, page_idx, offset].set(vsc, mode="drop"),
            ),
        )
    k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
    s = jnp.arange(page_table.shape[0], dtype=jnp.int32)
    page_idx = _safe_page_idx(
        lambda p: page_table[s, p], positions, active, page_size,
        page_table.shape[1], k_pages.shape[1],
    )
    offset = positions % page_size
    if v_pages is None:
        return _write_latent_rows(k_pages, k_new, page_idx, offset), None
    use, interpret = _pallas_mode(use_pallas)
    # same Mosaic constraint as the attention kernels: page slices need a
    # 128-lane-aligned minor dim on real TPU — a (padded) d % 128 pool
    mode, ax = kernel_mesh_axis(mesh, k_new.shape[2])
    if use and mode != "ref" and _write_lane_gate(k_pages, interpret):
        from gridllm_tpu.ops.pallas_kernels import paged_write_decode

        record_kernel_path("write_decode", True)
        kernel = partial(paged_write_decode, interpret=interpret)
        if mode == "wrap":
            from jax.sharding import PartitionSpec as P

            kernel = _wrap_write_kernel(mesh, ax, kernel,
                                        (P(None), P(None)))
        return kernel(k_pages, v_pages, k_new, v_new, page_idx, offset)
    record_kernel_path("write_decode", False)
    # one scatter over (page, row) applied to every layer: index arrays are
    # adjacent advanced indices after the leading ':' so the result keeps
    # [L, S, KVH, D] — matching k_new's layout
    k_pages = k_pages.at[:, page_idx, offset].set(k_new, mode="drop")
    v_pages = v_pages.at[:, page_idx, offset].set(v_new, mode="drop")
    return k_pages, v_pages


def write_multi_all(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    active: jnp.ndarray,
    page_size: int,
    use_pallas: bool | None = None,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-token append: write T consecutive tokens per slot across ALL
    layers at once (the speculative-verify KV write, ISSUE 5).

    k_pages/v_pages: [L, P, ps, KVH, D]; k_new/v_new: [L, S, T, KVH, D];
    positions: [S, T] absolute write position per (slot, candidate);
    active: [S] bool — inactive slots are dropped entirely. Every hazard
    (inactive slot, past-capacity position, unmapped page) masks to the
    out-of-bounds sentinel exactly like write_decode_all.

    The write is OPTIMISTIC: all T candidate rows land in the pool before
    accept/reject is known. Rejected rows are dropped afterwards by
    rollback_to_length — pure length bookkeeping, no data movement.

    Kernel path: the (slot, candidate) pairs flatten to S*T independent
    rows, which is exactly paged_write_decode's contract (one [KVH, D]
    row per destination, destinations never colliding — positions within
    a slot are consecutive and distinct, pages are slot-exclusive).

    int8 pools (QuantPages): the flattened rows quantize per row and the
    scales scatter alongside, exactly like write_decode_all.
    """
    numcheck.check_finite(
        "kv.write", *(x for x in (k_new, v_new) if x is not None))
    if isinstance(k_pages, QuantPages):
        k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
        n_layers, s, t = k_new.shape[:3]
        pos = positions.reshape(-1)
        slot_of = jnp.repeat(
            jnp.arange(page_table.shape[0], dtype=jnp.int32), t)
        page_idx = _safe_page_idx(
            lambda p: page_table[slot_of, p], pos, jnp.repeat(active, t),
            page_size, page_table.shape[1], k_pages.data.shape[1],
        )
        offset = pos % page_size
        record_kernel_path("write_multi", False)
        kq, ksc = quantize_kv_rows(
            k_new.reshape(n_layers, s * t, *k_new.shape[3:]))
        vq, vsc = quantize_kv_rows(
            v_new.reshape(n_layers, s * t, *v_new.shape[3:]))
        return (
            QuantPages(
                k_pages.data.at[:, page_idx, offset].set(kq, mode="drop"),
                k_pages.scale.at[:, page_idx, offset].set(ksc, mode="drop"),
            ),
            QuantPages(
                v_pages.data.at[:, page_idx, offset].set(vq, mode="drop"),
                v_pages.scale.at[:, page_idx, offset].set(vsc, mode="drop"),
            ),
        )
    k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
    n_layers, s, t = k_new.shape[:3]
    pos = positions.reshape(-1)
    slot_of = jnp.repeat(jnp.arange(page_table.shape[0], dtype=jnp.int32), t)
    page_idx = _safe_page_idx(
        lambda p: page_table[slot_of, p], pos, jnp.repeat(active, t),
        page_size, page_table.shape[1], k_pages.shape[1],
    )
    offset = pos % page_size
    k_flat = k_new.reshape(n_layers, s * t, *k_new.shape[3:])
    if v_pages is None:
        return _write_latent_rows(k_pages, k_flat, page_idx, offset), None
    v_flat = v_new.reshape(n_layers, s * t, *v_new.shape[3:])
    use, interpret = _pallas_mode(use_pallas)
    mode, ax = kernel_mesh_axis(mesh, k_new.shape[3])
    if use and mode != "ref" and _write_lane_gate(k_pages, interpret):
        from gridllm_tpu.ops.pallas_kernels import paged_write_decode

        record_kernel_path("write_multi", True)
        kernel = partial(paged_write_decode, interpret=interpret)
        if mode == "wrap":
            from jax.sharding import PartitionSpec as P

            kernel = _wrap_write_kernel(mesh, ax, kernel,
                                        (P(None), P(None)))
        return kernel(k_pages, v_pages, k_flat, v_flat, page_idx, offset)
    record_kernel_path("write_multi", False)
    k_pages = k_pages.at[:, page_idx, offset].set(k_flat, mode="drop")
    v_pages = v_pages.at[:, page_idx, offset].set(v_flat, mode="drop")
    return k_pages, v_pages


def rollback_to_length(cache: PagedKVCache,
                       new_lengths: jnp.ndarray) -> PagedKVCache:
    """Truncate each slot's valid KV to `new_lengths` — the speculative
    ROLLBACK (ISSUE 5): after a verify step optimistically wrote K+1
    candidate rows (write_multi_all), the accepted length is committed
    here and every rejected row is dropped.

    Dropping is pure bookkeeping, exact by the pool's own invariants:

    - reads: every attention path masks keys at k_pos >= lengths[slot]
      (plus the in-register overlay), so rolled-back rows are invisible —
      the same mechanism that guards stale data in owned-but-unwritten
      page tails;
    - writes: the next decode/verify step writes at the committed
      lengths, overwriting the junk rows in place;
    - prefix cache (PR 3): verify writes only touch positions >= the
      slot's prompt length, strictly past any refcount-shared prefix page
      (shared pages are fully covered by prompt-minus-last-token), so a
      rollback can never corrupt — or expose junk through — a page another
      request shares. Host-side page ownership is untouched: pages are
      allocated to slot capacity at admission and registered for reuse
      only from the final HOST-visible context (engine._finish), which
      never includes rolled-back tokens.
    """
    return dataclasses.replace(cache, lengths=new_lengths)


def commit_tree_path(cache: PagedKVCache,
                     path: jnp.ndarray,
                     active: jnp.ndarray) -> PagedKVCache:
    """Compact the ACCEPTED root-to-leaf path of a tree-verify step into
    contiguous KV rows (ISSUE 18).

    Tree verify writes node i's K/V optimistically at storage position
    ``lengths + i`` (write_multi_all), but node i's LOGICAL position is
    ``lengths + depth[i]`` — a rejected sibling leaves a hole between
    accepted chain rows. ``path[s, j]`` names the tree node whose row
    backs committed position ``lengths[s] + 1 + j`` (0 = no KV: the
    final corrected/bonus token, or beyond n_emit — spec_accept_tree's
    contract). This copies row ``lengths + path[s, j]`` over row
    ``lengths + 1 + j`` for every ``path[s, j] > 0`` and leaves lengths
    untouched (the caller rolls forward with rollback_to_length, same as
    the chain path).

    Safety invariants:

    - all gathers read the ORIGINAL pool and all scatters land via the
      out-of-bounds sentinel (mode="drop"), so overlapping src/dst rows
      and inactive/unmapped hazards are both safe;
    - topological node order (parents[i] < i) gives src >= dst for every
      copy, so the accepted path only ever moves data DOWN toward its
      committed position, never over a row another slot could read —
      pages are slot-exclusive past the prompt, and tree rows start at
      position ``lengths`` >= prompt length, strictly past any
      refcount-shared prefix page (same argument as rollback_to_length);
    - int8 pools (QuantPages) move the quantized data AND the per-row
      scale verbatim — a dequantize/requantize round trip is NOT exact
      (the scale would be recomputed from the row's int8 absmax), so the
      committed row must be bit-identical to the optimistic write.
    """
    s, n = path.shape
    ps = cache.page_size
    table = cache.page_table
    max_pages = table.shape[1]
    pool = cache.k.data if isinstance(cache.k, QuantPages) else cache.k
    num_pages = pool.shape[1]

    j = jnp.arange(n, dtype=jnp.int32)[None, :]
    do = active[:, None] & (path > 0) & (path != j + 1)
    src_pos = (cache.lengths[:, None] + path).reshape(-1)
    dst_pos = (cache.lengths[:, None] + 1 + j).reshape(-1)
    dv = do.reshape(-1)
    slot_of = jnp.repeat(jnp.arange(s, dtype=jnp.int32), n)

    # src: gather clamps out-of-range and wraps -1 entries to a real page,
    # so a hazardous read returns junk — harmless, the matching scatter
    # row is masked to the sentinel below and dropped.
    src_page = table[slot_of, jnp.clip(src_pos // ps, 0, max_pages - 1)]
    src_off = src_pos % ps
    dst_page = _safe_page_idx(
        lambda p: table[slot_of, p], dst_pos, dv, ps, max_pages, num_pages,
    )
    dst_off = dst_pos % ps

    def move(pages):
        if pages is None:          # a latent pool has no V
            return None
        if isinstance(pages, QuantPages):
            return QuantPages(
                pages.data.at[:, dst_page, dst_off].set(
                    pages.data[:, src_page, src_off], mode="drop"),
                pages.scale.at[:, dst_page, dst_off].set(
                    pages.scale[:, src_page, src_off], mode="drop"),
            )
        return pages.at[:, dst_page, dst_off].set(
            pages[:, src_page, src_off], mode="drop")

    return dataclasses.replace(cache, k=move(cache.k), v=move(cache.v))


def write_prefill_all(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    table_row: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    page_size: int,
    use_pallas: bool | None = None,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write a prefill chunk for ONE slot across ALL layers at once.

    k_pages/v_pages: [L, P, ps, KVH, D]; k_new/v_new: [L, T, KVH, D].
    Kernel path (TPU) requires T % page_size == 0 (static check) and
    page-aligned `start` (engine-guaranteed; see paged_write_chunk).
    Under `mesh`: full-manual shard_map, kv-heads split over tp.

    int8 pools (QuantPages): per-row quantize + scale scatter, like
    write_decode_all (scatter path — see the rationale there).
    """
    numcheck.check_finite(
        "kv.write", *(x for x in (k_new, v_new) if x is not None))
    if isinstance(k_pages, QuantPages):
        k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
        t = jnp.arange(k_new.shape[1], dtype=jnp.int32)
        pos = start + t
        page_idx = _safe_page_idx(
            lambda p: table_row[p], pos, t < length, page_size,
            table_row.shape[0], k_pages.data.shape[1],
        )
        offset = pos % page_size
        record_kernel_path("write_prefill", False)
        kq, ksc = quantize_kv_rows(k_new)   # [L, T, KVH, D] / [L, T]
        vq, vsc = quantize_kv_rows(v_new)
        return (
            QuantPages(
                k_pages.data.at[:, page_idx, offset].set(kq, mode="drop"),
                k_pages.scale.at[:, page_idx, offset].set(ksc, mode="drop"),
            ),
            QuantPages(
                v_pages.data.at[:, page_idx, offset].set(vq, mode="drop"),
                v_pages.scale.at[:, page_idx, offset].set(vsc, mode="drop"),
            ),
        )
    k_new, v_new = _pad_new_lanes(k_pages, k_new, v_new)
    use, interpret = _pallas_mode(use_pallas)
    mode, ax = kernel_mesh_axis(mesh, k_new.shape[2])
    if use and mode != "ref" and k_new.shape[1] % page_size == 0 and (
        _write_lane_gate(k_pages, interpret)
    ):
        from gridllm_tpu.ops.pallas_kernels import paged_write_chunk

        record_kernel_path("write_prefill", True)
        kernel = partial(
            paged_write_chunk, page_size=page_size, interpret=interpret
        )
        if mode == "wrap":
            from jax.sharding import PartitionSpec as P

            kernel = _wrap_write_kernel(mesh, ax, kernel,
                                        (P(None), P(), P()))
        return kernel(k_pages, v_pages, k_new, v_new, table_row, start,
                      length)
    record_kernel_path("write_prefill", False)
    t = jnp.arange(k_new.shape[1], dtype=jnp.int32)
    pos = start + t
    page_idx = _safe_page_idx(
        lambda p: table_row[p], pos, t < length, page_size,
        table_row.shape[0], k_pages.shape[1],
    )
    offset = pos % page_size
    k_pages = k_pages.at[:, page_idx, offset].set(k_new, mode="drop")
    if v_pages is not None:        # a latent pool has no V
        v_pages = v_pages.at[:, page_idx, offset].set(v_new, mode="drop")
    return k_pages, v_pages


def gather_kv(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    table_row: jnp.ndarray,
    page_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialize one slot's K/V [max_pages*page_size, KVH, D] from the pool.

    Reference implementation (CPU-testable); the Pallas paged-attention
    kernel reads pages in place instead of materializing. int8 pools
    (QuantPages) dequantize here — float32 out, which the refs cast to
    anyway — so every jnp fallback reads correct values for free.
    """
    rows = jnp.maximum(table_row, 0)
    if isinstance(k_pages, QuantPages):
        pages_k = k_pages.take(rows)              # [maxp, ps, KVH, D] f32
        pages_v = v_pages.take(rows)
    else:
        pages_k = k_pages[rows]                   # [maxp, ps, KVH, D]
        pages_v = v_pages[rows]
    kvh, d = k_pages.shape[-2], k_pages.shape[-1]
    n = table_row.shape[0] * page_size
    return pages_k.reshape(n, kvh, d), pages_v.reshape(n, kvh, d)


def _page_chain_key(parent: bytes, tokens: list[int]) -> bytes:
    """Content-address of one FULL page given its prefix: the hash chain
    hash(parent_hash, page_token_ids). blake2b so collisions are
    cryptographically negligible — a collision here would silently serve
    another prompt's KV."""
    h = hashlib.blake2b(parent, digest_size=16)
    h.update(b" ".join(b"%d" % t for t in tokens))
    return h.digest()


class PageAllocator:
    """Host-side ref-counted page allocator (plain Python, not traced).

    Owns which pages back which slot; the device only sees the resulting
    int32 tables. O(1) alloc/free per page.

    Automatic prefix caching (ISSUE 3): pages holding FULL pages of a
    completed request's context are content-addressed by a hash chain
    (key_i = hash(key_{i-1}, page_i_token_ids)) and, once their refcount
    drops to zero, parked in an LRU of reusable blocks instead of the free
    list. A new request matches its longest cached prefix page-by-page,
    bumps refcounts, and shares those pages copy-free; fresh allocations
    evict from the LRU only when the free list is empty. `cache_pages`
    bounds the LRU (0 disables caching entirely — byte-identical to the
    pre-cache allocator; a negative value means unbounded).

    Sharing is page-aligned and read-only by construction: a matched page
    is fully covered by the new request's prompt minus its last token (the
    last token must run through the model to produce logits), prefill
    starts writing at the page boundary after the match, and decode writes
    land past the prompt — so a shared page is never written while shared,
    and a refcount pins it against eviction for as long as any request
    reads it.
    """

    def __init__(self, num_pages: int, page_size: int,
                 max_pages_per_slot: int, cache_pages: int = 0,
                 model: str = "", snapshots: int = 0):
        # a hybrid family's snapshot pool (RecurrentState.snap_*): entry
        # index by the chain key of the page that ENDS at the snapshot's
        # boundary, least recently saved or restored first. > 0 also caps
        # match_prefix at the deepest matched boundary that has one
        self.snapshots = snapshots
        self._snap_by_key: OrderedDict[bytes, int] = OrderedDict()
        self._snap_free: list[int] = list(range(snapshots - 1, -1, -1))
        # slot -> (page-match tokens, restore tokens, snapshot entry or -1),
        # staged by match_prefix, read by the engine, counted by alloc()
        self._staged_state: dict[int, tuple[int, int, int]] = {}
        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self.cache_pages = cache_pages
        self.model = model or "unknown"
        # Tiered KV cache (ISSUE 11): optional host-tier hooks the engine
        # installs. spill_sink(page, chain_key) fires right before a
        # REGISTERED page is evicted from the reuse LRU (the engine copies
        # the page to host RAM); restore_source(chain_key) is consulted by
        # match_prefix on a chain miss and returns a freshly installed,
        # registered, refcount-0 page id (or None). Both run under the
        # engine's _alloc_lock — the same lock every allocator mutation
        # holds — so the callback may call back into claim_page /
        # register_claimed / unpin_pages safely (RLock).
        self.spill_sink: Any = None
        self.restore_source: Any = None
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self._owned: dict[int, list[int]] = {}
        self._refs: dict[int, int] = {}          # page → owners (≥ 1)
        self._key_of: dict[int, bytes] = {}      # page → registered chain key
        self._page_by_key: dict[bytes, int] = {}  # chain key → page
        self._lru: OrderedDict[int, None] = OrderedDict()  # ref-0 cached pages
        # match accounting staged per slot by match_prefix and committed by
        # the matching alloc() — a pool-exhausted admission retry re-runs
        # match_prefix, and counting there would tally the same prompt's
        # pages once per retry
        self._staged_stats: dict[int, tuple[int, int, bool]] = {}
        # cumulative counters (mirrored into the obs registry); kept as
        # plain ints so the engine can compute a hit rate without reading
        # the registry back
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.cow_copies = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Reusable (refcount-0, content-addressed) pages parked in the LRU."""
        return len(self._lru)

    @property
    def reclaimable_pages(self) -> int:
        """Pages a fresh allocation can obtain: free + evictable cached."""
        return len(self._free) + len(self._lru)

    def pages_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def can_fit(self, num_tokens: int) -> bool:
        """True iff a FRESH slot could ever hold num_tokens: within both the
        per-slot page cap (permanent) and the current free pool (transient)."""
        need = self.pages_for(num_tokens)
        return need <= self.max_pages_per_slot and need <= self.reclaimable_pages

    def fits_slot_cap(self, num_tokens: int) -> bool:
        """Permanent-capacity check only (retrying can't fix a False)."""
        return self.pages_for(num_tokens) <= self.max_pages_per_slot

    def _take_page(self) -> int | None:
        if self._free:
            return self._free.pop()
        if self._lru:  # evict the least-recently-released cached block
            page, _ = self._lru.popitem(last=False)
            self._spill(page)
            self._drop_key(page)
            self.evictions += 1
            _PREFIX_EVICTIONS.inc(model=self.model)
            return page
        return None

    def _spill(self, page: int) -> None:
        """Offer an about-to-be-evicted registered page to the host tier
        (no-op without a sink). A sink failure loses the page from the
        tier — the later match is just a miss — never the eviction."""
        sink = self.spill_sink
        if sink is None:
            return
        key = self._key_of.get(page)
        if key is None:
            return
        try:
            sink(page, key)
        except Exception as e:  # noqa: BLE001 — spill is best-effort
            from gridllm_tpu.utils.logging import get_logger

            get_logger("kvcache").warning(
                "host-tier spill failed; page content lost from tier",
                model=self.model, page=page, error=str(e))

    def _drop_key(self, page: int) -> None:
        key = self._key_of.pop(page, None)
        if key is not None and self._page_by_key.get(key) == page:
            del self._page_by_key[key]
            # one eviction order: a snapshot goes with the page it ends on
            self._drop_snapshot(key)

    def _drop_snapshot(self, key: bytes) -> None:
        entry = self._snap_by_key.pop(key, None)
        if entry is not None:
            self._snap_free.append(entry)
            _STATE_SNAPSHOTS.inc(model=self.model, event="evicted")

    @property
    def snapshots_used(self) -> int:
        return len(self._snap_by_key)

    def state_match(self, slot: int) -> tuple[int, int, int]:
        """(page-match tokens, restore tokens, snapshot entry or -1) of
        the slot's last match_prefix; zeros for a family with no state."""
        return self._staged_state.get(slot, (0, 0, -1))

    def snapshot_entries(self, keys: list[bytes]) -> list[int]:
        """A snapshot entry for each boundary's chain key a chunk launch is
        about to save the state at: -1 where the key already has one (it
        counts as used now), else a free entry or the least recently used
        one's. Registered here, at dispatch: the device runs launches in
        order, so a later admission that restores it reads what this one
        wrote, and pages of an unfinished request match nothing yet."""
        out: list[int] = []
        for key in keys:
            if key in self._snap_by_key:
                self._snap_by_key.move_to_end(key)
                out.append(-1)
                continue
            if not self._snap_free:
                fresh = set(keys)
                old = next((k for k in self._snap_by_key if k not in fresh),
                           None)
                if old is None:
                    out.append(-1)
                    continue
                self._drop_snapshot(old)
            entry = self._snap_free.pop()
            self._snap_by_key[key] = entry
            _STATE_SNAPSHOTS.inc(model=self.model, event="saved")
            out.append(entry)
        return out

    def match_prefix(self, slot: int, token_ids: list[int]) -> int:
        """Pin the longest cached prefix of `token_ids` to a FRESH slot.

        Walks the hash chain one full page at a time, bumping each matched
        page's refcount (removing it from the eviction LRU) and appending
        it to the slot's page list. The match is capped at the last page
        boundary strictly below len(token_ids): the final token must be
        recomputed to produce the sampled-token logits, so a fully-cached
        prompt still prefills its tail. Returns the number of cached
        TOKENS (a multiple of page_size; 0 when caching is off)."""
        if self.cache_pages == 0:
            return 0
        owned = self._owned.setdefault(slot, [])
        if owned:  # match only seeds a fresh slot
            return 0
        ps = self.page_size
        max_full = min((len(token_ids) - 1) // ps, self.max_pages_per_slot)
        key = b""
        matched = 0
        cow = False
        keys: list[bytes] = []
        for i in range(max_full):
            key = _page_chain_key(key, token_ids[i * ps:(i + 1) * ps])
            keys.append(key)
            page = self._page_by_key.get(key)
            if page is None and self.restore_source is not None:
                # tiered KV cache (ISSUE 11): the chain misses in HBM but
                # the host tier may hold the spilled page — the engine
                # callback pages it back in (claim + device write +
                # register) and we keep walking, so a long request's
                # eviction storm costs restores, not cold prefills
                try:
                    page = self.restore_source(key)
                except Exception as e:  # noqa: BLE001 — degrade to cold
                    from gridllm_tpu.utils.logging import get_logger

                    get_logger("kvcache").warning(
                        "host-tier restore failed; cold prefill",
                        model=self.model, error=str(e))
                    page = None
            if page is None:
                break
            self._lru.pop(page, None)
            self._refs[page] = self._refs.get(page, 0) + 1
            owned.append(page)
            matched += 1
        else:
            # whole cap matched: if the NEXT full page is cached too, the
            # request is about to write into a page the cache holds — the
            # partial-tail copy-on-write (rebuilt privately by prefill)
            if (max_full + 1) * ps <= len(token_ids):
                tail_key = _page_chain_key(
                    key, token_ids[max_full * ps:(max_full + 1) * ps])
                cow = tail_key in self._page_by_key
        if self.snapshots:
            # the state must be restored where the pages end: keep the
            # pages up to the deepest matched boundary that has a
            # snapshot, give the rest back (they are computed again, into
            # pages of the slot's own)
            keep = next((i for i in range(matched, 0, -1)
                         if keys[i - 1] in self._snap_by_key), 0)
            entry = -1
            if keep:
                self._snap_by_key.move_to_end(keys[keep - 1])
                entry = self._snap_by_key[keys[keep - 1]]
            for page in owned[keep:]:
                self._release_page(page)
            del owned[keep:]
            self._staged_state[slot] = (matched * ps, keep * ps, entry)
            matched, cow = keep, False
        # stage the accounting; the successful alloc() commits it (an
        # admission that bounces off an exhausted pool retries this whole
        # sequence and must not re-count the same prompt)
        self._staged_stats[slot] = (
            matched, self.pages_for(len(token_ids)), cow)
        return matched * ps

    def _commit_match_stats(self, slot: int) -> None:
        staged = self._staged_stats.pop(slot, None)
        if staged is None:
            return
        matched, prompt_pages, cow = staged
        found, kept, entry = self._staged_state.get(slot, (0, 0, -1))
        if found:
            _STATE_PREFIX.inc(model=self.model, outcome=(
                "hit" if kept == found else "short" if kept else "miss"))
            if found - kept:
                _STATE_REPLAY.inc(found - kept, model=self.model)
            if entry >= 0:
                _STATE_SNAPSHOTS.inc(model=self.model, event="restored")
        self.hits += matched
        self.misses += prompt_pages - matched
        if matched:
            _PREFIX_HITS.inc(matched, model=self.model)
        if prompt_pages - matched:
            _PREFIX_MISSES.inc(prompt_pages - matched, model=self.model)
        if cow:
            self.cow_copies += 1
            _PREFIX_COW.inc(model=self.model)

    def alloc(self, slot: int, num_tokens: int) -> list[int] | None:
        """Ensure `slot` owns enough pages for `num_tokens` total tokens.
        Returns the slot's full page list, or None if the pool is exhausted
        (caller must preempt/queue — mirrors the scheduler holding jobs when
        no worker has capacity, reference JobScheduler.ts:176-204). Pages
        pinned by match_prefix count toward the total; fresh pages come
        from the free list first, then evict the reuse LRU."""
        from gridllm_tpu import faults

        if faults.check("alloc.alloc"):
            # injected pool exhaustion: exercises the caller's requeue/
            # backpressure path without actually draining the pool
            return None
        owned = self._owned.setdefault(slot, [])
        need = self.pages_for(num_tokens) - len(owned)
        if need > self.reclaimable_pages:
            return None
        if need > self.max_pages_per_slot - len(owned):
            return None
        for _ in range(max(0, need)):
            page = self._take_page()
            assert page is not None  # guarded by reclaimable check above
            self._refs[page] = 1
            owned.append(page)
        self._commit_match_stats(slot)
        return owned

    def free(self, slot: int, token_ids: list[int] | None = None) -> None:
        """Release a slot's pages. With `token_ids` (the request's final
        context, prompt + generated — KV fully written on device), full
        pages are first registered under their chain keys so future
        requests can match them. Each page's refcount then drops; at zero a
        registered page parks in the reuse LRU, an unregistered one returns
        to the free list."""
        self._staged_stats.pop(slot, None)  # uncommitted match: retry path
        self._staged_state.pop(slot, None)
        owned = self._owned.pop(slot, [])
        if token_ids is not None and self.cache_pages != 0:
            n_full = min(len(token_ids) // self.page_size, len(owned))
            key = b""
            for i in range(n_full):
                key = _page_chain_key(
                    key, token_ids[i * self.page_size:(i + 1) * self.page_size]
                )
                page = owned[i]
                cur = self._page_by_key.get(key)
                if cur is None and page not in self._key_of:
                    # first holder of this content wins; a page already
                    # registered under another key (matched from cache)
                    # keeps its identity, duplicates stay unregistered and
                    # fall back to the free list on release
                    self._page_by_key[key] = page
                    self._key_of[page] = key
        for page in owned:
            self._release_page(page)

    def _release_page(self, page: int) -> None:
        """Drop one reference to `page`; at zero, park a registered page
        in the reuse LRU (bounded by cache_pages) or return an
        unregistered one to the free list. Shared by free() and
        unpin_pages() so both sides of an export/import pin obey the
        same refcount/LRU rules."""
        refs = self._refs.get(page, 1) - 1
        if refs > 0:
            self._refs[page] = refs
            return
        self._refs.pop(page, None)
        if page in self._key_of:
            self._lru[page] = None  # most-recently released
            cap = self.cache_pages
            while cap > 0 and len(self._lru) > cap:
                old, _ = self._lru.popitem(last=False)
                self._spill(old)
                self._drop_key(old)
                self.evictions += 1
                _PREFIX_EVICTIONS.inc(model=self.model)
                self._free.append(old)
        else:
            self._free.append(page)

    def evict_cached(self, pages: list[int]) -> int:
        """Force-drop refcount-0 cached pages to the free list WITHOUT
        the spill hook — the suspend-to-host park path (engine
        ``park_to_host``) calls this after it has already copied the
        pages into the host tier, which is what actually frees the HBM.
        Pages still pinned by a live request (not in the LRU) are left
        untouched: a shared page must never be freed mid-decode. Returns
        the number of pages dropped."""
        n = 0
        for page in pages:
            if page in self._lru:
                self._lru.pop(page)
                self._drop_key(page)
                self._free.append(page)
                n += 1
        return n

    # -- KV-page migration (ISSUE 7) ----------------------------------------
    #
    # The transfer subsystem moves the cached full-page prefix of a prompt
    # between workers. On the export side pin_prefix/unpin_pages bracket
    # the device gather (a refcount pin keeps the pages from being evicted
    # or handed to a fresh allocation mid-copy); on the import side
    # install_page registers externally produced pages under their chain
    # keys so the very next admission's match_prefix can share them.

    def chain_keys(self, token_ids: list[int],
                   n_pages: int | None = None) -> list[bytes]:
        """Chain keys for the first `n_pages` FULL pages of token_ids.
        Default cap is one page below len (the last token is always
        recomputed — the same cap match_prefix applies, so export and a
        later match agree on coverage); the import side passes the exact
        page count its wire payload covers."""
        ps = self.page_size
        cap = (len(token_ids) - 1) // ps if n_pages is None else n_pages
        cap = min(cap, len(token_ids) // ps)
        keys: list[bytes] = []
        key = b""
        for i in range(cap):
            key = _page_chain_key(key, token_ids[i * ps:(i + 1) * ps])
            keys.append(key)
        return keys

    def pin_prefix(self, token_ids: list[int]) -> tuple[list[int], int]:
        """Bump refcounts on the cached pages covering token_ids' longest
        full-page prefix (no slot involved). Returns (pages, tokens
        covered); release with unpin_pages. Pinned pages leave the
        eviction LRU, so a concurrent admission cannot reclaim them."""
        pages: list[int] = []
        if self.cache_pages == 0:
            return pages, 0
        for key in self.chain_keys(token_ids):
            page = self._page_by_key.get(key)
            if page is None:
                break
            self._lru.pop(page, None)
            self._refs[page] = self._refs.get(page, 0) + 1
            pages.append(page)
        return pages, len(pages) * self.page_size

    def unpin_pages(self, pages: list[int]) -> None:
        for page in pages:
            self._release_page(page)

    def peek_key(self, key: bytes) -> int | None:
        """The page cached under `key`, if any (no state change)."""
        return self._page_by_key.get(key)

    def claim_page(self) -> int | None:
        """Take a pool page for externally imported content, PINNED at
        refcount 1 and deliberately UNREGISTERED: the chain key must not
        become matchable until the page's KV data has actually landed on
        the device (a concurrent admission matching an unwritten page
        would silently decode over garbage). Callers write the data,
        then register_claimed() + unpin_pages(). Returns None when the
        pool has nothing reclaimable."""
        if self.cache_pages == 0:
            return None
        page = self._take_page()
        if page is None:
            return None
        self._refs[page] = 1
        return page

    def register_claimed(self, page: int, key: bytes) -> None:
        """Publish a claimed page under its chain key AFTER its data was
        written. If a concurrent import registered the same content
        first, the first registration wins and this page stays
        unregistered (it returns to the free list on unpin — exactly the
        duplicate rule free() applies)."""
        if key in self._page_by_key or page in self._key_of:
            return
        self._page_by_key[key] = page
        self._key_of[page] = key

    def pages_owned(self, slot: int) -> int:
        """Pages the slot holds now (its table row without the padding)."""
        return len(self._owned.get(slot, ()))

    def table_row(self, slot: int) -> list[int]:
        owned = self._owned.get(slot, [])
        return owned + [-1] * (self.max_pages_per_slot - len(owned))
