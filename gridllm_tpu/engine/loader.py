"""Checkpoint loading: HF safetensors directory → (sharded) param pytree.

SURVEY.md §5.4: the reference has no model checkpointing (models live in
Ollama's store); this is the rebuild's native replacement, and §7 names
"HF checkpoint → sharded-layout loading without host-RAM blowups" a hard
part. Approach:

- safetensors are opened with framework="numpy" → tensors are lazily
  mmap-backed; nothing materializes until sliced.
- per-leaf placement: each finished leaf is `jax.device_put` to its
  NamedSharding immediately, so peak host RAM ≈ one stacked leaf group
  (largest: w_down L×F×E), not the whole checkpoint.
- dtype conversion happens on the way in (bf16 by default).
"""

from __future__ import annotations

import json
import os
import re
import threading
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.obs import default_registry
from gridllm_tpu.utils.config import env_int
from gridllm_tpu.utils.logging import get_logger

log = get_logger("engine.loader")


def _open_safetensors(path: str) -> dict[str, Callable[[], np.ndarray]]:
    """Map HF tensor name → thunk returning the numpy array (mmap-lazy)."""
    from safetensors import safe_open

    files = sorted(
        os.path.join(path, f) for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no *.safetensors under {path}")
    index: dict[str, Callable[[], np.ndarray]] = {}
    for f in files:
        handle = safe_open(f, framework="numpy")
        for name in handle.keys():  # noqa: SIM118 — safe_open has no __iter__
            index[name] = (lambda h, n: lambda: h.get_tensor(n))(handle, name)
    return index


def _name_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    """The family's HF layout contract — owned by the model module
    (llama.HF_MAP / mixtral.HF_MAP) so loader and state-dict converter
    cannot drift. {} is the layer index; an extra {} the expert index."""
    if cfg.family in ("mixtral", "smallthinker"):
        from gridllm_tpu.models import mixtral

        return mixtral.hf_map(cfg)
    if cfg.family == "gemma2":
        from gridllm_tpu.models import gemma

        return gemma.hf_map(cfg)
    from gridllm_tpu.models import llama

    return llama.hf_map(cfg)


def load_checkpoint(
    cfg: ModelConfig,
    path: str,
    dtype=jnp.bfloat16,
    shardings: Any | None = None,
    quantize: str | None = None,
) -> Any:
    """Load an HF checkpoint dir into our stacked-layer pytree.

    `shardings`: optional pytree (from parallel.param_shardings on params of
    the same structure) — each leaf is placed onto its sharding as soon as it
    is assembled. `quantize="int8"`: matmul leaves are quantized HOST-side
    (ops/quant.py) so the bf16 copy never reaches HBM — required for the
    llama3:70b-on-v5e-8 memory budget (BASELINE config #3).
    """
    from gridllm_tpu.models import hf_layout
    from gridllm_tpu.ops.quant import NO_QUANT_SUBTREES, quantize_np_leaf

    if cfg.family in ("kimi_linear", "longcat_flash", "granite_hybrid"):
        raise NotImplementedError(
            f"{cfg.name}: {cfg.family} checkpoints are not read (its "
            "equations are written from the published keys and the report, "
            "the tensor names of no modeling file are here): it is served "
            "on seeded weights")
    idx = _open_safetensors(path)

    def place(pathkeys: tuple[str, ...], arr: np.ndarray):
        if quantize == "int8" and pathkeys[0] not in NO_QUANT_SUBTREES:
            out = quantize_np_leaf(pathkeys[-1], arr)
            if not hasattr(out, "q"):
                out = jnp.asarray(out, dtype)
        else:
            out = jnp.asarray(arr, dtype)
        if shardings is not None:
            s = shardings
            for k in pathkeys:
                s = s[k]
            out = jax.device_put(out, s)
        log.debug("loaded leaf", leaf="/".join(pathkeys), shape=list(out.shape))
        return out

    def get(name: str) -> np.ndarray:
        return idx[name]()

    if cfg.family == "bert_embed":
        from gridllm_tpu.models import bert_embed

        return bert_embed.from_getter(cfg, get, dtype, place)
    if cfg.family == "llava":
        from gridllm_tpu.models import llava

        return llava.from_getter(cfg, get, dtype, place)
    if cfg.family == "deepseek_v2":
        # two stacked trees (the leading dense layers, the routed ones)
        # and re-paired RoPE columns: the family assembles its own
        from gridllm_tpu.models import deepseek

        return deepseek.from_getter(cfg, get, dtype, place)
    if cfg.family == "olmo_hybrid":
        # two stacked trees by the layers' kinds, [periods, ...]
        from gridllm_tpu.models import olmo_hybrid

        return olmo_hybrid.from_getter(cfg, get, dtype, place)
    if cfg.family == "laguna":
        # trees stacked by the layers' kinds and their place in a period
        from gridllm_tpu.models import laguna

        return laguna.from_getter(cfg, get, dtype, place)
    return hf_layout.to_pytree(cfg, get, _name_map(cfg), dtype, place)


def save_checkpoint(params: Any, cfg: ModelConfig, path: str) -> None:
    """Write our pytree back out as a single HF-layout safetensors file
    (round-trip for tests + lets checkpoints produced here load in HF)."""
    from safetensors.numpy import save_file

    from gridllm_tpu.models import hf_layout

    if cfg.family == "deepseek_v2":
        raise NotImplementedError(
            "deepseek_v2 checkpoints are read (models/deepseek.from_getter), "
            "not written: the inverse of its RoPE re-pairing is not here")
    if cfg.family in ("olmo_hybrid", "laguna"):
        raise NotImplementedError(
            f"{cfg.family} checkpoints are read (the family module's "
            "from_getter), not written")
    os.makedirs(path, exist_ok=True)
    if cfg.family == "bert_embed":
        from gridllm_tpu.models import bert_embed

        out = bert_embed.to_hf_tensors(params, cfg)
    else:
        out = hf_layout.to_hf_tensors(params, cfg, _name_map(cfg))
    save_file(out, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({"model_name": cfg.name}, f)


# ---------------------------------------------------------------------------
# Host-RAM weight snapshot tier (ISSUE 20) — the weights twin of the KV
# host tier: unloading a model parks its device params as host numpy
# arrays keyed by checkpoint identity; a later load of the same identity
# restores via host→device transfer instead of re-reading safetensors
# (or re-running init). Capacity-bounded LRU; a miss degrades to the
# normal disk/init path, never an error.

_SNAP_BYTES = default_registry().gauge(
    "gridllm_weight_snapshot_bytes",
    "Host RAM held by parked weight snapshots (engine/loader.py); "
    "bounded by GRIDLLM_WEIGHT_SNAPSHOT_BYTES.",
)
_SNAP_MODELS = default_registry().gauge(
    "gridllm_weight_snapshot_models",
    "Distinct checkpoint identities resident in the weight snapshot "
    "tier (engine/loader.py).",
)
_SNAP_EVENTS = default_registry().counter(
    "gridllm_weight_snapshot_events_total",
    "Weight snapshot tier activity by event: park, hit (restore served "
    "from host RAM), miss (load fell through to disk/init), evict "
    "(LRU capacity pressure).",
    ("event",),
)


class WeightSnapshotTier:
    """LRU of host-side param pytrees, keyed by checkpoint identity.

    Entries survive :meth:`restore` (weights are immutable — the same
    snapshot can warm many future loads); capacity pressure evicts the
    least-recently-touched identity. Thread-safe: parks run on worker
    admin tasks while restores run on engine construction threads.
    """

    def __init__(self, capacity_bytes: int):
        self.capacity_bytes = max(int(capacity_bytes), 0)
        self._entries: OrderedDict[str, tuple[Any, int]] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.parks = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def enabled(self) -> bool:
        return self.capacity_bytes > 0

    @staticmethod
    def _host_copy(params: Any) -> tuple[Any, int]:
        size = 0

        def pull(a):
            nonlocal size
            h = np.asarray(jax.device_get(a))
            size += h.nbytes
            return h

        return jax.tree_util.tree_map(pull, params), size

    def park(self, key: str, params: Any) -> bool:
        """Copy ``params`` to host RAM under ``key``. Returns False when
        the tier is disabled or the snapshot alone exceeds capacity."""
        if not self.enabled:
            return False
        host, size = self._host_copy(params)
        if size > self.capacity_bytes:
            log.info("weight snapshot too large for tier; dropped",
                     key=key, bytes=size, capacity=self.capacity_bytes)
            return False
        with self._lock:
            if key in self._entries:
                _, old = self._entries.pop(key)
                self._bytes -= old
            while self._bytes + size > self.capacity_bytes and self._entries:
                old_key, (_, old_size) = self._entries.popitem(last=False)
                self._bytes -= old_size
                self.evictions += 1
                _SNAP_EVENTS.inc(event="evict")
                log.info("weight snapshot evicted", key=old_key, bytes=old_size)
            self._entries[key] = (host, size)
            self._bytes += size
            self.parks += 1
            self._publish()
        _SNAP_EVENTS.inc(event="park")
        log.info("weight snapshot parked", key=key, bytes=size)
        return True

    def restore(self, key: str) -> Any | None:
        """Host pytree for ``key``, or None on miss. The entry is kept
        (moved to MRU) — callers must not mutate the returned arrays."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                _SNAP_EVENTS.inc(event="miss")
                return None
            self._entries.move_to_end(key)
            self.hits += 1
        _SNAP_EVENTS.inc(event="hit")
        return entry[0]

    def drop(self, key: str) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._bytes -= entry[1]
                self._publish()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            self._publish()

    def _publish(self) -> None:
        _SNAP_BYTES.set(self._bytes)
        _SNAP_MODELS.set(len(self._entries))

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "capacityBytes": self.capacity_bytes,
                "parks": self.parks,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
            }


_tier: WeightSnapshotTier | None = None
_tier_lock = threading.Lock()


def weight_snapshot_tier() -> WeightSnapshotTier:
    """Process-wide tier, sized from GRIDLLM_WEIGHT_SNAPSHOT_BYTES at
    first touch (all engines in a worker share one host-RAM budget)."""
    global _tier
    with _tier_lock:
        if _tier is None:
            _tier = WeightSnapshotTier(env_int("GRIDLLM_WEIGHT_SNAPSHOT_BYTES"))
        return _tier


def reset_weight_snapshot_tier() -> None:
    """Forget the singleton (tests re-read the env on next touch)."""
    global _tier
    with _tier_lock:
        _tier = None
