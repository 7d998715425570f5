"""Laguna decoder (laguna-xs2:33b, PR 45): attention layers of two SHAPES
(window layers with more query heads, global layers with fewer; a RoPE
rule each; a sigmoid gate a head), a leading dense layer, then routed
experts behind a sigmoid router with a shared expert.

A module of its own, not the llama skeleton widened: the skeleton's
scan stacks layers of ONE shape and threads window and RoPE as traced
scalars, and `wq`, `wo` and the gate of a 48-head and of a 64-head layer
cannot share a stack. The layers are stacked BY KIND: `dense` the leading
dense layers (global), then whole periods of window layers and a global
one: `win`, a tuple with one stacked tree [periods, ...] for each window
place of the period, `glob` the global layers' [periods, ...], and `tail`,
a tuple of [1, ...] trees for window layers behind the last whole period
(three at the published depth of 40, none at a depth of 1 + 4n).
benchmark/reference/laguna_f32.py states the equations. What is shared is
called, not copied: the expert layer is `mixtral._moe_mlp` (the sigmoid
scores and the shared expert are data of the config there), the dense one
`llama._mlp`, the head `llama._unembed`, and every read of a pool is
`ops.attention.ragged_paged_attention`.

Two kinds of cache, one for each kind of layer (ModelConfig.ring_layers):
a global layer's K and V go to the page pool (whose leading axis is the
dense and the global layers only), a window layer's to the slot's RING
(`PagedKVCache.win`, ops/kvcache.WindowRing): a window, a launch's rows
and a page, whatever the context's length. The ring is read and written
through a page table like the pool (`WindowRing.table`), so the kernels
are the ones every family runs, at this family's two group sizes. A chunk
launch hands the ring's last pages back at up to `SAVES` page boundaries
it passes (`state_io`: the prefix cache's snapshots, as
models/olmo_hybrid.py does for a state), and an admission from cached
pages starts from a snapshot the engine restored (`restore_snapshot`).

One layer body (`_layer`) and one stack runner (`_stack`) serve every
phase; a phase is an `attend` closure. The entry points are the ones an
engine launches, and `validate_mesh` refuses every mesh: `hidden_states`
(/api/embed), `decode_step`, `verify_step` and `mixed_step`, which admits
every prompt chunk by chunk. There is no `prefill` / `prefill_chunk`: only
an `sp` or `pp` engine calls those.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama, mixtral
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.attention import attention_prefill, ragged_paged_attention
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    WindowRing,
    write_decode_all,
    write_multi_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import (
    apply_rope,
    precompute_rope,
    rms_norm,
    yarn_factors,
)

Params = dict[str, Any]

# the engine asks decode_step / verify_step for the routed statistics
STEP_STATS = True
# page boundaries one chunk launch can hand the ring's pages back at
SAVES = 2

# attend(window: bool, li, q [B,T,H,D], k, v [B,T,KVH,D]) -> [B,T,H,D]
Attend = Callable[..., jnp.ndarray]


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: no sharding of the two stacks or of the rings has been
    written or proved. Refused rather than run unproved."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: laguna is served on one device only (no sharding "
            "of layers stacked by kind, or of the window layers' rings, "
            "has been written)")


def layout(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(leading dense layers, period, whole periods, window layers behind
    them) of the layer pattern: dense global layers, then periods of
    window layers that end in a global one. Anything else is refused."""
    kd, win = cfg.first_k_dense, tuple(int(w > 0) for w in cfg.layer_windows)
    rest = win[kd:]
    per = rest.index(0) + 1 if 0 in rest else len(rest) + 1
    n, tail = divmod(len(rest), per)
    one = (1,) * (per - 1) + (0,)
    heads = cfg.heads_layout
    if (any(win[:kd]) or rest != one * n + (1,) * tail or per < 2
            or len({heads[i] for i, w in enumerate(win) if w}) > 1
            or len({heads[i] for i, w in enumerate(win) if not w}) > 1):
        raise ValueError(
            f"{cfg.name}: the layers are not dense global layers, then "
            f"periods of window layers ending in a global one, each kind "
            f"of one head count: {win}, {heads}")
    return kd, per, n, tail


def kind_heads(cfg: ModelConfig, window: bool) -> int:
    """Query heads of a window layer or of a global one."""
    return next(h for h, w in zip(cfg.heads_layout, cfg.layer_windows)
                if bool(w) == window)


def new_ring(cfg: ModelConfig, slots: int, launch_rows: int, snapshots: int,
             page_size: int, head_dim: int, dtype=jnp.bfloat16) -> WindowRing:
    """The window layers' rings for every slot, a launch of `launch_rows`
    wide, with a pool of `snapshots` behind them."""
    return WindowRing.create(
        cfg.ring_layers, slots, cfg.ring_pages(launch_rows, page_size),
        -(-cfg.sliding_window // page_size), snapshots, page_size,
        cfg.num_kv_heads, head_dim, dtype)


def restore_snapshot(cache: PagedKVCache, slot, entry, at) -> PagedKVCache:
    """A prefix-cache admission's rings: snapshot `entry`, taken at
    position `at`, copied into the slot ahead of its first chunk launch."""
    return dataclasses.replace(
        cache, win=cache.win.restore(slot, entry, at, cache.page_size))


# ---------------------------------------------------------------------------
# one layer, one stack
# ---------------------------------------------------------------------------


def _rotations(cfg: ModelConfig):
    """{window?: (inverse frequencies of the values that rotate, cos/sin
    multiplier)}: the global layers' rule (YaRN on part of a head) and the
    window layers' (the whole head, unscaled)."""
    d = cfg.head_dim_
    rot = int(d * cfg.partial_rotary_factor)
    return {
        False: (precompute_rope(rot, cfg.rope_theta, cfg.rope_scaling),
                yarn_factors(cfg.rope_scaling)[1]),
        True: (precompute_rope(d, cfg.window_rope_theta or cfg.rope_theta),
               1.0),
    }


def _rope(x: jnp.ndarray, pos: jnp.ndarray, rotation) -> jnp.ndarray:
    """x [..., T, H, D] with the first 2 len(inv_freq) values of each head
    rotated (split halves), cos and sin times the multiplier."""
    inv_freq, mult = rotation
    r = 2 * inv_freq.shape[0]
    # rotated and scaled in float32, rounded once
    turned = (apply_rope(x[..., :r].astype(jnp.float32), pos, inv_freq)
              * mult).astype(x.dtype)
    return turned if r == x.shape[-1] else jnp.concatenate(
        [turned, x[..., r:]], axis=-1)


def _layer(cfg: ModelConfig, lp: Params, x, pos, rotation, attend: Attend,
           window: bool, li, mlp):
    """One decoder layer on x [B, T, E] -> (x, its K and V [B, T, KVH, D],
    the feed-forward's statistics). The layer's heads are its weights'."""
    p = llama._precision(x)
    d, kvh = cfg.head_dim_, cfg.num_kv_heads
    pre = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q = jnp.dot(pre, lp["wq"], precision=p)
    q = _rope(q.reshape(*q.shape[:-1], -1, d), pos, rotation)
    k = jnp.dot(pre, lp["wk"], precision=p).reshape(*x.shape[:-1], kvh, d)
    k = _rope(k, pos, rotation)
    v = jnp.dot(pre, lp["wv"], precision=p).reshape(*x.shape[:-1], kvh, d)
    att = attend(window, li, q, k, v)
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                jnp.dot(pre, lp["wg"], precision=p).astype(jnp.float32))
            att = (att.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    x = x + jnp.dot(att.reshape(*x.shape[:-1], -1), lp["wo"], precision=p)
    y, stats = mlp(lp, rms_norm(x, lp["mlp_norm"], cfg.rms_eps))
    return x + y, (k, v), stats


def _stack(params: Params, cfg: ModelConfig, x, pos, attend: Attend,
           mesh=None, live=None):
    """Every layer on x [B, T, E]: the leading dense layers one by one,
    ONE scan over the whole periods, the window layers behind them one by
    one. Returns (x, the pool layers' (K, V) [Lg, B, T, KVH, D] (dense,
    then global), the ring layers' [Lw, ...], the routed statistics [2])."""
    kd, per, n, tail = layout(cfg)
    rot = _rotations(cfg)
    moe = partial(mixtral._moe_mlp, cfg, mesh, live)

    def dense_mlp(lp, h):
        return llama._mlp(lp, h), None

    def at(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    def stacked(kvs):
        return jax.tree.map(lambda *a: jnp.stack(a), *kvs)

    pool_kv, ring_kv = [], []
    for i in range(kd):
        x, kv, _ = _layer(cfg, at(params["dense"], i), x, pos, rot[False],
                          attend, False, jnp.int32(i), dense_mlp)
        pool_kv.append(kv)
    pool_kv = [stacked(pool_kv)] if pool_kv else []

    def body(x, xs):
        win_p, glob_p, pi = xs
        kvs, stats = [], jnp.zeros((2,), jnp.int32)
        # beside each period's slice the whole stack and the period's
        # index, for the grouped experts' kernel
        for j, lp in enumerate(win_p):
            lp = {**lp, "layer_stack": (params["win"][j], pi)}
            x, kv, st = _layer(cfg, lp, x, pos, rot[True], attend, True,
                               pi * (per - 1) + j, moe)
            kvs.append(kv)
            stats = stats + st
        glob_p = {**glob_p, "layer_stack": (params["glob"], pi)}
        x, gkv, st = _layer(cfg, glob_p, x, pos, rot[False], attend, False,
                            kd + pi, moe)
        return x, (stacked(kvs), gkv, stats + st)

    stats = jnp.zeros((2,), jnp.int32)
    if n:
        x, (wkv, gkv, st) = jax.lax.scan(
            body, x,
            (params["win"], params["glob"], jnp.arange(n, dtype=jnp.int32)))
        ring_kv.append(jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), wkv))
        pool_kv.append(gkv)
        stats = st.sum(axis=0)
    tails = []
    for j in range(tail):
        x, kv, st = _layer(cfg, at(params["tail"][j], 0), x, pos, rot[True],
                           attend, True, jnp.int32(n * (per - 1) + j), moe)
        tails.append(kv)
        stats = stats + st
    if tails:
        ring_kv.append(stacked(tails))

    def joined(parts):
        return jax.tree.map(lambda *a: jnp.concatenate(a), *parts)

    return x, joined(pool_kv), joined(ring_kv), stats


# ---------------------------------------------------------------------------
# the phases: each an `attend` over `_stack`
# ---------------------------------------------------------------------------


def hidden_states(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                  seq_lens: jnp.ndarray | None = None, mesh=None) -> jnp.ndarray:
    """Final-norm hidden states [B, T, E], cache-free: plain causal
    attention, a window layer over its window."""
    b, t = tokens.shape
    x = params["embed"][tokens]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    if seq_lens is None:
        seq_lens = jnp.full((b,), t, jnp.int32)

    def attend(window, li, q, k, v):
        return attention_prefill(
            q, k, v, seq_lens, use_pallas=cfg.use_pallas,
            window=cfg.sliding_window if window else 0, mesh=mesh)

    x, _, _, _ = _stack(params, cfg, x, pos, attend, mesh,
                        pos < seq_lens[:, None])
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None) -> jnp.ndarray:
    """Cache-free full forward: tokens [B, T] -> logits [B, T, V] (fp32)."""
    return llama._unembed(cfg, params, hidden_states(params, cfg, tokens,
                                                     mesh=mesh))


def _pools(cfg: ModelConfig, cache: PagedKVCache, window: bool):
    """(K pool, V pool, page table, window) a layer of this kind reads."""
    if window:
        win = cache.win
        return (win.k, win.v, win.table(cache.page_table.shape[1]),
                cfg.sliding_window)
    return cache.k, cache.v, cache.page_table, 0


def mixed_step(params: Params, cfg: ModelConfig, chunk_tokens, chunk_start,
               chunk_len, slot, table_row, tokens, cache, active, mesh=None,
               embeds=None, state_io=None):
    """One fused chunked-prefill + decode step (llama.mixed_step's
    contract): rows [0, C) the admitting slot's chunk against its cached
    prefix and its ring, rows [C, C + S) one decode token a slot, one
    ragged launch a layer. Writes both pools. `state_io` = (positions
    [SAVES], snapshot entries [SAVES]): page boundaries this chunk passes
    at which the ring's last pages are saved."""
    c = chunk_tokens.shape[0]
    ps, win = cache.page_size, cache.win
    assert c <= (win.ring_pages - 1) * ps - cfg.sliding_window, (
        f"a chunk of {c} rows in a ring of {win.ring_pages} pages")
    dt = params["embed"].dtype
    xc = params["embed"][chunk_tokens] if embeds is None else embeds
    x = jnp.concatenate([xc.astype(dt), params["embed"][tokens]])
    total = chunk_start + chunk_len
    positions = cache.lengths
    pos = jnp.concatenate(
        [chunk_start + jnp.arange(c, dtype=jnp.int32), positions])
    live = jnp.concatenate([jnp.arange(c) < chunk_len, active])
    ring_row = win.row(slot, table_row.shape[0])

    def attend(window, li, q, k, v):
        k_pool, v_pool, table, w = _pools(cfg, cache, window)
        oc, og = ragged_paged_attention(
            k_pool, v_pool, ps, layer=li, use_pallas=cfg.use_pallas,
            window=w, mesh=mesh,
            q_chunk=q[:, :c], chunk_row=ring_row if window else table_row,
            chunk_start=chunk_start, chunk_total=total, k_chunk=k[0, :c],
            v_chunk=v[0, :c],
            q_group=q[0, c:, None], page_table=table,
            group_lengths=positions, k_group=k[0, c:, None],
            v_group=v[0, c:, None])
        return jnp.concatenate([oc[0], og[:, 0]])[None]

    x, (kg, vg), (kw, vw), _ = _stack(
        params, cfg, x[None], pos[None], attend, mesh, live[None])
    kg, vg, kw, vw = (z[:, 0] for z in (kg, vg, kw, vw))
    # region writes target disjoint pages (the admitting slot is not yet
    # active), so the order is immaterial
    k_pool, v_pool = write_prefill_all(
        cache.k, cache.v, kg[:, :c], vg[:, :c], table_row, chunk_start,
        chunk_len, ps, use_pallas=cfg.use_pallas, mesh=mesh)
    wk, wv = write_prefill_all(
        win.k, win.v, kw[:, :c], vw[:, :c], ring_row, chunk_start, chunk_len,
        ps, use_pallas=cfg.use_pallas, mesh=mesh)
    k_pool, v_pool = write_decode_all(
        k_pool, v_pool, kg[:, c:], vg[:, c:], cache.page_table, positions,
        active, ps, use_pallas=cfg.use_pallas, mesh=mesh)
    wk, wv = write_decode_all(
        wk, wv, kw[:, c:], vw[:, c:], win.table(table_row.shape[0]),
        positions, active, ps, use_pallas=cfg.use_pallas, mesh=mesh)
    win = dataclasses.replace(win, k=wk, v=wv)
    if state_io is not None:
        win = win.save(slot, *state_io, ps)
    x = rms_norm(x[0], params["final_norm"], cfg.rms_eps)
    chunk_logits = llama._unembed(cfg, params, x[jnp.maximum(chunk_len - 1, 0)])
    dec_logits = llama._unembed(cfg, params, x[c:])
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    ).at[slot].set(total)
    return chunk_logits, dec_logits, dataclasses.replace(
        cache, k=k_pool, v=v_pool, win=win,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=new_lengths)


def _step_launch(params: Params, cfg: ModelConfig, tokens, cache, active,
                 mesh=None):
    """t rows of every slot (decode: 1, verify: K + 1) at positions
    lengths + i, written optimistically to both pools. Returns (final-norm
    x [S, t, E], the cache with lengths unchanged, the routed statistics)."""
    s, t = tokens.shape
    ps, win = cache.page_size, cache.win
    base = cache.lengths
    pos = base[:, None] + jnp.arange(t, dtype=jnp.int32)[None]

    def attend(window, li, q, k, v):
        k_pool, v_pool, table, w = _pools(cfg, cache, window)
        return ragged_paged_attention(
            k_pool, v_pool, ps, layer=li, use_pallas=cfg.use_pallas,
            window=w, mesh=mesh, q_group=q, page_table=table,
            group_lengths=base, k_group=k, v_group=v)[1]

    x, (kg, vg), (kw, vw), stats = _stack(
        params, cfg, params["embed"][tokens], pos, attend, mesh,
        jnp.broadcast_to(active[:, None], tokens.shape))
    k_pool, v_pool = write_multi_all(
        cache.k, cache.v, kg, vg, cache.page_table, pos, active, ps,
        use_pallas=cfg.use_pallas, mesh=mesh)
    wk, wv = write_multi_all(
        win.k, win.v, kw, vw, win.table(cache.page_table.shape[1]), pos,
        active, ps, use_pallas=cfg.use_pallas, mesh=mesh)
    return rms_norm(x, params["final_norm"], cfg.rms_eps), dataclasses.replace(
        cache, k=k_pool, v=v_pool,
        win=dataclasses.replace(win, k=wk, v=wv)), stats


def decode_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, with_stats: bool = False):
    """One decode step for ALL slots (llama.decode_step's contract)."""
    x, new, stats = _step_launch(params, cfg, tokens[:, None], cache, active,
                                 mesh)
    logits = llama._unembed(cfg, params, x[:, 0])
    new = dataclasses.replace(new, lengths=jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context))
    return (logits, new, stats) if with_stats else (logits, new)


def verify_step(params: Params, cfg: ModelConfig, tokens, cache, active,
                mesh=None, tree_pos=None, tree_mask=None,
                with_stats: bool = False):
    """One speculative-verify forward for ALL slots (llama.verify_step's
    contract: candidates written optimistically, lengths unchanged; a
    rejected row of a ring is overwritten in place by the next launch, as
    a page's is)."""
    if tree_pos is not None or tree_mask is not None:
        raise NotImplementedError(
            f"{cfg.name}: tree verification is not served for the window "
            "layers' rings (commit_tree_path moves rows of the one pool)")
    x, new, stats = _step_launch(params, cfg, tokens, cache, active, mesh)
    logits = llama._unembed(cfg, params, x)
    return (logits, new, stats) if with_stats else (logits, new)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights)."""
    e, v, d = cfg.hidden_size, cfg.vocab_size, cfg.head_dim_
    kvh, X, f = cfg.num_kv_heads, cfg.num_experts, cfg.expert_width
    fs = cfg.num_shared_experts * f
    kd, per, n, tail = layout(cfg)
    ks = iter(jax.random.split(key, 16 * (per + tail + 2)))

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return mixtral._normal_leaf(
            next(ks), shape=shape, scale=scale, dtype=dtype)

    def attention(m: int, window: bool) -> Params:
        h = kind_heads(cfg, window)
        out = {
            "attn_norm": jnp.ones((m, e), dtype),
            "wq": w(m, e, h * d), "wk": w(m, e, kvh * d),
            "wv": w(m, e, kvh * d), "wo": w(m, h * d, e),
            "mlp_norm": jnp.ones((m, e), dtype),
        }
        if cfg.attn_gate:
            out["wg"] = w(m, e, h)
        return out

    def sparse(m: int, window: bool) -> Params:
        out = {
            **attention(m, window),
            "router": w(m, e, X, scale=0.02),
            "we_gate": w(m, X, e, f), "we_up": w(m, X, e, f),
            "we_down": w(m, X, f, e),
        }
        if fs:
            out.update(ws_gate=w(m, e, fs), ws_up=w(m, e, fs),
                       ws_down=w(m, fs, e))
        return out

    fd = cfg.intermediate_size
    params: Params = {
        "embed": w(v, e, scale=0.02),
        "dense": {**attention(kd, False), "w_gate": w(kd, e, fd),
                  "w_up": w(kd, e, fd), "w_down": w(kd, fd, e)},
        "win": tuple(sparse(n, True) for _ in range(per - 1)),
        "glob": sparse(n, False),
        "tail": tuple(sparse(1, True) for _ in range(tail)),
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# HF weight conversion (the tensor names are the llama family's and the
# usual routed block's, ASSUMED: the modeling file is not here)
# ---------------------------------------------------------------------------

_L = "model.layers.{}."
ATTN_HF_MAP: dict[str, tuple[str, bool]] = {
    "attn_norm": (_L + "input_layernorm.weight", False),
    "wq": (_L + "self_attn.q_proj.weight", True),
    "wk": (_L + "self_attn.k_proj.weight", True),
    "wv": (_L + "self_attn.v_proj.weight", True),
    "wo": (_L + "self_attn.o_proj.weight", True),
    "wg": (_L + "self_attn.g_proj.weight", True),
    "mlp_norm": (_L + "post_attention_layernorm.weight", False),
}
DENSE_HF_MAP = {
    **ATTN_HF_MAP,
    "w_gate": (_L + "mlp.gate_proj.weight", True),
    "w_up": (_L + "mlp.up_proj.weight", True),
    "w_down": (_L + "mlp.down_proj.weight", True),
}
SPARSE_HF_MAP = {
    **ATTN_HF_MAP,
    "router": (_L + "mlp.gate.weight", True),
    "ws_gate": (_L + "mlp.shared_expert.gate_proj.weight", True),
    "ws_up": (_L + "mlp.shared_expert.up_proj.weight", True),
    "ws_down": (_L + "mlp.shared_expert.down_proj.weight", True),
}
_EXPERTS = {"we_gate": "gate_proj", "we_up": "up_proj", "we_down": "down_proj"}


def from_getter(cfg: ModelConfig, get, dtype=jnp.bfloat16, place=None) -> Params:
    """The pytree from `get(published tensor name) -> host array`: a
    stacked tree for each place in the period."""
    import numpy as np

    if place is None:
        def place(path, arr):
            return jnp.asarray(arr, dtype)

    kd, per, n, tail = layout(cfg)

    def leaf(tmpl, tr, i):
        a = np.asarray(get(tmpl.format(i)))
        return a.T if tr else a

    def tree(name, name_map, layers):
        out = {key: place((name, key), np.stack(
            [leaf(tmpl, tr, i) for i in layers]))
            for key, (tmpl, tr) in name_map.items()
            if cfg.attn_gate or key != "wg"}
        if name_map is SPARSE_HF_MAP:
            for key, proj in _EXPERTS.items():
                tmpl = _L + "mlp.experts.{}." + proj + ".weight"
                out[key] = place((name, key), np.stack([np.stack(
                    [np.asarray(get(tmpl.format(i, x))).T
                     for x in range(cfg.num_experts)]) for i in layers]))
        return out

    def at(pi, j):
        return kd + pi * per + j

    params: Params = {
        "embed": place(("embed",), np.asarray(get("model.embed_tokens.weight"))),
        "dense": tree("dense", DENSE_HF_MAP, range(kd)),
        "win": tuple(tree(f"win{j}", SPARSE_HF_MAP,
                          [at(pi, j) for pi in range(n)])
                     for j in range(per - 1)),
        "glob": tree("glob", SPARSE_HF_MAP,
                     [at(pi, per - 1) for pi in range(n)]),
        "tail": tuple(tree(f"tail{j}", SPARSE_HF_MAP, [at(n, j)])
                      for j in range(tail)),
        "final_norm": place(("final_norm",), np.asarray(get("model.norm.weight"))),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = place(
            ("lm_head",), np.asarray(get("lm_head.weight")).T)
    return params
