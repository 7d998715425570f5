"""Routed-experts decoders: Mixtral (BASELINE.md config #4: mixtral:8x7b EP)
and SmallThinker (smallthinker:21b, PR 33); `_moe_mlp` is also
DeepSeek-V2's expert layer (models/deepseek.py, PR 36).

Shares llama's decoder skeleton (attention, norms, paged KV cache) and
swaps the FFN for a top-k routed mixture of experts. One module serves
the families because they differ in data, not in code: the experts'
activation (`cfg.expert_act`: SwiGLU or ReGLU), the state the router reads
(`cfg.router_pre_attn`: llama._ffn hands the hook the pre-attention normed
state), the per-layer window and RoPE pattern (threaded by the skeleton),
the expert width (`cfg.expert_width`: `intermediate_size` unless
`moe_intermediate_size` says otherwise), whether the top-k weights are
renormalised (`cfg.norm_topk_prob`, `cfg.routed_scaling_factor`), shared
experts every token takes (`cfg.num_shared_experts`: `_shared_mlp`, added
once whatever form the routed ones take) and the HF tensor names. The
reference has no MoE (or any model) code — SURVEY.md §2.5 marks expert
parallelism "No … north star names Mixtral 8×7B EP as a target config".

Two forms of the expert layer, one function (tests hold them equal):

- the all-experts einsum (`_moe_mlp_dense`): every expert computes every
  token, non-selected (token, expert) pairs zero-weighted. No dynamic
  shapes, no token dropping, and under GSPMD it shards on the "ep" mesh
  axis (each shard computes its X/ep experts for all tokens, the weighted
  combine is the all-reduce XLA inserts; parallel/sharding.py `we_*`);
- the sorted dispatch (`_moe_mlp_ragged`, `_moe_mlp_ragged_ep`): tokens
  sorted by expert, one `jax.lax.ragged_dot` a projection, top_k row
  FLOPs instead of X.

Which runs (`_use_ragged`): on one chip the all-experts form, at every
row count, for both families. What one TPU v5e chip read
(deploy/tpu_moe_forms.py, jax 0.9.0; PERF.md, PR 33), device time of one
layer's expert products: at smallthinker's 64 experts of 2560 x 768 top-6,
80 rows (a verify launch) 1.00 ms all-experts against 1.74 ms sorted, where
the experts' 755 MB take 0.92 ms at the chip's bandwidth; 1040 rows (a
chunk) 4.07 against 6.6 ms, the all-experts form at the MXU's peak (785
GFLOP) and XLA's `ragged-dot` at a tenth of it. At mixtral's 8 of 4096 x
14336 top-2 (host clock around one call): 4.5 against 7.0 ms at 80 rows,
16.3 against 39.1 at 1040. The price is X/top_k times the arithmetic: a
chunk of 1024 is compute-bound there, and a grouped product that reached
the weights' roofline would be four times faster (PERF.md section 7).
At deepseek-v2-lite's 64 experts of 2048 x 1408 top-6 (PERF.md, PR 36,
call 2; host clock around one call): 80 rows 2.12 ms all-experts against
4.61 ms sorted (the experts' 1,107 MB at 522 GB/s), 528 rows (a chunk of
512 beside 16 decode rows) 3.81 against 9.36 ms: the rule stands for this
shape too.
`GRIDLLM_MOE_RAGGED=on` still forces the sorted dispatch. Under a mesh the
inherited rule stands (the `ep` dispatch from `_RAGGED_MIN_TOKENS` rows
up), not measured: its claims, "top_k-proportional FLOPs per shard" and
that an all-to-all token exchange would buy nothing over replicated
tokens, stand unread.

Routing numerics follow HF `MixtralSparseMoeBlock`: softmax over ALL
expert logits in fp32 → top-k → renormalize the selected weights (the
same numbers as SmallThinker's top-k → softmax over the chosen);
deepseek_v2 keeps the softmax's own weights (`norm_topk_prob` false).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.kvcache import PagedKVCache
from gridllm_tpu.utils.config import env_str

Params = dict[str, Any]


# the sorted dispatch, where it runs (`_use_ragged`), starts at this many
# rows a call: under it the all-experts form is one small einsum
_RAGGED_MIN_TOKENS = 16


def _route(cfg: ModelConfig, lp: Params, r: jnp.ndarray):
    """Router math (HF MixtralSparseMoeBlock order): softmax over ALL
    expert logits in fp32 → top-k → renormalize (unless the config says
    the softmax's own weights stand). Returns (top_w, top_i).
    SmallThinker's order (top-k of the logits, softmax over the chosen)
    gives the same numbers: exp(s_j) / Σ_chosen exp(s), either way."""
    with jax.named_scope("moe_router"):
        probs = jax.nn.softmax(
            jnp.dot(r.astype(jnp.float32), lp["router"].astype(jnp.float32)),
            axis=-1,
        )  # [..., X] fp32 — router math stays fp32 (tiny; routing flips are costly)
        top_w, top_i = jax.lax.top_k(probs, cfg.experts_per_token)
        if cfg.norm_topk_prob:
            top_w = top_w / top_w.sum(axis=-1, keepdims=True)
        elif cfg.routed_scaling_factor != 1.0:
            # deepseek_v2: the softmax's own weights, times a constant
            top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_i


def _act(cfg: ModelConfig):
    """The gate's activation: SiLU (SwiGLU, mixtral) or ReLU (ReGLU)."""
    return {"silu": jax.nn.silu, "relu": jax.nn.relu}[cfg.expert_act]


def _route_stats(cfg: ModelConfig, top_i: jnp.ndarray, live) -> jnp.ndarray:
    """[live token rows routed, experts with at least one live row] of one
    layer, int32[2]: what the engine's gridllm_moe_* counters sum."""
    flat = top_i.reshape(-1, cfg.experts_per_token)
    if live is None:
        live = jnp.ones(flat.shape[:1], bool)
    hit = jnp.zeros((cfg.num_experts,), jnp.int32).at[flat].max(
        jnp.broadcast_to(live.reshape(-1, 1).astype(jnp.int32), flat.shape))
    return jnp.stack([live.sum().astype(jnp.int32), hit.sum()])


def _moe_mlp_dense(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                   top_w, top_i) -> jnp.ndarray:
    """Dense form: every expert computes every token, non-selected pairs
    zero-weighted. One big batched einsum over the stacked expert axis —
    no dynamic shapes, EP-shardable (each "ep" shard computes its X/ep
    experts for all tokens; the combine is the all-reduce XLA inserts).
    X/top_k times the ragged form's row FLOPs and a [T, X, F]
    intermediate: see the module docstring for what the chip read."""
    p = llama._precision(x)
    one_hot = jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
    gates = jnp.einsum("...k,...kx->...x", top_w, one_hot).astype(x.dtype)

    with jax.named_scope("moe_experts"):
        g = jnp.einsum("...e,xef->...xf", x, lp["we_gate"], precision=p)
        u = jnp.einsum("...e,xef->...xf", x, lp["we_up"], precision=p)
        y = _act(cfg)(g) * u * gates[..., None]
        return jnp.einsum("...xf,xfe->...e", y, lp["we_down"], precision=p)


def _moe_mlp_ragged(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                    top_w, top_i) -> jnp.ndarray:
    """Sorted ragged dispatch (VERDICT #7): tokens sorted by expert, then
    ONE grouped matmul per projection via jax.lax.ragged_dot — T·top_k row
    FLOPs instead of the dense form's T·X (4× for 8×7b prefill), exact
    (no capacity factor, no token dropping), static shapes throughout
    (argsort/bincount are fixed-size; raggedness lives in group_sizes
    values, not array shapes). Slower than the all-experts form on one
    v5e chip at every shape read (module docstring), so only
    GRIDLLM_MOE_RAGGED=on runs it there."""
    k, X = cfg.experts_per_token, cfg.num_experts
    lead = x.shape[:-1]
    e = x.shape[-1]
    xf = x.reshape(-1, e)                       # [T, E]
    t = xf.shape[0]

    flat_expert = top_i.reshape(-1)             # [T*k]
    token_idx = jnp.repeat(jnp.arange(t), k)    # [T*k]
    order = jnp.argsort(flat_expert)            # stable → token order kept
    rows = token_idx[order]                     # [T*k] source token per row
    xs = xf[rows]                               # [T*k, E] sorted operand
    group_sizes = jnp.bincount(flat_expert, length=X).astype(jnp.int32)

    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, lp["we_gate"], group_sizes)
        u = jax.lax.ragged_dot(xs, lp["we_up"], group_sizes)
        y = (_act(cfg)(g) * u).astype(x.dtype)
        down = jax.lax.ragged_dot(y, lp["we_down"], group_sizes)  # [T*k, E]

    w = top_w.reshape(-1)[order].astype(x.dtype)              # [T*k]
    out = jnp.zeros((t, e), x.dtype).at[rows].add(down * w[:, None])
    return out.reshape(*lead, e)


def _moe_mlp_ragged_ep(
    cfg: ModelConfig, lp: Params, x: jnp.ndarray, top_w, top_i, mesh
) -> jnp.ndarray:
    """EP ragged dispatch under a mesh (VERDICT r03 next-round #7: the
    meshed dense form paid X/top_k = 4× redundant expert FLOPs exactly
    where EP matters — sharded prefill).

    shard_map over ("ep", "tp"): each shard holds X/ep experts (their
    gate/up/down slabs further split F-wise over tp), runs the SAME sorted
    ragged_dot dispatch as the single-device path but over its LOCAL
    expert range (assignments outside the range sort to the tail, get
    group_sizes 0, and are zero-weighted — NaN-proofed before the
    combine), then one psum over (ep, tp) merges expert contributions and
    the tp partial sums in a single collective. Tokens are replicated into
    the shard (activations are bytes; expert weights are the GBs), so the
    only cross-device traffic is the output psum — an all-to-all token
    exchange is not built (what it would buy on ICI is not measured).

    Per-shard row FLOPs: T·top_k/ep on average vs the dense form's T·X/ep
    — the same 4× saving (8×7b, top_k=2) the single-device ragged path
    gets, now under the mesh.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    k = cfg.experts_per_token
    lead = x.shape[:-1]
    e = x.shape[-1]
    xf = x.reshape(-1, e)
    t = xf.shape[0]
    # routing inputs are replicated — the caller ran the canonical _route
    # ONCE outside the shard_map (routing numerics single-sourced)
    top_w = top_w.reshape(t, k)
    top_i = top_i.reshape(t, k)
    act = _act(cfg)

    def shard_fn(xf, top_w, top_i, wg, wu, wd):
        xl = wg.shape[0]                       # local experts
        lo = jax.lax.axis_index("ep") * xl
        flat = top_i.reshape(-1)               # [T*k] global expert ids
        tok = jnp.repeat(jnp.arange(t), k)
        el = flat - lo
        valid = (el >= 0) & (el < xl)
        order = jnp.argsort(jnp.where(valid, el, xl))  # invalid → tail
        rows = tok[order]
        xs = xf[rows]
        gs = jnp.bincount(
            jnp.where(valid, el, xl), length=xl + 1
        )[:xl].astype(jnp.int32)

        g = jax.lax.ragged_dot(xs, wg, gs)
        u = jax.lax.ragged_dot(xs, wu, gs)
        y = (act(g) * u).astype(xf.dtype)
        d = jax.lax.ragged_dot(y, wd, gs)

        vs = valid[order]
        w = jnp.where(vs, top_w.reshape(-1)[order], 0.0).astype(xf.dtype)
        d = jnp.where(vs[:, None], d, 0)       # rows past all groups
        out = jnp.zeros((t, e), xf.dtype).at[rows].add(d * w[:, None])
        return jax.lax.psum(out, ("ep", "tp"))

    out = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P(), P("ep", None, "tp"), P("ep", None, "tp"),
                  P("ep", "tp", None)),
        out_specs=P(),
    )(xf, top_w, top_i, lp["we_gate"], lp["we_up"], lp["we_down"])
    return out.reshape(*lead, e)


def _shared_mlp(lp: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The shared experts (deepseek_v2): one SwiGLU of num_shared_experts
    x the expert width that every token takes, unweighted."""
    with jax.named_scope("moe_shared"):
        p = llama._precision(x)
        g = jnp.dot(x, lp["ws_gate"], precision=p)
        u = jnp.dot(x, lp["ws_up"], precision=p)
        return jnp.dot(jax.nn.silu(g) * u, lp["ws_down"], precision=p)


def _use_ragged(n_tokens: int, meshed: bool) -> bool:
    """Whether a call of `n_tokens` rows takes the sorted dispatch:
    GRIDLLM_MOE_RAGGED on / off says so; `auto` takes it under a mesh on a
    TPU (the inherited rule, not measured) and nowhere else: one chip read
    the all-experts form faster at every shape, and the CPU's ragged_dot
    is a serial loop over the groups."""
    raw = env_str("GRIDLLM_MOE_RAGGED").lower()
    on = (meshed and jax.default_backend() == "tpu" if raw == "auto"
          else raw in ("1", "on", "true"))
    return on and n_tokens >= _RAGGED_MIN_TOKENS


def _moe_mlp(
    cfg: ModelConfig, mesh, live, lp: Params, x: jnp.ndarray,
    r: jnp.ndarray | None = None,
):
    """Sparse-MoE FFN: x [..., E] → ([..., E], `_route_stats`).

    lp carries router [E, X] and stacked experts we_gate/we_up [X, E, F],
    we_down [X, F, E] (the per-layer slice of the [L, X, ...] leaves), and
    where the family has shared experts ws_gate/ws_up [E, Fs], ws_down
    [Fs, E], whose output is added once whatever form the routed ones
    take. `r`
    is what the router reads (x itself unless the family taps another
    state, llama._ffn); `live` ([...] bool or None) marks the token rows
    that belong to a request.

    Form selection (trace-time, static; `_use_ragged`):
    - meshed + prefill-sized tokens + divisible layout → shard_map EP
      ragged dispatch (top_k-proportional FLOPs per shard; not measured
      on the chip);
    - meshed otherwise (decode-sized batches, indivisible X/F) → dense
      all-experts einsum (EP-shardable via GSPMD, no dynamic shapes);
    - single device → the all-experts form (the faster on the chip at
      every shape read), unless GRIDLLM_MOE_RAGGED=on.
    """
    y, stats = _routed_mlp(cfg, mesh, live, lp, x, r)
    if cfg.num_shared_experts:
        y = y + _shared_mlp(lp, x)
    return y, stats


def _routed_mlp(cfg: ModelConfig, mesh, live, lp: Params, x: jnp.ndarray,
                r: jnp.ndarray | None):
    """The routed experts of `_moe_mlp`, in the form `_use_ragged` picks."""
    top_w, top_i = _route(cfg, lp, x if r is None else r)
    stats = _route_stats(cfg, top_i, live)
    n_tokens = 1
    for s in x.shape[:-1]:
        n_tokens *= s
    ragged = _use_ragged(n_tokens, mesh is not None)
    if mesh is not None:
        ep = mesh.shape.get("ep", 1)
        tp = mesh.shape.get("tp", 1)
        divisible = (
            cfg.num_experts % ep == 0
            and cfg.expert_width % tp == 0
        )
        if ragged and divisible:
            return _moe_mlp_ragged_ep(cfg, lp, x, top_w, top_i, mesh), stats
        return _moe_mlp_dense(cfg, lp, x, top_w, top_i), stats
    if ragged and cfg.use_pallas is not False:
        return _moe_mlp_ragged(cfg, lp, x, top_w, top_i), stats
    return _moe_mlp_dense(cfg, lp, x, top_w, top_i), stats


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _normal_leaf(key, *, shape, scale, dtype):
    """One random leaf under one jit, so that the float32 normals fuse
    with the scale and the cast: eagerly a [L, X, E, F] expert leaf stands
    whole in float32 first (6 GB at 12 layers of 64 x 2560 x 768), which
    a chip that already holds most of the tree cannot give."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params: llama attention skeleton + MoE expert leaves."""
    e, f = cfg.hidden_size, cfg.expert_width
    X, L = cfg.num_experts, cfg.num_layers
    base_key, k_r, k_g, k_u, k_d = jax.random.split(key, 5)
    params = llama.init_params(cfg, base_key, dtype, dense_ffn=False)
    lp = params["layers"]

    def w(k, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return _normal_leaf(k, shape=shape, scale=scale, dtype=dtype)

    lp["router"] = w(k_r, L, e, X, scale=0.02)
    lp["we_gate"] = w(k_g, L, X, e, f)
    lp["we_up"] = w(k_u, L, X, e, f)
    lp["we_down"] = w(k_d, L, X, f, e)
    return params


# the engine asks decode_step / verify_step for their statistics
# (with_stats) and counts them: gridllm_moe_* (obs/perf.py)
STEP_STATS = True


def _mlp_for(cfg: ModelConfig, mesh=None, live=None):
    """llama's feed-forward hook for this family. `live` ([rows] bool, or
    None = all) rides in as a closure: the skeleton never sees it."""
    return partial(_moe_mlp, cfg, mesh, live)


def _rows_live(tokens: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
    """[T] bool: the rows of a padded bucket or chunk that hold a token."""
    return jnp.arange(tokens.shape[0], dtype=jnp.int32) < length


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray | None = None,
    mesh=None,
) -> jnp.ndarray:
    return llama.hidden_states(
        params, cfg, tokens, mlp=_mlp_for(cfg, mesh), seq_lens=seq_lens,
        mesh=mesh,
    )


def forward(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            mesh=None) -> jnp.ndarray:
    return llama.forward(params, cfg, tokens, mlp=_mlp_for(cfg, mesh))


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    attn: llama.AttnFn | None = None,
    mesh=None,
    embeds: jnp.ndarray | None = None,  # family-API uniformity (vision)
) -> tuple[jnp.ndarray, PagedKVCache]:
    return llama.prefill(
        params, cfg, tokens, length, cache, slot, table_row,
        mlp=_mlp_for(cfg, mesh, _rows_live(tokens, length)[None]),
        attn=attn, mesh=mesh, embeds=embeds,
    )


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mesh=None,
    embeds: jnp.ndarray | None = None,  # family-API uniformity (vision)
) -> tuple[jnp.ndarray, PagedKVCache]:
    return llama.prefill_chunk(
        params, cfg, tokens, start, length, cache, slot, table_row,
        mlp=_mlp_for(cfg, mesh, _rows_live(tokens, length)[None]),
        mesh=mesh, embeds=embeds,
    )


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mesh=None,
    with_stats: bool = False,
) -> tuple[jnp.ndarray, PagedKVCache]:
    return llama.decode_step(
        params, cfg, tokens, cache, active,
        mlp=_mlp_for(cfg, mesh, active), mesh=mesh, with_stats=with_stats,
    )


def verify_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mesh=None,
    tree_pos: jnp.ndarray | None = None,
    tree_mask: jnp.ndarray | None = None,
    with_stats: bool = False,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Speculative-verify forward (llama.verify_step contract) with the
    MoE feed-forward routed per candidate token — _moe_mlp is leading-dim
    agnostic, so the [S, T, E] verify stream routes like prefill's (and
    the tree-verify args pass straight through). Every candidate row of
    an active slot is live: which drafts will be accepted is not known
    until the logits are."""
    live = jnp.broadcast_to(active[:, None], tokens.shape)
    return llama.verify_step(
        params, cfg, tokens, cache, active, mlp=_mlp_for(cfg, mesh, live),
        mesh=mesh, tree_pos=tree_pos, tree_mask=tree_mask,
        with_stats=with_stats,
    )


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    chunk_tokens: jnp.ndarray,
    chunk_start: jnp.ndarray,
    chunk_len: jnp.ndarray,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, PagedKVCache]:
    """Fused chunked-prefill + decode step (llama.mixed_step contract);
    the flat [C+S, E] ragged token batch routes through the MoE exactly
    like any other leading-dim layout."""
    live = jnp.concatenate([_rows_live(chunk_tokens, chunk_len), active])
    return llama.mixed_step(
        params, cfg, chunk_tokens, chunk_start, chunk_len, slot, table_row,
        tokens, cache, active, mlp=_mlp_for(cfg, mesh, live[None]),
        mesh=mesh, embeds=embeds,
    )


# ---------------------------------------------------------------------------
# HF weight conversion (layout contract with transformers MixtralForCausalLM)
# ---------------------------------------------------------------------------

# Same single-source-of-truth scheme as llama.HF_MAP (w1=gate, w2=down,
# w3=up per HF MixtralBlockSparseTop2MLP); engine/loader.py reads this.
HF_MAP: dict[str, tuple[str, bool]] = {
    **{k: v for k, v in llama.HF_MAP.items()
       if k not in ("w_gate", "w_up", "w_down")},
    "router": ("model.layers.{}.block_sparse_moe.gate.weight", True),
    "we_gate": ("model.layers.{}.block_sparse_moe.experts.{}.w1.weight", True),
    "we_down": ("model.layers.{}.block_sparse_moe.experts.{}.w2.weight", True),
    "we_up": ("model.layers.{}.block_sparse_moe.experts.{}.w3.weight", True),
}


# SmallThinkerForCausalLM as PowerInfer published it (modeling file of the
# checkpoint's repository; not in this transformers): same attention and
# norm names, `primary_router`, experts with `gate`/`up`/`down`.
_ST = "model.layers.{}.block_sparse_moe."
SMALLTHINKER_HF_MAP: dict[str, tuple[str, bool]] = {
    **{k: v for k, v in HF_MAP.items() if not k.startswith(("we_", "router"))},
    "router": (_ST + "primary_router.weight", True),
    "we_gate": (_ST + "experts.{}.gate.weight", True),
    "we_up": (_ST + "experts.{}.up.weight", True),
    "we_down": (_ST + "experts.{}.down.weight", True),
}


def hf_map(cfg: ModelConfig) -> dict[str, tuple[str, bool]]:
    return SMALLTHINKER_HF_MAP if cfg.family == "smallthinker" else HF_MAP


def convert_hf_state_dict(cfg: ModelConfig, sd: dict[str, Any], dtype=jnp.bfloat16) -> Params:
    """HF `MixtralForCausalLM.state_dict()` (or SmallThinker's) → our pytree."""
    return llama.convert_state_dict(cfg, sd, hf_map(cfg), dtype)
