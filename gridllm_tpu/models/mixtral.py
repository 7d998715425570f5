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

Four forms of the expert layer, one function (tests hold them equal):

- the all-experts einsum (`_moe_mlp_dense`): every expert computes every
  token, non-selected (token, expert) pairs zero-weighted. No dynamic
  shapes, no token dropping, and under GSPMD it shards on the "ep" mesh
  axis (each shard computes its X/ep experts for all tokens, the weighted
  combine is the all-reduce XLA inserts; parallel/sharding.py `we_*`);
- the sorted dispatch (`_moe_mlp_ragged`, `_moe_mlp_ragged_ep`): tokens
  sorted by expert, one `jax.lax.ragged_dot` a projection, top_k row
  FLOPs instead of X;
- the grouped product (`_moe_mlp_grouped`, PR 53): the all-experts form's
  mathematics over the experts a LIVE row touched and no others, one
  Pallas kernel (ops/pallas_kernels.py `grouped_experts`): an expert's
  gate, up and down slabs arrive once by double-buffered DMA from the
  stacked leaves as they are stored, all rows multiply each (a row that
  did not pick the expert has a gate of zero: rows are neither sorted nor
  gathered), float32 sums over F and over experts. The touched set is
  `_touched`, which is also what `_route_stats` counts;
- that kernel's sorted regime (`_moe_mlp_grouped_sorted`, PR 58): the
  picks laid out by expert in XLA (ops/experts.py `sorted_layout`: a
  running count a group, no sort; every group starts at a multiple of the
  row tile, so a tile belongs to one expert), the rows gathered, one
  Pallas product over the row tiles in use (ops/pallas_kernels.py
  `grouped_experts_sorted`, the custom call named `grouped_experts` like
  the other: a tile's expert from scalar prefetch indexes the stacked
  leaves, so an expert's slabs arrive once and meet only the rows that
  PICKED it; float32 sums over F), then a row's picks gathered back,
  weighted in float32, summed in float32 and cast once. A pick of an
  absent or zero-compute expert, and every pick of a row that is not
  live, is in no group.

Which runs on one chip (`expert_form`) is a rule of the SHAPE: the rows
of the call. Under the chip's ridge (197 TFLOP/s over 819 GB/s = 240
rows: a verify launch's 80, a decode launch's 16) the products are bound
by the bytes they read, all rows against one expert is less arithmetic
than its slabs take to arrive, and the grouped form reads the fewest
bytes: it runs there. From 240 rows (a mixed launch's 528) that
arithmetic binds (at 528 rows it is what the all-experts form does), and
the sorted regime runs: the arithmetic the picks need, against slabs read
once. The row tile follows the rows a group is expected to have (rows x
top-k over the router's width, to the next power of two between 16 and
256: `sorted_tile_rows`); a tile under 128 rows costs the MXU what 128
do, so tiles much smaller than a group only multiply the passes. What one
TPU v5e chip read (deploy/tpu_moe_forms.py, jax 0.9.0), one layer's
expert products, first the two forms XLA builds:

| experts (E x F), top-k, X/k | rows | all-experts | sorted | source |
| --- | --- | --- | --- | --- |
| 64 (2560 x 768), 6, 10.7 | 80 | 1.00 ms | 1.74 ms | PR 33, device time |
| | 1040 | 4.07 | 6.6 | the experts' 755 MB take 0.92 ms at the chip's bandwidth |
| 8 (4096 x 14336), 2, 4 | 80 | 4.5 | 7.0 | PR 33, host clock |
| | 1040 | 16.3 | 39.1 | |
| 64 (2048 x 1408), 6, 10.7 | 80 | 2.12 | 4.61 | PR 36, host clock |
| | 528 | 3.81 | 9.36 | |
| 256 (2048 x 512), 8, 32 | 80 | 2.86 (2.13) | 3.15 (2.34) | PR 45, host clock (device time of the three products) |
| | 528 | 5.30 (4.49) | 4.04 (2.85) | the experts' 1.61 GB take 1.97 ms (805,306,368 is their PARAMETERS) |

The grouped form beside the all-experts form, device time of the whole
form (the gates and the touched flags in XLA, then the kernel), by the
experts the rows touch (PR 53; `--touch 0.1,0.33,1`):

| experts (E x F) | rows | all-experts | grouped: a tenth | a third | all touched |
| --- | --- | --- | --- | --- | --- |
| 64 (2560 x 768) | 16 | 1.001 ms | 0.099 (6) | 0.333 (21) | 0.785 (50) |
| | 80 | 1.004 | 0.107 (6) | 0.341 (21) | 1.011 (64) |
| | 239 | 1.058 | 0.129 (6) | 0.363 (21) | 1.033 (64) |
| 64 (2048 x 1408) | 16 | 1.471 | 0.144 (6) | 0.487 (21) | 1.150 (50) |
| | 80 | 1.469 | 0.153 (6) | 0.495 (21) | 1.478 (64) |
| | 239 | 1.562 | 0.179 (6) | 0.522 (21) | 1.504 (64) |
| 256 (2048 x 512) | 16 | 2.134 | 0.224 (26) | 0.582 (69) | 0.855 (102) |
| | 80 | 2.135 | 0.232 (26) | 0.714 (84) | 1.944 (232) |
| | 239 | 2.486 | 0.253 (26) | 0.735 (84) | 2.167 (256) |
| 64 held of 256 (2304 x 1024) | 16 | 1.251 | 0.158 (8) | 0.401 (21) | 1.111 (59) |
| | 80 | 1.259 | 0.166 (8) | 0.409 (21) | 1.213 (64) |
| | 239 | 1.344 | 0.193 (8) | 0.435 (21) | 1.239 (64) |
| 8 (4096 x 14336), F-tiles of 512 | 16 | 3.729 | 0.936 (2) | 1.402 (3) | 3.729 (8) |
| | 80 | 3.739 | 0.942 (2) | 1.408 (3) | 3.735 (8) |
| | 239 | 3.913 | 0.960 (2) | 1.426 (3) | 3.753 (8) |

(In brackets the experts touched; 16 rows of top-6 or top-8 cannot touch
them all.) 727-755 GB/s on the touched experts' bytes from a third
touched up at 16 and 80 rows (89-92 % of the chip's bandwidth, the
all-experts form's own rate when every expert is touched: within 1 % of
it at every shape at 80 rows, and 2-13 % faster than it at 239, where
the einsum's `[rows, X, F]` intermediate begins to cost), 660-730 GB/s
at a tenth, where the first slab's fetch and the operations before the
kernel weigh most; at 239 rows 550-700 GB/s below all touched: the rows'
arithmetic is then half of what the slabs' arrival hides. F-tiles of 128 to 1,024 columns read the same as whole
slabs (within 1 %; 256 columns 3 % slower at 4096 x 14336), so a step
takes an expert whole where its three slabs fit the kernel's VMEM twice.

Past the ridge, the sorted regime beside the two forms XLA builds, the
rows routed as a random router says (every expert touched), device time
of the whole form (PR 58; the row tile in brackets; "floor" is the held
experts' bytes at the chip's 819 GB/s):

| experts (E x F), top-k | rows | all-experts | sorted (`ragged-dot`) | sorted regime (tile) | floor |
| --- | --- | --- | --- | --- | --- |
| 64 (2560 x 768), 6 | 528 | 2.104 ms | 3.950 | 1.181 (64) | 0.92 |
| | 1040 | 4.068 | 6.935 | 1.436 (128) | |
| 64 (2048 x 1408), 6 | 528 | 3.098 | 8.499 | 1.621 (64) | 1.35 |
| | 1040 | 6.071 | 14.032 | 1.789 (128) | |
| 256 (2048 x 512), 8 | 528 | 4.500 | 3.249 | 2.353 (32) | 1.97 |
| | 1040 | 8.789 | 4.118 | 2.698 (64) | |
| 64 held of 256 (2304 x 1024), 8 | 528 | 2.537 | 2.654 | 1.366 (32) | 1.11 |
| | 1040 | 5.022 | 3.549 | 1.599 (64) | |
| 16 held of 512 + 256 zero (6144 x 2048), 12, F-tiles of 512 | 528 | 3.370 | 3.595 | 2.240 (16) | 1.47 |
| | 1040 | 6.611 | 6.222 | 3.118 (32) | |
| 8 (4096 x 14336), 2, F-tiles of 512 | 528 | 7.940 | 20.941 | 4.016 (256) | 3.44 |
| | 1040 | 15.366 | 38.175 | 6.481 (256) | |

With every expert touched the kernel alone runs within a tenth of the
floor (1.02 of SmallThinker's 1.18 ms, 2.13 of Laguna's 2.35); the rest
is XLA's: the rows' gather into the padded layout (0.11 ms at 7,168 rows
x 2560: its static bound, not the rows in use, is what is written), the
picks' way back and the layout's small operations. Other row tiles read
slower at both shapes tried (SmallThinker 32 / 64 / 128: 1.42 / 1.29 /
1.39 ms; Laguna 16 / 32 / 64: 2.54 / 2.41 / 2.58, before the layout's
running count became a product). Where the rows' picks fall among a
third or a tenth of the experts (`--touch`), as a document's rows do in
the cells, the regime reads only those: SmallThinker 0.68 and 0.46 ms
against the all-experts form's 2.10 whatever is touched, Laguna 1.07 and
0.61 against `ragged-dot`'s 1.54 and 0.97. The shares that hold a part
of the experts pay the layout's static bound for picks they do not hold
(LongCat: 6,336 picks laid out for 132 held), and at 1,040 rows
Mixtral's groups of 260 rows take two tiles of 256, so its F-tiles
arrive twice: both still the fastest form read.

Up to X/k of 10.7 the all-experts form is the faster of XLA's two at
every row count: at the MXU's peak in a chunk (785 GFLOP in 4.07 ms)
where XLA's `ragged-dot` runs at a tenth of it; at 32 the sorted dispatch
won past the ridge. PR 45 to PR 57 chose between them by
`_SORTED_MIN_WASTE` and `_SORTED_MIN_HELD_A_PICK`; since PR 58 neither
is anybody's choice on one chip with kernels allowed (the sorted regime
read faster than both at all six shapes, Mixtral's 8 x top-2 included:
the all-experts form wastes only 4x there, but its F-tiles arrive once
for 132 rows a group in tiles of 256), and the constants are gone.
`GRIDLLM_MOE_RAGGED=on` / `off` still forces a form (`off`: the
all-experts form everywhere, the A/B switch of both grouped regimes). Under a mesh
the inherited rule stands (the `ep` dispatch from `_RAGGED_MIN_TOKENS`
rows up), not measured: its claims, "top_k-proportional FLOPs per shard"
and that an all-to-all token exchange would buy nothing over replicated
tokens, stand unread.

A chip may hold A SHARE of a layer's experts (`cfg.experts_held` from
`cfg.experts_first`: one chip of the expert-parallel group that shares
each layer, kimi-linear:48b-ep4, PR 51): the router keeps its width, its
top-k and its normalisation over all the chosen, the expert leaves hold
the held experts only, every form computes those (`_held`: the all-experts
form's one-hot and the grouped form's gates over the held, the sorted
form's groups over the held, `_sorted_share`, which is an `ep` shard's
body too), and a pick of an expert that lives elsewhere adds nothing: the exchange that would bring
its part is not run, and nothing stands in for it. The sorted regime's
row tile reads the picks expected HERE (rows x top-k over the router's
whole width); its layout is bounded by every pick of a row being held
here (T x k rows and a tile's padding a held expert), whatever share of
them is.

ZERO-COMPUTE experts (`cfg.zero_experts`, longcat_flash, PR 57): the
router is `cfg.router_width` = num_experts + zero_experts wide, and a pick
at or past num_experts is an identity expert: it adds its weight times the
token itself, with no product and no weight read (`_zero_mlp`, added once
whatever form the routed ones take, as a shared expert is: every chip of
an expert-parallel group computes it alike for its own tokens). Such a
pick is neither held nor absent: `_held` gives it no gate, `_touched`
counts no expert for it, `_route_stats` counts it apart.

Routing numerics follow HF `MixtralSparseMoeBlock`: softmax over ALL
expert logits in fp32 → top-k → renormalize the selected weights (the
same numbers as SmallThinker's top-k → softmax over the chosen);
deepseek_v2 keeps the softmax's own weights (`norm_topk_prob` false).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.experts import (
    grouped_experts,
    sorted_experts,
    sorted_tile_rows,
)
from gridllm_tpu.ops.kvcache import PagedKVCache
from gridllm_tpu.utils.config import env_str

Params = dict[str, Any]


# the sorted dispatch, where a mesh or GRIDLLM_MOE_RAGGED=on runs it
# (`_use_ragged`), starts at this many rows a call: under it the
# all-experts form is one small einsum
_RAGGED_MIN_TOKENS = 16
# one chip: the rows from which the grouped kernel takes its sorted regime,
# the chip's ridge (197 TFLOP/s over 819 GB/s). Under it all rows against a
# touched expert is less arithmetic than its slabs take to arrive; past it
# that arithmetic binds and each expert meets its own rows only
_SORTED_MIN_ROWS = 240


def _route(cfg: ModelConfig, lp: Params, r: jnp.ndarray):
    """Router math, float32: the experts' scores (`cfg.router_score`:
    softmax over ALL expert logits, HF MixtralSparseMoeBlock's order, or
    each logit's sigmoid) -> top-k -> the chosen weights divided by their
    sum (`cfg.norm_topk_prob`; else the scores' own) -> times
    `cfg.routed_scaling_factor`. Returns (top_w, top_i). With
    `cfg.router_bias` the top-k is of scores + `lp["router_bias"]` and the
    weights are still the scores' (the normalisation over all the chosen,
    held here or not).
    SmallThinker's order (top-k of the logits, softmax over the chosen)
    gives the softmax's numbers: exp(s_j) / sum_chosen exp(s), either way."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(
            r.astype(jnp.float32), lp["router"].astype(jnp.float32))
        # [..., X] fp32 - router math stays fp32 (tiny; routing flips are costly)
        scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        if cfg.router_bias:
            # the bias CHOOSES (scores + bias ranked), the scores weigh
            _, top_i = jax.lax.top_k(
                scores + lp["router_bias"].astype(jnp.float32),
                cfg.experts_per_token)
            top_w = jnp.take_along_axis(scores, top_i, axis=-1)
        else:
            top_w, top_i = jax.lax.top_k(scores, cfg.experts_per_token)
        if cfg.norm_topk_prob:
            top_w = top_w / top_w.sum(axis=-1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_i


def _act(cfg: ModelConfig):
    """The gate's activation: SiLU (SwiGLU, mixtral) or ReLU (ReGLU)."""
    return {"silu": jax.nn.silu, "relu": jax.nn.relu}[cfg.expert_act]


def _held(cfg: ModelConfig, top_i: jnp.ndarray):
    """Router picks as this chip's share sees them: (the pick's index
    among the held experts, `held` itself for an absent one or a
    zero-compute one; whether it is held)."""
    first, held = cfg.held_experts
    local = top_i - first
    here = (local >= 0) & (local < held)
    return jnp.where(here, local, held), here


def _touched(cfg: ModelConfig, top_i: jnp.ndarray, live) -> jnp.ndarray:
    """int32 [experts held]: 1 where at least one live row picked the
    expert. ONE rule for what `_route_stats` counts and what the grouped
    form reads, so the counter and the kernel cannot disagree."""
    flat = top_i.reshape(-1, cfg.experts_per_token)
    if live is None:
        live = jnp.ones(flat.shape[:1], bool)
    if not cfg.routes_elsewhere:
        return jnp.zeros((cfg.num_experts,), jnp.int32).at[flat].max(
            jnp.broadcast_to(live.reshape(-1, 1).astype(jnp.int32), flat.shape))
    on = jnp.broadcast_to(live.reshape(-1, 1).astype(jnp.int32), flat.shape)
    return jnp.zeros((cfg.held_experts[1],), jnp.int32).at[
        _held(cfg, flat)[0]].max(on, mode="drop")


def _route_stats(cfg: ModelConfig, top_i: jnp.ndarray, live) -> jnp.ndarray:
    """[live token rows routed, experts with at least one live row] of one
    layer, int32[2]: what the engine's gridllm_moe_* counters sum. A share
    (`cfg.experts_held`) counts the HELD experts touched and adds the live
    rows' picks [on held experts, on absent ones]: int32[4]; a family with
    zero-compute experts adds [on those]: int32[5]."""
    flat = top_i.reshape(-1, cfg.experts_per_token)
    if live is None:
        live = jnp.ones(flat.shape[:1], bool)
    hit = _touched(cfg, flat, live)
    if not cfg.routes_elsewhere:
        return jnp.stack([live.sum().astype(jnp.int32), hit.sum()])
    on = jnp.broadcast_to(live.reshape(-1, 1).astype(jnp.int32), flat.shape)
    picks = (on * _held(cfg, flat)[1]).sum()
    if not cfg.zero_experts:
        return jnp.stack([live.sum().astype(jnp.int32), hit.sum(), picks,
                          on.sum() - picks])
    zero = (on * (flat >= cfg.num_experts)).sum()
    return jnp.stack([live.sum().astype(jnp.int32), hit.sum(), picks,
                      on.sum() - picks - zero, zero])


def _moe_mlp_dense(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                   top_w, top_i) -> jnp.ndarray:
    """Dense form: every expert computes every token, non-selected pairs
    zero-weighted. One big batched einsum over the stacked expert axis —
    no dynamic shapes, EP-shardable (each "ep" shard computes its X/ep
    experts for all tokens; the combine is the all-reduce XLA inserts).
    X/top_k times the ragged form's row FLOPs and a [T, X, F]
    intermediate: see the module docstring for what the chip read. Of a
    share (`cfg.experts_held`) the expert leaves hold the held experts
    only and a pick of an absent one has no gate: it adds nothing."""
    p = llama._precision(x)
    if not cfg.routes_elsewhere:
        one_hot = jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
    else:       # an absent or zero-compute pick's row of the one-hot is zeros
        one_hot = jax.nn.one_hot(_held(cfg, top_i)[0], cfg.held_experts[1],
                                 dtype=jnp.float32)
    gates = jnp.einsum("...k,...kx->...x", top_w, one_hot).astype(x.dtype)

    with jax.named_scope("moe_experts"):
        g = jnp.einsum("...e,xef->...xf", x, lp["we_gate"], precision=p)
        u = jnp.einsum("...e,xef->...xf", x, lp["we_up"], precision=p)
        y = _act(cfg)(g) * u * gates[..., None]
        return jnp.einsum("...xf,xfe->...e", y, lp["we_down"], precision=p)


def _expert_leaves(lp: Params):
    """(the tree the grouped kernels read the experts from, the layer's
    index in it): the whole stacked leaves and this layer's index where a
    layer scan hands them over (`lp["layer_stack"]`), because a custom call
    given the scan's per-layer slice would be given a copy of it; else the
    layer's own leaves and None."""
    layers, li = lp.get("layer_stack", (None, None))
    return (lp if layers is None else layers), li


def _moe_mlp_grouped(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                     top_w, top_i, live) -> jnp.ndarray:
    """Grouped form: the all-experts form's mathematics over the experts
    a live row TOUCHED and no other (ops/experts.py `grouped_experts`: one
    Pallas product, an expert's slabs read once, float32 sums over F and
    over experts). A row that is not live has no gate and comes back
    zeros; of a share, a pick of an absent expert has none either."""
    k, held = cfg.experts_per_token, cfg.held_experts[1]
    w = top_w.reshape(-1, k)
    if live is not None:
        w = jnp.where(live.reshape(-1, 1), w, 0.0)
    idx = top_i.reshape(-1, k)
    if cfg.routes_elsewhere:
        idx = _held(cfg, idx)[0]        # absent, zero-compute: a row of zeros
    gates = jnp.where(idx[..., None] == jnp.arange(held), w[..., None],
                      0.0).sum(axis=1)
    we, li = _expert_leaves(lp)
    with jax.named_scope("moe_experts"):
        out = grouped_experts(
            x.reshape(-1, x.shape[-1]), gates, _touched(cfg, top_i, live),
            we["we_gate"], we["we_up"], we["we_down"], li,
            act=cfg.expert_act, use_pallas=cfg.use_pallas)
    return out.reshape(x.shape)


def _moe_mlp_grouped_sorted(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                            top_w, top_i, live,
                            tile_rows: int | None = None) -> jnp.ndarray:
    """Grouped form, sorted regime (rows past the chip's ridge): each held
    expert's slabs read once and multiplied by the rows that PICKED it and
    no others (ops/experts.py `sorted_experts`: the picks laid out by
    expert in XLA, one Pallas product over row tiles, the picks' outputs
    weighted and summed a row in float32). A pick of an absent or
    zero-compute expert, and every pick of a row that is not live, is in
    no group: it multiplies nothing and adds nothing. The row tile follows
    the rows a group is expected to have (`sorted_tile_rows`)."""
    k, held = cfg.experts_per_token, cfg.held_experts[1]
    idx = top_i.reshape(-1, k)
    if cfg.routes_elsewhere:
        idx = _held(cfg, idx)[0]
    if live is not None:
        idx = jnp.where(live.reshape(-1, 1), idx, held)
    we, li = _expert_leaves(lp)
    tm = tile_rows or sorted_tile_rows(idx.shape[0] * k / cfg.router_width)
    with jax.named_scope("moe_experts"):
        out = sorted_experts(
            x.reshape(-1, x.shape[-1]), top_w.reshape(-1, k), idx,
            we["we_gate"], we["we_up"], we["we_down"], li, tm=tm,
            act=cfg.expert_act, use_pallas=cfg.use_pallas)
    return out.reshape(x.shape)


def _moe_mlp_ragged(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
                    top_w, top_i) -> jnp.ndarray:
    """Sorted ragged dispatch (VERDICT #7): tokens sorted by expert, then
    ONE grouped matmul per projection via jax.lax.ragged_dot — T·top_k row
    FLOPs instead of the dense form's T·X (4× for 8×7b prefill), exact
    (no capacity factor, no token dropping), static shapes throughout
    (argsort/bincount are fixed-size; raggedness lives in group_sizes
    values, not array shapes). On one v5e chip the faster form only for
    no shape read (module docstring): the grouped kernel's sorted regime
    does the same with an expert's slabs read once."""
    k, X = cfg.experts_per_token, cfg.num_experts
    lead = x.shape[:-1]
    e = x.shape[-1]
    xf = x.reshape(-1, e)                       # [T, E]
    t = xf.shape[0]
    if cfg.routes_elsewhere:
        out = _sorted_share(
            _act(cfg), xf, top_w.reshape(t, k), top_i.reshape(t, k),
            cfg.held_experts[0], lp["we_gate"], lp["we_up"], lp["we_down"])
        return out.reshape(*lead, e)

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


def _sorted_share(act, xf, top_w, top_i, lo, wg, wu, wd):
    """The sorted dispatch over the experts [lo, lo + wg.shape[0]) that
    are HERE (an `ep` shard's, or a share's: `cfg.experts_held`): xf
    [T, E], top_w / top_i [T, k] over ALL experts. A pick of an expert
    that is not here sorts to the tail, is in no group (no product reads
    it) and adds zero. Returns this share's part of the output [T, E]."""
    t, e = xf.shape
    k = top_i.shape[-1]
    xl = wg.shape[0]                       # local experts
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
    return jnp.zeros((t, e), xf.dtype).at[rows].add(d * w[:, None])


def _moe_mlp_ragged_ep(
    cfg: ModelConfig, lp: Params, x: jnp.ndarray, top_w, top_i, mesh
) -> jnp.ndarray:
    """EP ragged dispatch under a mesh (VERDICT r03 next-round #7: the
    meshed dense form paid X/top_k = 4× redundant expert FLOPs exactly
    where EP matters — sharded prefill).

    shard_map over ("ep", "tp"): each shard holds X/ep experts (their
    gate/up/down slabs further split F-wise over tp), runs the SAME sorted
    ragged_dot dispatch as the single-device path but over its LOCAL
    expert range (`_sorted_share`), then one psum over (ep, tp) merges expert contributions and
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
        lo = jax.lax.axis_index("ep") * wg.shape[0]
        out = _sorted_share(act, xf, top_w, top_i, lo, wg, wu, wd)
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


def _zero_mlp(cfg: ModelConfig, x: jnp.ndarray, top_w, top_i) -> jnp.ndarray:
    """The zero-compute experts' part: each row times the sum of its
    weights on picks at or past `cfg.num_experts` (identity experts)."""
    with jax.named_scope("moe_zero"):
        w = jnp.where(top_i >= cfg.num_experts, top_w, 0.0).sum(axis=-1)
        return (x.astype(jnp.float32) * w[..., None]).astype(x.dtype)


def _use_ragged(n_tokens: int, meshed: bool,
                backend: str | None = None) -> bool:
    """Whether a call of `n_tokens` rows takes the sorted `ragged_dot`
    dispatch: GRIDLLM_MOE_RAGGED on / off says so; `auto` takes it under a
    mesh on a TPU only (the inherited rule, not measured; the CPU's
    ragged_dot is a serial loop over the groups). One chip never does by
    itself: at every shape read the grouped kernel's sorted regime is the
    faster (the module docstring's table)."""
    raw = env_str("GRIDLLM_MOE_RAGGED").lower()
    if raw != "auto":
        return raw in ("1", "on", "true") and n_tokens >= _RAGGED_MIN_TOKENS
    return (meshed and (backend or jax.default_backend()) == "tpu"
            and n_tokens >= _RAGGED_MIN_TOKENS)


def expert_form(cfg: ModelConfig, n_tokens: int, mesh=None,
                backend: str | None = None) -> str:
    """The form `_routed_mlp` gives a call of `n_tokens` rows: "grouped",
    "grouped_sorted", "sorted" or "all_experts" (the dispatch spans' meta
    and gridllm_moe_form_rows). On one TPU chip with kernels allowed the
    grouped kernel: at rows under the chip's ridge, where the products are
    bound by the bytes they read, every row against each touched expert;
    from there its sorted regime, each expert against the rows that picked
    it; unless GRIDLLM_MOE_RAGGED forces a form."""
    backend = backend or jax.default_backend()
    if mesh is not None:
        ok = (cfg.num_experts % mesh.shape.get("ep", 1) == 0
              and cfg.expert_width % mesh.shape.get("tp", 1) == 0)
    else:
        ok = cfg.use_pallas is not False
        if (ok and backend == "tpu"
                and env_str("GRIDLLM_MOE_RAGGED").lower() == "auto"):
            return ("grouped" if n_tokens < _SORTED_MIN_ROWS
                    else "grouped_sorted")
    ragged = _use_ragged(n_tokens, mesh is not None, backend)
    return "sorted" if ragged and ok else "all_experts"


def _moe_mlp(
    cfg: ModelConfig, mesh, live, lp: Params, x: jnp.ndarray,
    r: jnp.ndarray | None = None,
):
    """Sparse-MoE FFN: x [..., E] → ([..., E], `_route_stats`).

    lp carries router [E, X] and stacked experts we_gate/we_up [X, E, F],
    we_down [X, F, E] (the per-layer slice of the [L, X, ...] leaves), and
    where the family has shared experts ws_gate/ws_up [E, Fs], ws_down
    [Fs, E], whose output is added once whatever form the routed ones
    take; a layer scan adds `layer_stack` = (the stacked tree its slice
    came from, the layer's index in it), which the grouped form reads in
    the slice's place. `r`
    is what the router reads (x itself unless the family taps another
    state, llama._ffn); `live` ([...] bool or None) marks the token rows
    that belong to a request.

    Form selection (trace-time, static; `_use_ragged`):
    - meshed + prefill-sized tokens + divisible layout → shard_map EP
      ragged dispatch (top_k-proportional FLOPs per shard; not measured
      on the chip);
    - meshed otherwise (decode-sized batches, indivisible X/F) → dense
      all-experts einsum (EP-shardable via GSPMD, no dynamic shapes);
    - single device → by the shape (`expert_form`): on a TPU the grouped
      kernel, under the chip's ridge of 240 rows all rows against each
      touched expert, from there its sorted regime; elsewhere the
      all-experts form; unless GRIDLLM_MOE_RAGGED says.
    """
    y, stats = _routed_mlp(cfg, mesh, live, lp, x, r)
    if cfg.num_shared_experts:
        y = y + _shared_mlp(lp, x)
    return y, stats


def _routed_mlp(cfg: ModelConfig, mesh, live, lp: Params, x: jnp.ndarray,
                r: jnp.ndarray | None):
    """The routed experts of `_moe_mlp`, in the form `expert_form` picks."""
    top_w, top_i = _route(cfg, lp, x if r is None else r)
    stats = _route_stats(cfg, top_i, live)
    n_tokens = math.prod(x.shape[:-1])
    form = expert_form(cfg, n_tokens, mesh)
    if form == "grouped":
        y = _moe_mlp_grouped(cfg, lp, x, top_w, top_i, live)
    elif form == "grouped_sorted":
        y = _moe_mlp_grouped_sorted(cfg, lp, x, top_w, top_i, live)
    elif form == "all_experts":
        y = _moe_mlp_dense(cfg, lp, x, top_w, top_i)
    elif mesh is not None:
        y = _moe_mlp_ragged_ep(cfg, lp, x, top_w, top_i, mesh)
    else:
        y = _moe_mlp_ragged(cfg, lp, x, top_w, top_i)
    if cfg.zero_experts:
        y = y + _zero_mlp(cfg, x, top_w, top_i)
    return y, stats


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
