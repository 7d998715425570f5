"""LongCat-Flash decoder (longcat-flash:560b, PR 57; the language model of
LongCat-Flash-Omni and -Chat alike): a block of TWO latent-attention
sublayers and two dense feed-forwards, with one expert layer on a shortcut
around the second half.

Nothing here is new mathematics, and almost nothing is new code: the
module owns the block's dataflow (`_stack`) and its parameters, and calls
the rest.

- latent attention is DeepSeek-V2's (`deepseek._project`, `_absorbed`,
  `_expanded` and the `attend` closures over the latent pool), with what
  this family adds as data of the config: a low-rank query (`q = W_qb
  RMSNorm(W_qa h)`, `cfg.q_lora_rank`) and two constants, the query times
  sqrt(hidden / q_lora_rank) and the normed latent times sqrt(hidden /
  kv_lora_rank) (`cfg.mla_scales`); the cache row is `[c * s_kv, k_pe]`;
- a block owns `cfg.attn_sublayers` = 2 layers of the latent pool: pool
  layer `2 l + i` is sublayer i of block l (`cfg.cache_layers`), under
  the ONE page table, allocator and prefix cache every family has;
- the expert layer is `mixtral._moe_mlp`: a softmax router of
  `cfg.router_width` (the routed experts and behind them the zero-compute
  ones, whose picks add the token itself: `mixtral._zero_mlp`), a
  selection bias that chooses while the scores weigh, no renormalisation,
  a scaling factor, and of the routed experts those `cfg.held_experts`
  says (one chip of the expert-parallel group that shares each block);
- the dense feed-forwards are `llama._mlp`, the head `llama._unembed`.

The block, for input x (benchmark/reference/longcat_flash_f32.py states
the equations):

    a  = x + MLA_0(norm_in0(x));   h1 = norm_post0(a)
    m  = MoE(h1)                   # the shortcut leaves here
    b  = a + SwiGLU_0(h1)
    c  = b + MLA_1(norm_in1(b))
    y  = c + SwiGLU_1(norm_post1(c)) + m     # and rejoins here

In the published system the shortcut lets the experts' exchange overlap
the second attention and feed-forward; on one chip there is no exchange to
hide, and it is dataflow: where `m` leaves and where it rejoins.

The phases are deepseek's (`decode_step`, `verify_step`, `mixed_step`,
`hidden_states`, `forward`: each an `attend` closure over a stack runner),
given this module's `_stack`: ONE scan over the blocks, the stacked tree
and the block's index handed to the grouped experts' kernel as
`deepseek._stack` does. `validate_mesh` refuses every mesh.

Params: `layers` is one stacked tree [blocks, ...]: `att` and `ffn`, a
pair of trees each (one a sublayer), and the expert layer's leaves beside
them (`router [E, router_width]`, `router_bias`, `we_* [held, ...]`).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from gridllm_tpu.models import deepseek, llama, mixtral
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.layers import precompute_rope, rms_norm

Params = dict[str, Any]

# the engine asks decode_step / verify_step for the routed statistics
STEP_STATS = True


def validate_mesh(cfg: ModelConfig, mesh) -> None:
    """No mesh: the latent row has one head, and the experts' exchange
    over `ep` is not built (a share is held by `cfg.experts_held`, on one
    chip)."""
    if mesh is not None:
        raise ValueError(
            f"{cfg.name}: longcat_flash is served on one device only (a "
            "latent cache has one head: no mesh axis splits it, and the "
            "experts' exchange has not been written)")


def _stack(params: Params, cfg: ModelConfig, x, pos, attend: deepseek.Attend,
           mesh=None, live=None):
    """Every block on x [B, T, E] (`deepseek._stack`'s contract): ONE scan
    over the blocks. Returns (x, rows [2 x blocks, B, T, R + dr] in the
    pool's order, the expert layers' statistics [blocks, 5])."""
    inv_freq = precompute_rope(cfg.qk_rope_head_dim, cfg.rope_theta, None)
    n = cfg.attn_sublayers
    p = llama._precision(x)

    def body(x, xs):
        lp, l = xs
        # the whole stack and the index, for the grouped experts' kernel
        lp = {**lp, "layer_stack": (params["layers"], l)}
        rows = []
        for i, (ap, fp) in enumerate(zip(lp["att"], lp["ffn"])):
            q_nope, q_pe, row = deepseek._project(
                cfg, ap, rms_norm(x, ap["attn_norm"], cfg.rms_eps), pos,
                inv_freq)
            att = attend(ap, n * l + i, q_nope, q_pe, row)
            x = x + jnp.dot(att, ap["wo"], precision=p)
            h = rms_norm(x, ap["mlp_norm"], cfg.rms_eps)
            if i == 0:      # the shortcut: the experts read the first half
                m, stats = mixtral._moe_mlp(cfg, mesh, live, lp, h)
            x = x + llama._mlp(fp, h)
            rows.append(row)
        with jax.named_scope("scmoe_join"):
            x = x + m
        return x, (jnp.stack(rows), stats)

    x, (rows, stats) = jax.lax.scan(
        body, x, (params["layers"],
                  jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return x, rows.reshape(-1, *rows.shape[2:]), stats


hidden_states = partial(deepseek.hidden_states, stack=_stack)
forward = partial(deepseek.forward, stack=_stack)
decode_step = partial(deepseek.decode_step, stack=_stack)
verify_step = partial(deepseek.verify_step, stack=_stack)
mixed_step = partial(deepseek.mixed_step, stack=_stack)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params (tests, the benchmark's seeded weights): normal
    at fan-in^-0.5 (router, embedding and head 0.02), the selection bias
    normal at a quarter of the softmax scores' own spread (logits of
    0.02 sqrt(E) over router_width outputs) so that it changes some
    choices and not most. Expert leaves hold the HELD experts, the
    embedding and the head the held rows of the vocabulary."""
    e, v, h = cfg.hidden_size, cfg.vocab_rows, cfg.num_heads
    r, dr, rq = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.q_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    f, fm, n = cfg.intermediate_size, cfg.expert_width, cfg.num_layers
    held = cfg.held_experts[1]
    ks = iter(jax.random.split(key, 16 * cfg.attn_sublayers + 16))

    def w(*shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return mixtral._normal_leaf(
            next(ks), shape=shape, scale=scale, dtype=dtype)

    def attention() -> Params:
        return {
            "attn_norm": jnp.ones((n, e), dtype),
            "w_qa": w(n, e, rq), "q_norm": jnp.ones((n, rq), dtype),
            "w_qb": w(n, rq, h * (dn + dr)),
            "w_kva": w(n, e, r + dr), "kv_norm": jnp.ones((n, r), dtype),
            "w_kvb": w(n, r, h * (dn + dv)),
            "wo": w(n, h * dv, e),
            "mlp_norm": jnp.ones((n, e), dtype),
        }

    def dense() -> Params:
        return {"w_gate": w(n, e, f), "w_up": w(n, e, f), "w_down": w(n, f, e)}

    subs = range(cfg.attn_sublayers)
    params: Params = {
        "embed": w(v, e, scale=0.02),
        "layers": {
            "att": tuple(attention() for _ in subs),
            "ffn": tuple(dense() for _ in subs),
            "router": w(n, e, cfg.router_width, scale=0.02),
            "router_bias": w(n, cfg.router_width,
                             scale=0.005 * e ** 0.5 / cfg.router_width),
            "we_gate": w(n, held, e, fm), "we_up": w(n, held, e, fm),
            "we_down": w(n, held, fm, e),
        },
        "final_norm": jnp.ones((e,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(e, v, scale=0.02)
    return params
