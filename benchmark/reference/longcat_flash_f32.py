"""Plain float32 reference of the LongCat-Flash decoder (the language model
of meituan-longcat/LongCat-Flash-Omni; ``model_type: longcat_flash``),
written from the model's published ``config.json`` keys and the catalog's
description of the family (28 "double-layers": two latent-attention
sublayers a block, a dense feed-forward path parallel to a
shortcut-connected mixture of experts, zero-computation identity experts).
No cache, kernel, chunk or batch appears. Pre-norm residual blocks,
RMSNorm with ``rms_norm_eps``, no bias on any projection, untied head.

For a block's input x [T, E] (E = ``hidden_size``):

    MLA_i(h):  cq = RMSNorm(h W_qa)                     [q_lora_rank]
               q  = (cq W_qb) * s_q      -> H heads of [q_nope dn | q_pe dr]
               kva = h W_kva                            [kv_lora_rank + dr]
               c  = RMSNorm(kva[:R]) * s_kv;   k_pe = rope(kva[R:])
               [k_nope dn | v dv] = c W_kvb  a head;  q_pe = rope(q_pe)
               softmax((q_nope.k_nope + q_pe.k_pe) (dn + dr)^-0.5) v  -> W_o
               s_q = sqrt(E / q_lora_rank)   (``mla_scale_q_lora``)
               s_kv = sqrt(E / kv_lora_rank) (``mla_scale_kv_lora``)
    a  = x + MLA_0(RMSNorm_in0(x))
    h1 = RMSNorm_post0(a)
    m  = MoE(h1)                    # the shortcut: leaves here
    b  = a + SwiGLU_0(h1)           # dense, ``ffn_hidden_size``
    c  = b + MLA_1(RMSNorm_in1(b))
    y  = c + SwiGLU_1(RMSNorm_post1(c)) + m             # rejoins here

    MoE(h):  s = softmax(h W_r) over ``router_experts`` + ``zero_expert_num``
             outputs (float32); the top ``moe_topk`` of s + bias (the bias
             chooses, it never weighs); w_j = s_j * ``routed_scaling_factor``,
             NOT renormalised;
             sum over chosen j < router_experts of w_j SwiGLU_j(h)
             [``expert_ffn_hidden_size``]  +  sum over chosen j >=
             router_experts of w_j h                  (identity experts)

    logits = RMSNorm(x_L) W_head

RoPE rotates the ``qk_rope_head_dim`` values of q_pe and of the one shared
k_pe by theta ``rope_theta``, unscaled, lane i paired with lane i + dr/2
(the program's split halves; the published code de-interleaves pairs
(2j, 2j + 1) first, a fixed permutation of columns for seeded weights, as
deepseek-v2-lite-L10's file states).

**A share of the experts.** The configuration holds ``n_routed_experts`` of
the router's ``router_experts`` from ``experts_first`` (one chip of the
expert-parallel group that shares each block): the router, its top-k and
its weights are the whole model's; a pick of a routed expert that is not
held adds nothing, here as in the program, and that partial sum is what
rejoins the block. A zero-compute pick is computed here whatever the
share (every chip does, for its own tokens). The expert leaves hold the
held experts only.

Inferences (the configuration's file lists them under ``assumed``; where
the published modeling code differs, the published form wins): the two
latent scales' values and places; the RoPE pairing and that nothing scales
it; the softmax router with a selection bias (a checkpoint buffer and no
key: "PID expert bias" in the family's description), weights not
renormalised; the zero-compute experts behind the routed ones in the
router's order; ``model_type``.

Straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, one sublayer's weights upcast at
a time, the experts a group at a time, rows through attention, the
feed-forwards, the experts and the head in blocks (``BLOCK``, attention
``ATTN_BLOCK``) so that 7.8 k rows at the published widths fit beside the
served weights; the head's logits leave the device a block at a time.
Imports nothing from the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``; ``layers``, one stacked tree [blocks, ...]: ``att``, a
pair of trees (one a sublayer) with ``attn_norm [E]``, ``w_qa [E, rq]``,
``q_norm [rq]``, ``w_qb [rq, H*(dn+dr)]``, ``w_kva [E, R+dr]``, ``kv_norm
[R]``, ``w_kvb [R, H*(dn+dv)]``, ``wo [H*dv, E]``, ``mlp_norm [E]`` (the
post-attention norm); ``ffn``, a pair with ``w_gate``/``w_up [E, F]``,
``w_down [F, E]``; ``router [E, X + Z]``, ``router_bias [X + Z]``,
``we_gate``/``we_up [held, E, Fm]``, ``we_down [held, Fm, E]``;
``final_norm [E]``, ``lm_head [E, V]``; all applied as ``x @ W``.

Switches, each a model wrong in one way, for the comparisons that have to
fail: ``no_shortcut`` (m dropped), ``no_zero`` (the zero-compute picks add
nothing), ``unit_scales`` (s_q = s_kv = 1), ``no_bias`` (the top-k of the
scores alone), ``round_to=<dtype>`` (every weight rounded through a lower
precision; ``float8_e4m3fn`` is the nearest below bfloat16: the contract's
control), and ``skip_layer`` (one BLOCK left out).

**A router's tie is not judged** (``ROUTER_TIE``, ``margins``), as in
``kimi_linear_f32.py``: where the k-th and (k+1)-th of the values the
router chooses by lie nearer than ``ROUTER_TIE`` (in logit units over the
row's rms: the gap in s + bias over the k-th score, the softmax's slope
there), the served bfloat16 model and this one may each rightly take
another expert. Only a tie that a HELD or a ZERO-COMPUTE expert takes part
in counts: which of two absent experts is chosen moves nothing here. The
tie is THIS model's, 2^-4 (Kimi's and Laguna's is 2^-5): the router is 768
wide, so the 12th and 13th of the values it chooses by lie 0.011 apart in
the median and under 0.047 at nine positions of ten, and on the chip the
served model and this one parted by more than 0.15 at gaps up to 0.060 and
at none beyond (PR 57: every position of eight served records read raw;
the configuration's file has the readings at each tie).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# rows a block; lengths are padded to a multiple of it (causal: a row never
# sees the padding behind it), so that a handful of shapes compile
BLOCK = 512
ATTN_BLOCK = 128
# experts upcast to float32 at a time
EXPERT_GROUP = 8
# the gap under which a position is not judged (module docstring)
ROUTER_TIE = 2.0 ** -4


class RoutedLogits(np.ndarray):
    """float32 logits [T, V] that carry ``router_gap`` [T, blocks]."""

    router_gap = None


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight.astype(F32)


def rope(x, theta: float):
    """x [T, H, D] at positions 0..T-1: lane i with lane i + D/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dn", "dr", "eps", "theta", "s_q", "s_kv"))
def mla(x, ap, *, heads, dn, dr, eps, theta, s_q, s_kv):
    """x [T, E] (a sublayer's input) -> x + MLA(RMSNorm_in(x)): K and V
    rebuilt a head from the latent, causal softmax, rows in blocks."""
    t = x.shape[0]
    w = {k: v.astype(F32) for k, v in ap.items()}
    h = rms_norm(x, w["attn_norm"], eps)
    r = w["kv_norm"].shape[0]
    q = ((rms_norm(h @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]) * s_q
         ).reshape(t, heads, dn + dr)
    kva = h @ w["w_kva"]
    c = rms_norm(kva[:, :r], w["kv_norm"], eps) * s_kv
    k_pe = rope(kva[:, None, r:], theta)                        # [T, 1, dr]
    kvb = (c @ w["w_kvb"]).reshape(t, heads, -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe, (t, heads, dr))], axis=-1)
    v = kvb[..., dn:]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta)], axis=-1)

    def rows(a):
        qa = jax.lax.dynamic_slice_in_dim(q, a, ATTN_BLOCK)
        s = jnp.einsum("thd,nhd->htn", qa, k) * (dn + dr) ** -0.5
        ok = jnp.arange(t)[None, :] <= (a + jnp.arange(ATTN_BLOCK))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return jnp.einsum("htn,nhd->thd", p, v)

    o = jax.lax.map(rows, jnp.arange(0, t, ATTN_BLOCK))
    return x + o.reshape(t, -1) @ w["wo"]


@jax.jit
def swiglu(h, fp):
    """h [T, E] (normed) -> the dense SwiGLU's output, rows in blocks."""
    g, u, d = (fp[k].astype(F32) for k in ("w_gate", "w_up", "w_down"))

    def rows(hb):
        return (jax.nn.silu(hb @ g) * (hb @ u)) @ d

    return jax.lax.map(rows, h.reshape(-1, BLOCK, h.shape[-1])).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scaling", "first", "routed", "bias", "zero"))
def moe(h, ep, *, top_k, scaling, first, routed, bias, zero):
    """h [T, E] (normed) -> (the expert layer's output m [T, E], [T] the
    gap of the router's choice, see ROUTER_TIE). The held experts are
    [first, first + held) of the `routed` ones, held = the expert leaves'
    leading size; a pick at or past `routed` is an identity expert."""
    logit = h @ ep["router"].astype(F32)                     # [T, X + Z]
    scores = jax.nn.softmax(logit, axis=-1)
    choose = scores + (ep["router_bias"].astype(F32) if bias else 0.0)
    c_more, i_more = jax.lax.top_k(choose, top_k + 1)
    idx = i_more[:, :top_k]
    w_top = scaling * jnp.take_along_axis(scores, idx, axis=-1)
    held = ep["we_gate"].shape[0]
    here = (i_more >= first) & (i_more < first + held)
    counts = here | (i_more >= routed)          # held or zero-compute
    s_k = jnp.take_along_axis(scores, i_more[:, top_k - 1:top_k], axis=-1)[:, 0]
    gap = ((c_more[:, top_k - 1] - c_more[:, top_k]) / jnp.maximum(s_k, 1e-30)
           / jnp.sqrt(jnp.mean(logit * logit, axis=-1)))
    gap = jnp.where(counts[:, top_k - 1] | counts[:, top_k], gap, jnp.inf)
    rows = jnp.arange(h.shape[0])[:, None]
    local = jnp.where(here[:, :top_k], idx - first, held)    # others: dropped
    weight = jnp.zeros((h.shape[0], held), F32).at[rows, local].set(
        w_top, mode="drop")
    grp = math.gcd(EXPERT_GROUP, held)

    def group(acc, xs):
        wg, wu, wd, wt = xs               # [grp, E, F] .. , wt [grp, T]
        g = jnp.einsum("te,xef->txf", h, wg.astype(F32))
        u = jnp.einsum("te,xef->txf", h, wu.astype(F32))
        y = jax.nn.silu(g) * u * wt.T[..., None]
        return acc + jnp.einsum("txf,xfe->te", y, wd.astype(F32)), None

    def split(a):
        return a.reshape(held // grp, grp, *a.shape[1:])

    out, _ = jax.lax.scan(
        group, jnp.zeros_like(h),
        (split(ep["we_gate"]), split(ep["we_up"]), split(ep["we_down"]),
         split(weight.T)))
    if zero:
        out = out + h * jnp.where(idx >= routed, w_top, 0.0).sum(-1)[:, None]
    return out, gap


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm, eps) @ out_proj.astype(F32)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           no_shortcut: bool = False, no_zero: bool = False,
           unit_scales: bool = False, no_bias: bool = False,
           round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host, as `RoutedLogits`
    (``router_gap [T, blocks]`` attached). `spec` holds the published keys
    (``sizes`` lists them). `skip_layer` leaves one block out, the switches
    each break one mechanism, and `round_to` rounds every weight through
    that type on its way in: the checks of the check."""
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % BLOCK)
    eps = float(spec["rms_norm_eps"])
    e = spec["hidden_size"]
    s_q = (e / spec["q_lora_rank"]) ** 0.5 if (
        spec.get("mla_scale_q_lora") and not unit_scales) else 1.0
    s_kv = (e / spec["kv_lora_rank"]) ** 0.5 if (
        spec.get("mla_scale_kv_lora") and not unit_scales) else 1.0
    routed = int(spec.get("router_experts", spec["n_routed_experts"]))
    att = dict(heads=spec["num_attention_heads"], dn=spec["qk_nope_head_dim"],
               dr=spec["qk_rope_head_dim"], eps=eps,
               theta=float(spec["rope_theta"]), s_q=s_q, s_kv=s_kv)

    def held(a):
        return a if round_to is None else a.astype(round_to).astype(a.dtype)

    layers = params["layers"]
    experts = {k: layers[k] for k in (
        "router", "router_bias", "we_gate", "we_up", "we_down")}
    gaps = []
    with jax.default_matmul_precision("highest"):
        x = held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for l in range(int(spec["num_layers"])):
            if l == skip_layer:
                continue

            def of(tree):
                return jax.tree_util.tree_map(lambda a: held(a[l]), tree)

            m = None
            for i, (ap, fp) in enumerate(zip(layers["att"], layers["ffn"])):
                ap = of(ap)
                x = mla(x, {k: v for k, v in ap.items() if k != "mlp_norm"},
                        **att)
                h = rms_norm(x, ap["mlp_norm"], eps)
                if i == 0:
                    ep = of(experts)
                    outs = [moe(
                        h[a:a + BLOCK], ep, top_k=int(spec["moe_topk"]),
                        scaling=float(spec.get("routed_scaling_factor", 1.0)),
                        first=int(spec.get("experts_first") or 0),
                        routed=routed, bias=not no_bias, zero=not no_zero)
                        for a in range(0, h.shape[0], BLOCK)]
                    m = jnp.concatenate([o for o, _ in outs])
                    gaps.append(np.concatenate([np.asarray(g) for _, g in outs]))
                x = x + swiglu(h, of(fp))
            if not no_shortcut:
                x = x + m
        out = held(params["lm_head"])
        norm = held(params["final_norm"])
        rows = np.concatenate([
            np.asarray(head(x[a:a + BLOCK], norm, out, eps=eps))
            for a in range(0, n, BLOCK)])[:n].view(RoutedLogits)
    rows.router_gap = (np.stack(gaps, axis=-1)[:n] if gaps
                       else np.ones((n, 0), np.float32))
    return rows


def sizes(cfg) -> dict:
    """The published keys `logits` reads, from an object with the
    program's field names: in a rehearsal a tiny preset stands under the
    configuration file's name."""
    first, held = cfg.held_experts
    return {
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
        "num_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": cfg.q_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "mla_scale_q_lora": cfg.mla_scale_q_lora,
        "mla_scale_kv_lora": cfg.mla_scale_kv_lora,
        "rope_theta": cfg.rope_theta,
        "n_routed_experts": held, "router_experts": cfg.num_experts,
        "experts_first": first, "zero_expert_num": cfg.zero_experts,
        "moe_topk": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
    }


def penalized(rows, tokens, first: int, penalty: float, last_n: int):
    """llama.cpp's repeat penalty, as Ollama applies it by default (see
    llama_f32.penalized): ``rows[i]`` are the logits that predict
    ``tokens[first + i]``."""
    if penalty == 1.0 or last_n <= 0:
        return rows
    seen = np.zeros(rows.shape, bool)
    for i in range(rows.shape[0]):
        p = first + i
        seen[i, np.asarray(tokens[max(0, p - last_n):p], np.int64)] = True
    return jnp.where(seen, jnp.where(rows > 0, rows / penalty, rows * penalty), rows)


def margins(ref_logits, tokens, n_prompt: int, penalty: float = 1.0,
            last_n: int = 0, tie: float = ROUTER_TIE):
    """For each generated position p (token ``tokens[p]``, predicted from
    the logits at p - 1, under the request's repeat penalty): (reference
    maximum - reference logit of the served token, largest |logit| at
    that position). A position whose logits came through a router's tie
    (``router_gap`` under `tie` in any block) reads 0: it is not judged."""
    gap = getattr(ref_logits, "router_gap", None)
    rows = penalized(np.asarray(ref_logits[n_prompt - 1: len(tokens) - 1]),
                     tokens, n_prompt, penalty, last_n)
    served = jnp.asarray(tokens[n_prompt:])
    picked = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    short = rows.max(axis=-1) - picked
    if gap is not None and gap.shape[-1] and tie:
        tied = (gap[n_prompt - 1: len(tokens) - 1] < tie).any(axis=-1)
        short = jnp.where(jnp.asarray(tied), 0.0, short)
    return short, jnp.abs(rows).max(axis=-1)
