"""Plain float32 reference of the DeepSeek-V2 decoder, written from the
model's published ``config.json`` (deepseek-ai/DeepSeek-V2-Lite) and the
catalog's description ("MLA, no q_lora; 64 experts, top-6, 2 shared"), in
the EXPANDED form only: keys and values are rebuilt per head from the
latent, and no cache, kernel or absorbed product appears. Layer ``l``,
input ``x``, no bias anywhere, RMSNorm with ``rms_norm_eps``:

    h  = RMSNorm(x; attn_norm)
    q  = h Wq                        (heads x (nope + rope)), split q_nope | q_pe
    [c_raw, k_raw] = h W_kva         (kv_lora_rank + rope)
    c  = RMSNorm(c_raw; kv_norm);  k_pe = RoPE(k_raw)     (one key, all heads)
    [k_nope_i, v_i] = c W_kvb        (heads x (nope + v))
    k_i = [k_nope_i, k_pe];  q_i = [q_nope_i, RoPE(q_pe_i)]
    a_i = causal softmax(q_i k_i^T * s) v_i;   x1 = x + concat_i(a_i) Wo
    m  = RMSNorm(x1; mlp_norm)
    l < first_k_dense_replace:  x2 = x1 + W_down(silu(W_gate m) * (W_up m))
    else: p = softmax(m W_router) (float32, all experts); (p_top, idx) = top-k(p)
          w = p_top * routed_scaling_factor        (NOT renormalised)
          x2 = x1 + sum_j w_j E_idx_j(m) + S(m)    (E, S SwiGLUs; S unweighted)

then a final RMSNorm and the untied head. YaRN (``rope_scaling``): over
the rope values' pairs j, ``f_j = theta^(-2j/rope)``; ``dim(b) = rope
ln(orig / (2 pi b)) / (2 ln theta)``; ``low = max(floor(dim(beta_fast)),
0)``, ``high = min(ceil(dim(beta_slow)), rope - 1)``; ``ramp_j = clip((j -
low) / (high - low), 0, 1)``; ``f'_j = (f_j / factor) ramp_j + f_j (1 -
ramp_j)``; with ``m(x) = 0.1 x ln(factor) + 1`` the softmax scale is
``(nope + rope)^-0.5 m(mscale_all_dim)^2`` and cos/sin are multiplied by
``m(mscale) / m(mscale_all_dim)``.

Straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, one layer's weights upcast at a
time (an expert layer is 2.3 GB in float32) and rows through attention and
the experts in blocks of ``BLOCK``. Imports nothing from the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``; ``dense`` and ``layers``, two trees stacked on a leading
axis (the ``first_k_dense_replace`` leading layers, then the routed
ones), each with ``attn_norm``, ``wq [E, H*(nope+rope)]``, ``w_kva [E,
rank+rope]``, ``kv_norm [rank]``, ``w_kvb [rank, H*(nope+v)]``, ``wo
[H*v, E]``, ``mlp_norm``; ``dense`` adds ``w_gate``/``w_up [E, F]``,
``w_down [F, E]``; ``layers`` adds ``router [E, X]``, ``we_gate``/``we_up
[X, E, Fm]``, ``we_down [X, Fm, E]``, ``ws_gate``/``ws_up [E, Fs]``,
``ws_down [Fs, E]``; ``final_norm [E]``, ``lm_head [E, V]``; all applied
as ``x @ W``. Inferences (the configuration's file lists them under
``assumed``): the rotation pairs lane i with lane i + rope/2 (the
published code de-interleaves first: a fixed permutation of columns for
seeded weights); top-k of the softmax's probabilities.

Switches, each a model wrong in one way, for the comparisons that have to
fail: ``renormalise_topk`` (the six weights divided by their sum),
``no_shared`` (S left out), ``rope="plain"`` (YaRN off: the unscaled
frequencies and m = 1), ``round_to=<dtype>`` (every weight rounded through
a lower precision; ``float8_e4m3fn`` is the nearest below bfloat16: the
contract's control), and ``skip_layer``.

**A router's tie is not judged** (``ROUTER_TIE``, ``margins``), as in
``smallthinker_f32.py``: where a layer's k-th and (k+1)-th router logits
lie nearer than bfloat16's epsilon of the row's root mean square, the
served model and this one may each rightly take another expert. ``logits``
returns its array with ``router_gap [T, routed layers]`` attached and
``margins`` reads such a position as 0.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 512
# lengths are padded to a multiple of this (causal: a row never sees the
# padding behind it), so that a handful of shapes compile, not one a record
PAD = 128
# see smallthinker_f32.ROUTER_TIE: bfloat16's epsilon
ROUTER_TIE = 2.0 ** -7


class RoutedLogits(np.ndarray):
    """float32 logits [T, V] that carry ``router_gap`` [T, layers]."""

    router_gap = None


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def yarn(spec: dict, plain: bool = False):
    """(inverse frequencies [rope/2], softmax-scale factor, cos/sin
    factor) from ``rope_scaling``; `plain`: as if it were null."""
    d, theta = spec["qk_rope_head_dim"], float(spec["rope_theta"])
    freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    rs = spec.get("rope_scaling")
    if plain or not rs:
        return jnp.asarray(freq, F32), 1.0, 1.0
    orig, factor = rs["original_max_position_embeddings"], float(rs["factor"])

    def dim(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim(rs.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = freq / factor * ramp + freq * (1 - ramp)

    def m(x):
        return 0.1 * x * math.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = m(rs.get("mscale_all_dim", 0))
    return jnp.asarray(freq, F32), all_dim ** 2, m(rs.get("mscale", 1)) / all_dim


def rope(x, inv_freq, mult):
    """x [T, heads, D] at positions 0..T-1; pairs lane i with lane i + D/2."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * mult, jnp.sin(ang)[:, None, :] * mult
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "rank", "nope", "v_dim",
                                             "eps", "mult"))
def qkv(x, lp, inv_freq, *, heads, rank, nope, v_dim, eps, mult):
    """x [T, E] -> q, k [T, H, nope + rope], v [T, H, v_dim]: expanded."""
    t = x.shape[0]
    h = rms_norm(x, lp["attn_norm"].astype(F32), eps)
    q = (h @ lp["wq"].astype(F32)).reshape(t, heads, -1)
    kva = h @ lp["w_kva"].astype(F32)
    c = rms_norm(kva[:, :rank], lp["kv_norm"].astype(F32), eps)
    k_pe = rope(kva[:, None, rank:], inv_freq, mult)            # [T, 1, rope]
    kv = (c @ lp["w_kvb"].astype(F32)).reshape(t, heads, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], inv_freq, mult)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, heads, k_pe.shape[-1]))], -1)
    return q, k, kv[..., nope:]


@functools.partial(jax.jit, static_argnames=("first", "scale"))
def attend(q, k, v, *, first, scale):
    """Queries at positions first .. first + len(q) - 1 over all keys."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    dist = (first + jnp.arange(q.shape[0]))[:, None] - jnp.arange(k.shape[0])[None, :]
    scores = jnp.where((dist >= 0)[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return att.reshape(q.shape[0], -1)


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate.astype(F32)) * (m @ up.astype(F32))) @ down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x1, lp, *, eps):
    m = rms_norm(x1, lp["mlp_norm"].astype(F32), eps)
    return x1 + swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "scaling",
                                             "renormalise", "shared"))
def experts(x1, lp, *, eps, top_k, scaling, renormalise, shared):
    """x1 [T, E] (after attention) -> (x2, [T] the k-th less the (k+1)-th
    router logit over the row's rms)."""
    m = rms_norm(x1, lp["mlp_norm"].astype(F32), eps)
    s = m @ lp["router"].astype(F32)                         # [T, X]
    s_more, _ = jax.lax.top_k(s, top_k + 1)
    gap = ((s_more[:, top_k - 1] - s_more[:, top_k])
           / jnp.sqrt(jnp.mean(s * s, axis=-1)))
    p_top, idx = jax.lax.top_k(jax.nn.softmax(s, axis=-1), top_k)
    if renormalise:
        p_top = p_top / p_top.sum(axis=-1, keepdims=True)
    rows = jnp.arange(s.shape[0])[:, None]
    weight = jnp.zeros_like(s).at[rows, idx].set(p_top * scaling)
    g = jnp.einsum("te,xef->txf", m, lp["we_gate"].astype(F32))
    u = jnp.einsum("te,xef->txf", m, lp["we_up"].astype(F32))
    y = jax.nn.silu(g) * u * weight[..., None]
    out = x1 + jnp.einsum("txf,xfe->te", y, lp["we_down"].astype(F32))
    if shared:
        out = out + swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, gap


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm.astype(F32), eps) @ out_proj.astype(F32)


def layer(x, lp, spec: dict, rot, *, routed: bool, renormalise: bool,
          shared: bool):
    inv_freq, scale_mult, mult = rot
    eps = float(spec["rms_norm_eps"])
    nope = spec["qk_nope_head_dim"]
    q, k, v = qkv(x, lp, inv_freq, heads=spec["num_attention_heads"],
                  rank=spec["kv_lora_rank"], nope=nope,
                  v_dim=spec["v_head_dim"], eps=eps, mult=mult)
    scale = float((nope + spec["qk_rope_head_dim"]) ** -0.5 * scale_mult)
    out, gaps = [], []
    for a in range(0, x.shape[0], BLOCK):
        b = min(a + BLOCK, x.shape[0])
        x1 = x[a:b] + attend(q[a:b], k[:b], v[:b], first=a,
                             scale=scale) @ lp["wo"].astype(F32)
        if routed:
            x2, gap = experts(
                x1, lp, eps=eps, top_k=spec["num_experts_per_tok"],
                scaling=float(spec.get("routed_scaling_factor", 1.0)),
                renormalise=renormalise,
                shared=shared and bool(spec.get("n_shared_experts")))
            gaps.append(gap)
        else:
            x2 = dense_ffn(x1, lp, eps=eps)
        out.append(x2)
    return jnp.concatenate(out), (jnp.concatenate(gaps) if routed else None)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           renormalise_topk: bool = False, no_shared: bool = False,
           rope: str = "yarn", round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host, as `RoutedLogits`
    (``router_gap [T, routed layers]`` attached). `spec` holds the
    published keys (``sizes`` lists them). `skip_layer` leaves one layer
    out, the switches each break one mechanism, and `round_to` rounds every
    weight through that type on its way in: the checks of the check."""
    n_dense = params["dense"]["wq"].shape[0]
    n_layers = n_dense + params["layers"]["wq"].shape[0]
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % PAD)
    rot = yarn(spec, plain=(rope == "plain"))

    def held(a):
        return a if round_to is None else a.astype(round_to).astype(a.dtype)

    gaps = []
    with jax.default_matmul_precision("highest"):
        x = held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i in range(n_layers):
            if i == skip_layer:
                continue
            routed = i >= n_dense
            tree, j = ((params["layers"], i - n_dense) if routed
                       else (params["dense"], i))
            lp = jax.tree_util.tree_map(lambda a: held(a[j]), tree)
            x, gap = layer(x, lp, spec, rot, routed=routed,
                           renormalise=renormalise_topk,
                           shared=not no_shared)
            if routed:
                gaps.append(np.asarray(gap))
        out = held(params["embed"].T if spec.get("tie_word_embeddings")
                   else params["lm_head"])
        norm = held(params["final_norm"])
        rows = np.concatenate([
            np.asarray(head(x[a:a + BLOCK], norm, out,
                            eps=float(spec["rms_norm_eps"])))
            for a in range(0, n, BLOCK)])[:n].view(RoutedLogits)
    rows.router_gap = (np.stack(gaps, axis=-1)[:n] if gaps
                       else np.ones((n, 0), np.float32))
    return rows


def sizes(cfg) -> dict:
    """The published keys `logits` reads, from an object with the
    program's field names: in a rehearsal a tiny preset stands under the
    configuration file's name."""
    rs = cfg.rope_scaling
    return {
        "num_attention_heads": cfg.num_heads, "hidden_size": cfg.hidden_size,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "first_k_dense_replace": cfg.first_k_dense,
        "n_routed_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "n_shared_experts": cfg.num_shared_experts,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_scaling": None if rs is None else {
            "type": "yarn", "factor": rs.factor, "beta_fast": rs.beta_fast,
            "beta_slow": rs.beta_slow, "mscale": rs.mscale,
            "mscale_all_dim": rs.mscale_all_dim,
            "original_max_position_embeddings":
                rs.original_max_position_embeddings},
    }


def penalized(rows, tokens, first: int, penalty: float, last_n: int):
    """llama.cpp's repeat penalty (Keskar et al. 2019, CTRL), as Ollama
    applies it by default: at the position that predicts ``tokens[p]``,
    every token among the last `last_n` of ``tokens[:p]`` has its logit
    divided by `penalty` if positive and multiplied by it if not.
    ``rows[i]`` are the logits that predict ``tokens[first + i]``."""
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
    (``router_gap`` under `tie` in any layer) reads 0: it is not judged."""
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
