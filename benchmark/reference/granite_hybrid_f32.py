"""Plain float32 reference of the Granite 4.0-H decoder, written from the
model's published ``config.json`` (ibm-granite/granite-4.0-h-micro,
``model_type: granitemoehybrid``) and the layer its ``mamba_*`` keys name:
Mamba-2's state-space scan. The recurrence runs TOKEN BY TOKEN: no chunked
form, kernel, cache or snapshot appears.

Model: ``h0 = embedding_multiplier * E[ids]``; the blocks; a final RMSNorm;
``logits = (h E^T) / logits_scaling`` (the head is the embedding,
``tie_word_embeddings``). A block, both kinds of layer (``rm`` =
``residual_multiplier``):

    h   = x + rm * mix(RMSNorm(x))
    out = h + rm * W_down (SiLU(W_gate u) * (W_up u)),  u = RMSNorm(h)

(the family's SHARED MLP of ``shared_intermediate_size``; ``num_local_experts``
is 0, no routed expert). ``layer_types`` says which mixer a layer has.

An ``attention`` layer: q = W_q u (``num_attention_heads`` heads of hidden /
heads), k = W_k u, v = W_v u (``num_key_value_heads``), no bias, NO rotary
embedding (``position_embedding_type: nope``), scores times
``attention_multiplier`` (1/64 at the published config, NOT head_dim^-0.5),
causal softmax, W_o.

A ``mamba`` layer, for a token's normed input u, H = ``mamba_n_heads``, P =
``mamba_d_head``, N = ``mamba_d_state``, one group:

    [z, xBC, dt] = W_in u              widths H P, H P + 2 N, H (in this order)
    xBC = SiLU(conv(xBC) + b)          depthwise causal over the last
                                       mamba_d_conv tokens (tap K-1 on the
                                       token itself), with a bias
    [x, B, C] = xBC                    widths H P, N, N: B and C are ONE a
                                       group, shared by all H heads
    dt = softplus(dt + dt_bias)        a value a head
    a  = exp(-exp(A_log) * dt)         a scalar a head
    S <- a S + (dt x) B^T              S [P, N] a head, zero at position 0
    y  = S C + D x
    y  = RMSNorm(y * SiLU(z); w)       over the WHOLE H P: gate before norm
    out = W_out y

``mamba_chunk_size`` is the published kernel's block, not part of the
function.

Inferences (the configuration's file lists them under ``assumed``): the
order of z, xBC, dt in W_in's output and of x, B, C in xBC; the gate before
the norm; the norm over the whole inner width (one group); the shared MLP
as a SwiGLU. They follow the Mamba-2 layer the keys name; the config has no
key for them.

Straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, one layer's weights upcast at a
time, rows through the head in blocks of ``BLOCK``. Imports nothing from
the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``; ``mamba``, a tuple of period - 1 trees (one for each
state-space place in the period, in order) stacked [periods, ...]:
``w_in [E, 2 H P + 2 N + H]``, ``conv_w [K, H P + 2 N]``, ``conv_b [H P + 2
N]``, ``A_log``/``dt_bias``/``D [H]``, ``o_norm [H P]``, ``wo [H P, E]``;
``attn``, stacked [periods, ...]: ``wq [E, heads*d]``, ``wk``/``wv [E,
kv*d]``, ``wo [heads*d, E]``; both with ``attn_norm``, ``mlp_norm [E]`` (the
norms BEFORE the mixer and before the MLP), ``w_gate``/``w_up [E, F]``,
``w_down [F, E]``; ``final_norm [E]``; all applied as ``x @ W``.

Switches, each a model wrong in one way, for the comparisons that have to
fail: ``no_conv`` (the convolution left out: only the token's own tap and
the bias), ``no_decay`` (a = 1), ``no_skip`` (D x left out),
``attn_scale_rsqrt`` (head_dim^-0.5 in place of ``attention_multiplier``),
``rope_theta=<theta>`` (a rotary embedding put on the attention layers),
``embedding_multiplier=``, ``residual_multiplier=``, ``logits_scaling=``
(one of the multipliers replaced), ``round_to=<dtype>`` (every weight
rounded through a lower precision by two eager casts, OUTSIDE any jitted
program, where XLA cannot fold them away; ``float8_e4m3fn`` is the nearest
below bfloat16: the contract's control), and ``skip_layer``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 512
# lengths are padded to a multiple of this (causal: a row never sees the
# padding behind it), so that a handful of shapes compile, not one a record
PAD = 128


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight.astype(F32)


def conv_silu(x, w, b, *, own_tap_only: bool):
    """x [T, C], w [K, C], b [C]: y[t] = sum_i w[i] x[t - (K-1) + i] + b,
    zeros before position 0; then SiLU."""
    k = w.shape[0]
    if own_tap_only:
        return jax.nn.silu(x * w[k - 1] + b)
    t = x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(xp[i:i + t] * w[i] for i in range(k)) + b)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dh", "state", "eps", "decay", "conv", "skip"))
def mamba_mixer(u, lp, *, heads, dh, state, eps, decay, conv, skip):
    """u [T, E] (normed) -> the Mamba-2 mixer's output [T, E], token by
    token."""
    t = u.shape[0]
    w = {k: v.astype(F32) for k, v in lp.items()}
    di = heads * dh
    zxd = u @ w["w_in"]
    z, xbc, dt = (zxd[:, :di], zxd[:, di:2 * di + 2 * state],
                  zxd[:, 2 * di + 2 * state:])
    xbc = conv_silu(xbc, w["conv_w"], w["conv_b"], own_tap_only=not conv)
    x = xbc[:, :di].reshape(t, heads, dh)
    b, c = xbc[:, di:di + state], xbc[:, di + state:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                        # [T, H]
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt) if decay else jnp.ones_like(dt)

    def token(s, row):
        xt, bt, ct, dtt, at = row
        s = s * at[:, None, None] + (dtt[:, None] * xt)[:, :, None] * bt
        return s, jnp.einsum("hpn,n->hp", s, ct)

    _, y = jax.lax.scan(token, jnp.zeros((heads, dh, state), F32),
                        (x, b, c, dt, a))
    if skip:
        y = y + w["D"][:, None] * x
    y = rms_norm(y.reshape(t, di) * jax.nn.silu(z), w["o_norm"], eps)
    return y @ w["wo"]


def rope(x, theta):
    """Split-half rotation over a head [T, H, D] at positions 0..T-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "rope_theta"))
def attn_mixer(u, lp, *, heads, kv_heads, scale, rope_theta):
    """u [T, E] (normed) -> the attention mixer's output: causal softmax
    at `scale`, grouped queries, rows in blocks of BLOCK."""
    t = u.shape[0]
    w = {k: v.astype(F32) for k, v in lp.items()}
    d = w["wq"].shape[1] // heads
    q = (u @ w["wq"]).reshape(t, heads, d)
    k = (u @ w["wk"]).reshape(t, kv_heads, d)
    v = (u @ w["wv"]).reshape(t, kv_heads, d)
    if rope_theta:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    k, v = (jnp.repeat(z, heads // kv_heads, axis=1) for z in (k, v))
    outs = []
    for a in range(0, t, BLOCK):
        s = jnp.einsum("thd,nhd->htn", q[a:a + BLOCK], k) * scale
        ok = (jnp.arange(t)[None, :]
              <= (a + jnp.arange(min(BLOCK, t - a)))[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("htn,nhd->thd", p, v))
    return jnp.concatenate(outs).reshape(t, heads * d) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def pre_norm(x, lp, *, eps):
    return rms_norm(x, lp["attn_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "rm"))
def block(x, mixed, lp, *, eps, rm):
    h = x + rm * mixed
    u = rms_norm(h, lp["mlp_norm"], eps)
    gate, up, down = (lp[k].astype(F32) for k in ("w_gate", "w_up", "w_down"))
    return h + rm * ((jax.nn.silu(u @ gate) * (u @ up)) @ down)


@functools.partial(jax.jit, static_argnames=("eps", "divide"))
def head(x, final_norm, out_proj, *, eps, divide):
    return rms_norm(x, final_norm, eps) @ out_proj.astype(F32) / divide


def _held(a, round_to):
    """A weight as the reference holds it: as given, or rounded through
    `round_to` by two EAGER casts, each an operation of its own: no jitted
    program holds both, so XLA cannot fold them away."""
    if round_to is None:
        return a
    low = a.astype(round_to)
    return low.astype(a.dtype)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           no_conv: bool = False, no_decay: bool = False,
           no_skip: bool = False, attn_scale_rsqrt: bool = False,
           rope_theta=0.0, embedding_multiplier=None,
           residual_multiplier=None, logits_scaling=None,
           round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host. `spec` holds the
    published keys (``sizes`` lists them). `skip_layer` leaves one layer
    out, the switches each break one mechanism, and `round_to` rounds every
    weight through that type on its way in: the checks of the check."""
    kinds = list(spec["layer_types"])[:spec["num_hidden_layers"]]
    period = len(params["mamba"]) + 1
    at = kinds.index("attention")
    eps = float(spec["rms_norm_eps"])
    em = float(spec["embedding_multiplier"] if embedding_multiplier is None
               else embedding_multiplier)
    rm = float(spec["residual_multiplier"] if residual_multiplier is None
               else residual_multiplier)
    ls = float(spec["logits_scaling"] if logits_scaling is None
               else logits_scaling)
    heads = spec["num_attention_heads"]
    d = params["attn"]["wq"].shape[-1] // heads
    scale = d ** -0.5 if attn_scale_rsqrt else float(spec["attention_multiplier"])
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % PAD)

    def held(a):
        return _held(a, round_to)

    with jax.default_matmul_precision("highest"):
        x = em * held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i, kind in enumerate(kinds):
            if i == skip_layer:
                continue
            pi, place = divmod(i, period)
            if kind == "attention":
                lp = jax.tree_util.tree_map(lambda a: held(a[pi]),
                                            params["attn"])
                mixed = attn_mixer(
                    pre_norm(x, lp, eps=eps), lp, heads=heads,
                    kv_heads=spec["num_key_value_heads"], scale=scale,
                    rope_theta=float(rope_theta or 0.0))
            else:
                j = place - (place > at)
                lp = jax.tree_util.tree_map(lambda a: held(a[pi]),
                                            params["mamba"][j])
                mixed = mamba_mixer(
                    pre_norm(x, lp, eps=eps), lp, heads=spec["mamba_n_heads"],
                    dh=spec["mamba_d_head"], state=spec["mamba_d_state"],
                    eps=eps, decay=not no_decay, conv=not no_conv,
                    skip=not no_skip)
            x = block(x, mixed, lp, eps=eps, rm=rm)
        out = held(params["embed"]).T
        norm = held(params["final_norm"])
        return np.concatenate([
            np.asarray(head(x[a:a + BLOCK], norm, out, eps=eps, divide=ls))
            for a in range(0, n, BLOCK)])[:n]


def sizes(cfg) -> dict:
    """The published keys this module reads, from a program ModelConfig
    (a rehearsal's tiny preset has no file of them)."""
    names = {"linear_attention": "mamba", "full_attention": "attention"}
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "rms_norm_eps": cfg.rms_eps,
        "layer_types": [names[t] for t in cfg.layer_types],
        "mamba_n_heads": cfg.linear_num_heads,
        "mamba_d_head": cfg.linear_value_head_dim,
        "mamba_d_state": cfg.linear_key_head_dim,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
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
            last_n: int = 0):
    """For each generated position p (token ``tokens[p]``, predicted from
    the logits at p - 1, under the request's repeat penalty): (reference
    maximum - reference logit of the served token, largest |logit| at
    that position)."""
    rows = penalized(jnp.asarray(ref_logits[n_prompt - 1: len(tokens) - 1]),
                     tokens, n_prompt, penalty, last_n)
    served = jnp.asarray(tokens[n_prompt:])
    picked = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    return rows.max(axis=-1) - picked, jnp.abs(rows).max(axis=-1)
