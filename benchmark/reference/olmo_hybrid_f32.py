"""Plain float32 reference of the Olmo-Hybrid decoder, written from the
model's published ``config.json`` (allenai/Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``) and the layer its keys name (``linear_*``,
``linear_allow_neg_eigval``): the gated delta rule as the
flash-linear-attention library's ``GatedDeltaNet`` has it. The recurrence
runs TOKEN BY TOKEN: no chunked form, kernel, cache or snapshot appears.

Layers come in periods (``layer_types``): ``linear_attention`` x 3, then
``full_attention`` x 1. A linear layer, for a token's input x (hidden),
per head h of ``linear_num_key_heads``, dk = ``linear_key_head_dim``, dv =
``linear_value_head_dim``:

    q = SiLU(conv(W_q x))  k = SiLU(conv(W_k x))  v = SiLU(conv(W_v x))
        depthwise causal convolution over the last linear_conv_kernel_dim
        tokens, no bias (tap K-1 on the token itself)
    q^ = q / |q|_2 * dk^-0.5      k^ = k / |k|_2         (L2 per head)
    beta = 2 * sigmoid(W_b x)_h                (allow_neg_eigval doubles it)
    alpha = exp(-exp(A_log_h) * softplus((W_a x)_h + dt_bias_h))
    S <- alpha * S                             S [dk, dv], zero at position 0
    S <- S + k^ (beta * (v - S^T k^))^T        the delta rule
    o = S^T q^
    y = W_o [ RMSNorm_dv(o; w) * SiLU(W_g x) ]

A full layer: q = W_q x, k = W_k x, v = W_v x (no bias), RMSNorm over the
WHOLE width of q and of k, heads of hidden / num_attention_heads, causal
softmax attention at scale head_dim^-0.5, no rotary embedding, W_o. The
block, both kinds: ``h = x + RMSNorm(mix(x))``, ``out = h +
RMSNorm(SwiGLU(h))``. Then a final RMSNorm and the untied head.

Inferences (the configuration's file lists them under ``assumed``): the
equations above are the library layer's, not read from a modeling file,
which is not here; L2 normalisation as x * rsqrt(sum x^2 + 1e-6) and the
dk^-0.5 scale on q; the reordered (post-)norm block and the QK-norm over
the whole width are the Olmo family's convention, the config has no key
for either; ``rope_theta: null`` is read as NO rotary embedding (the
recurrent layers carry position).

Straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, one layer's weights upcast at a
time, rows through the head in blocks of ``BLOCK``. Imports nothing from
the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``; ``linear``, a tuple of three trees (one for each place
in the period) stacked [periods, ...]:
``wq``/``wk [E, H*dk]``, ``wv``/``wg [E, H*dv]``, ``wa``/``wb [E, H]``,
``wo [H*dv, E]``, ``conv_w [K, 2*H*dk + H*dv]`` (q, k, v channels side by
side), ``A_log``/``dt_bias [H]``, ``o_norm [dv]``; ``full``, stacked
[periods, ...]: ``wq``/``wk``/``wv``/``wo [E, E]``, ``q_norm``/``k_norm
[E]``; both with ``attn_norm``, ``mlp_norm [E]`` (the norms AFTER the
mixer and after the SwiGLU), ``w_gate``/``w_up [E, F]``, ``w_down [F,
E]``; ``final_norm [E]``, ``lm_head [E, V]``; all applied as ``x @ W``.

Switches, each a model wrong in one way, for the comparisons that have to
fail: ``beta_single`` (beta not doubled), ``no_decay`` (alpha = 1),
``no_conv`` (the convolution left out: only the token's own tap),
``rope_theta=<theta>`` (a rotary embedding put on the full layers),
``round_to=<dtype>`` (every weight rounded through a lower precision;
``float8_e4m3fn`` is the nearest below bfloat16: the contract's control),
and ``skip_layer``.
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


def swiglu(x, lp):
    gate, up, down = (lp[k].astype(F32) for k in ("w_gate", "w_up", "w_down"))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv_silu(x, w, *, own_tap_only: bool):
    """x [T, C], w [K, C]: y[t] = sum_i w[i] x[t - (K-1) + i], zeros before
    position 0; then SiLU."""
    k = w.shape[0]
    if own_tap_only:
        return jax.nn.silu(x * w[k - 1])
    t = x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(xp[i:i + t] * w[i] for i in range(k)))


@functools.partial(jax.jit, static_argnames=(
    "heads", "dk", "dv", "eps", "beta_scale", "decay", "conv"))
def linear_mixer(x, lp, *, heads, dk, dv, eps, beta_scale, decay, conv):
    """x [T, E] -> the linear layer's mixer output [T, E], token by token."""
    t = x.shape[0]
    w = {k: v.astype(F32) for k, v in lp.items()}
    pre = jnp.concatenate([x @ w["wq"], x @ w["wk"], x @ w["wv"]], axis=-1)
    c = conv_silu(pre, w["conv_w"], own_tap_only=not conv)
    q, k, v = jnp.split(c, [heads * dk, 2 * heads * dk], axis=-1)
    q = l2norm(q.reshape(t, heads, dk)) * dk ** -0.5
    k = l2norm(k.reshape(t, heads, dk))
    v = v.reshape(t, heads, dv)
    beta = beta_scale * jax.nn.sigmoid(x @ w["wb"])                # [T, H]
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(
        x @ w["wa"] + w["dt_bias"])) if decay else jnp.ones((t, heads), F32)

    def token(s, row):
        qt, kt, vt, bt, at = row
        s = s * at[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hkd,hk->hd", s, kt))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkd,hk->hd", s, qt)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), F32),
                        (q, k, v, beta, alpha))
    gate = jax.nn.silu((x @ w["wg"]).reshape(t, heads, dv))
    y = rms_norm(o, w["o_norm"], eps) * gate
    return y.reshape(t, heads * dv) @ w["wo"]


def rope(x, theta):
    """Split-half rotation over a head [T, H, D] at positions 0..T-1."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "rope_theta"))
def full_mixer(x, lp, *, heads, eps, rope_theta):
    """x [T, E] -> the full layer's mixer output: QK-norm over the whole
    width, causal softmax attention, rows in blocks of BLOCK."""
    t, e = x.shape
    d = e // heads
    w = {k: v.astype(F32) for k, v in lp.items()}
    q = rms_norm(x @ w["wq"], w["q_norm"], eps).reshape(t, heads, d)
    k = rms_norm(x @ w["wk"], w["k_norm"], eps).reshape(t, heads, d)
    v = (x @ w["wv"]).reshape(t, heads, d)
    if rope_theta:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    outs = []
    for a in range(0, t, BLOCK):
        s = jnp.einsum("thd,nhd->htn", q[a:a + BLOCK], k) * d ** -0.5
        ok = (jnp.arange(t)[None, :]
              <= (a + jnp.arange(min(BLOCK, t - a)))[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("htn,nhd->thd", p, v))
    return jnp.concatenate(outs).reshape(t, e) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def block(x, mixed, lp, *, eps):
    h = x + rms_norm(mixed, lp["attn_norm"], eps)
    return h + rms_norm(swiglu(h, lp), lp["mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm, eps) @ out_proj.astype(F32)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           beta_single: bool = False, no_decay: bool = False,
           no_conv: bool = False, rope_theta=0.0,
           round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host. `spec` holds the
    published keys (``sizes`` lists them). `skip_layer` leaves one layer
    out, the switches each break one mechanism, and `round_to` rounds every
    weight through that type on its way in: the checks of the check."""
    per = len(params["linear"]) + 1
    n_layers = params["full"]["wq"].shape[0] * per
    eps = float(spec["rms_norm_eps"])
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % PAD)

    def held(a):
        return a if round_to is None else a.astype(round_to).astype(a.dtype)

    allow = bool(spec.get("linear_allow_neg_eigval"))
    with jax.default_matmul_precision("highest"):
        x = held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i in range(n_layers):
            if i == skip_layer:
                continue
            pi, j = divmod(i, per)
            if j < per - 1:
                lp = jax.tree_util.tree_map(lambda a: held(a[pi]),
                                            params["linear"][j])
                mixed = linear_mixer(
                    x, lp, heads=spec["linear_num_key_heads"],
                    dk=spec["linear_key_head_dim"],
                    dv=spec["linear_value_head_dim"], eps=eps,
                    beta_scale=2.0 if allow and not beta_single else 1.0,
                    decay=not no_decay, conv=not no_conv)
            else:
                lp = jax.tree_util.tree_map(lambda a: held(a[pi]),
                                            params["full"])
                mixed = full_mixer(x, lp, heads=spec["num_attention_heads"],
                                   eps=eps, rope_theta=float(rope_theta or 0.0))
            x = block(x, mixed, lp, eps=eps)
        out = held(params["embed"].T if spec.get("tie_word_embeddings")
                   else params["lm_head"])
        norm = held(params["final_norm"])
        return np.concatenate([
            np.asarray(head(x[a:a + BLOCK], norm, out, eps=eps))
            for a in range(0, n, BLOCK)])[:n]


def sizes(cfg) -> dict:
    """The published keys this module reads, from a program ModelConfig
    (a rehearsal's tiny preset has no file of them)."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "rms_norm_eps": cfg.rms_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "linear_num_key_heads": cfg.linear_num_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel,
        "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
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
