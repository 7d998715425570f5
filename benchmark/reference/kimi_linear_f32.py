"""Plain float32 reference of the Kimi-Linear decoder, written from the
model's published ``config.json`` (moonshotai/Kimi-Linear-48B-A3B-Instruct,
``model_type: kimi_linear``) and its technical report (arXiv:2510.26692):
Kimi Delta Attention (KDA) 3:1 with latent attention (MLA) without
positional encoding, a dense first layer, then routed experts behind a
sigmoid router. The recurrence runs TOKEN BY TOKEN: no chunked form,
kernel, cache or snapshot appears. Pre-norm residual blocks, RMSNorm with
``rms_norm_eps``, no bias anywhere, no positional encoding anywhere
(``mla_use_nope``; KDA has none by construction), untied head.

A KDA layer (1-based index in ``linear_attn_config.kda_layers``; H =
``num_heads``, dk = dv = ``head_dim``, a convolution of
``short_conv_kernel_size`` taps), for the normed input x of a token:

    q~ = SiLU(conv(W_q x))   k~ = SiLU(conv(W_k x))   v = SiLU(conv(W_v x))
        three depthwise causal convolutions, tap K-1 on the token itself
    q = q~ / |q~|_2 * dk^-0.5      k = k~ / |k~|_2            (L2 a head)
    g = -exp(A_log_h) * softplus(W_f_up (W_f_down x) + dt_bias)   [H, dk]
        the log decay a head A KEY CHANNEL; alpha = exp(g) in (0, 1)
    beta = sigmoid(W_beta x)                                        [H]
    S <- Diag(alpha) S              S [dk, dv] a head, zero at position 0
    S <- S + k (beta (v - S^T k))^T                    the delta rule
    o = S^T q
    y = W_o [ RMSNorm_dv(o; w) * sigmoid(W_g_up (W_g_down x)) ]

An MLA layer (``full_attn_layers``; ``num_attention_heads`` heads of
``qk_nope_head_dim`` + ``qk_rope_head_dim``, values of ``v_head_dim``, a
latent of ``kv_lora_rank``, no q_lora): q = W_q x; [c, k_r] = W_kva x; c <-
RMSNorm(c); [k_nope, v] = W_kvb c a head; k = [k_nope, k_r] with k_r shared
by the heads and NOT rotated; causal softmax of q.k (dn + dr)^-0.5; W_o.

The feed-forward: a SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` layers; after them s = sigmoid(m W_r) over the
router's experts (float32), the top ``num_experts_per_token`` of s + b (b
the selection bias: it chooses, it never weighs), weights s_i / sum_chosen
s * ``routed_scaling_factor``, SwiGLU experts on the OUTPUT, plus one shared
SwiGLU expert unweighted.

**A share of the experts.** The configuration holds ``num_experts`` of the
router's ``router_experts`` from ``experts_first`` (one chip of the
expert-parallel group that shares each layer): the router, its top-k and
the normalisation over ALL the chosen are the whole model's; a pick of an
expert that is not held adds nothing, here as in the program, and that
partial sum is what goes on to the next layer. The expert leaves hold the
held experts only.

Inferences (the configuration's file lists them under ``assumed``): the
equations are written from the keys and the report, not from a modeling
file, which is not here; the selection bias (``use_grouped_topk`` with
sigmoid scores is the DeepSeek-V3 form of gate, whose bias is a checkpoint
buffer and not a key); the low-rank width of the decay's and the gate's
pair (``linear_attn_config.head_dim``); the place of dk^-0.5 (on q); L2
normalisation as x * rsqrt(sum x^2 + 1e-6); SiLU as the convolution's
activation (``hidden_act``).

Straightforward ``jax.numpy``: float32 under
``default_matmul_precision("highest")``, one layer's weights upcast at a
time, the experts a group at a time, rows through attention, the experts
and the head in blocks of ``BLOCK``. Imports nothing from the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``; ``layers``, a tuple with one tree a layer, each with
``attn_norm``, ``mlp_norm [E]``; a KDA layer ``wq``/``wk [E, H*dk]``, ``wv
[E, H*dv]``, ``conv_w [K, 2*H*dk + H*dv]`` (q, k, v channels side by side),
``wf_down [E, r]``, ``wf_up [r, H*dk]``, ``A_log [H]``, ``dt_bias [H*dk]``,
``wb [E, H]``, ``wg_down [E, r]``, ``wg_up [r, H*dv]``, ``o_norm [dv]``,
``wo [H*dv, E]``; an MLA layer ``wq [E, H*(dn+dr)]``, ``w_kva [E, R+dr]``,
``kv_norm [R]``, ``w_kvb [R, H*(dn+dv)]``, ``wo [H*dv, E]``; a dense layer
``w_gate``/``w_up [E, F]``, ``w_down [F, E]``; a sparse one ``router [E,
X]``, ``router_bias [X]``, ``we_gate``/``we_up [held, E, Fm]``, ``we_down
[held, Fm, E]``, ``ws_gate``/``ws_up [E, Fs]``, ``ws_down [Fs, E]``;
``final_norm [E]``, ``lm_head [E, V]``; all applied as ``x @ W``.

Switches, each a model wrong in one way, for the comparisons that have to
fail: ``scalar_decay`` (a head's channels all decay at their mean rate:
the gated delta rule KDA refines), ``no_decay`` (alpha = 1), ``no_conv``
(only the token's own tap), ``no_bias`` (the top-k of the scores alone),
``state_dtype=<dtype>`` (the state rounded through that type after every
token), ``round_to=<dtype>`` (every weight rounded through a lower
precision; ``float8_e4m3fn`` is the nearest below bfloat16: the contract's
control), and ``skip_layer``.

**A router's tie is not judged** (``ROUTER_TIE``, ``margins``), as in
``laguna_f32.py``: where the k-th and (k+1)-th of the values the router
chooses by lie nearer than ``ROUTER_TIE`` (in logit units over the row's
rms: the gap in s + b over the sigmoid's largest slope, 1/4), the served
bfloat16 model and this one may each rightly take another expert. Of a
share only a tie that an expert HELD HERE takes part in counts: which of
two absent experts is chosen moves nothing here but the normalisation, by
the gap itself.
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
# experts upcast to float32 at a time
EXPERT_GROUP = 32
# the gap under which a position is not judged (module docstring)
ROUTER_TIE = 2.0 ** -5


# a layer's leaves that are its feed-forward's; the others are its mixer's
FFN_KEYS = frozenset((
    "mlp_norm", "w_gate", "w_up", "w_down", "router", "router_bias",
    "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down"))


class RoutedLogits(np.ndarray):
    """float32 logits [T, V] that carry ``router_gap`` [T, layers]."""

    router_gap = None


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight.astype(F32)


def swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate.astype(F32)) * (m @ up.astype(F32))) @ down.astype(F32)


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
    "heads", "eps", "decay", "conv", "state_dtype"))
def kda_mixer(x, lp, *, heads, eps, decay, conv, state_dtype):
    """The normed input x [T, E] -> the KDA mixer's output [T, E], token
    by token. `decay`: "channel" (as published), "scalar" or "none"."""
    t = x.shape[0]
    w = {k: v.astype(F32) for k, v in lp.items()}
    dk, dv = w["wq"].shape[1] // heads, w["wv"].shape[1] // heads
    pre = jnp.concatenate([x @ w["wq"], x @ w["wk"], x @ w["wv"]], axis=-1)
    c = conv_silu(pre, w["conv_w"], own_tap_only=not conv)
    q, k, v = jnp.split(c, [heads * dk, 2 * heads * dk], axis=-1)
    q = l2norm(q.reshape(t, heads, dk)) * dk ** -0.5
    k = l2norm(k.reshape(t, heads, dk))
    v = v.reshape(t, heads, dv)
    beta = jax.nn.sigmoid(x @ w["wb"])                           # [T, H]
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(
        (x @ w["wf_down"]) @ w["wf_up"] + w["dt_bias"]).reshape(t, heads, dk)
    if decay == "scalar":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    alpha = jnp.exp(g) if decay != "none" else jnp.ones_like(g)

    def token(s, row):
        qt, kt, vt, bt, at = row
        s = s * at[:, :, None]
        u = bt[:, None] * (vt - jnp.einsum("hkd,hk->hd", s, kt))
        s = s + kt[:, :, None] * u[:, None, :]
        if state_dtype is not None:
            s = s.astype(state_dtype).astype(F32)
        return s, jnp.einsum("hkd,hk->hd", s, qt)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), F32),
                        (q, k, v, beta, alpha))
    gate = jax.nn.sigmoid(
        ((x @ w["wg_down"]) @ w["wg_up"]).reshape(t, heads, dv))
    y = rms_norm(o, w["o_norm"], eps) * gate
    return y.reshape(t, heads * dv) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("heads", "dn", "dr", "eps"))
def mla_mixer(x, lp, *, heads, dn, dr, eps):
    """The normed input x [T, E] -> the MLA mixer's output: K and V rebuilt
    a head from the latent, the shared key NOT rotated, causal softmax,
    rows in blocks of BLOCK."""
    t = x.shape[0]
    w = {k: v.astype(F32) for k, v in lp.items()}
    r = w["kv_norm"].shape[0]
    q = (x @ w["wq"]).reshape(t, heads, dn + dr)
    kva = x @ w["w_kva"]
    c = rms_norm(kva[:, :r], w["kv_norm"], eps)
    kvb = (c @ w["w_kvb"]).reshape(t, heads, -1)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(kva[:, None, r:], (t, heads, dr))],
        axis=-1)
    v = kvb[..., dn:]
    outs = []
    for a in range(0, t, BLOCK):
        s = jnp.einsum("thd,nhd->htn", q[a:a + BLOCK], k) * (dn + dr) ** -0.5
        ok = (jnp.arange(t)[None, :]
              <= (a + jnp.arange(min(BLOCK, t - a)))[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("htn,nhd->thd", p, v))
    return jnp.concatenate(outs).reshape(t, -1) @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x1, lp, *, eps):
    m = rms_norm(x1, lp["mlp_norm"], eps)
    return x1 + swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "scaling", "norm", "first", "bias"))
def experts(x1, lp, *, eps, top_k, scaling, norm, first, bias):
    """x1 [T, E] (after the mixer) -> (x2, [T] the gap of the router's
    choice, see ROUTER_TIE). The held experts are [first, first + held) of
    the router's, held = the expert leaves' leading size."""
    m = rms_norm(x1, lp["mlp_norm"], eps)
    s = m @ lp["router"].astype(F32)                         # [T, X]
    scores = jax.nn.sigmoid(s)
    choose = scores + (lp["router_bias"].astype(F32) if bias else 0.0)
    c_more, i_more = jax.lax.top_k(choose, top_k + 1)
    idx = i_more[:, :top_k]
    s_top = jnp.take_along_axis(scores, idx, axis=-1)
    w_top = scaling * (s_top / s_top.sum(-1, keepdims=True) if norm else s_top)
    held = lp["we_gate"].shape[0]
    here = (i_more >= first) & (i_more < first + held)
    gap = ((c_more[:, top_k - 1] - c_more[:, top_k]) * 4.0
           / jnp.sqrt(jnp.mean(s * s, axis=-1)))
    gap = jnp.where(here[:, top_k - 1] | here[:, top_k], gap, jnp.inf)
    rows = jnp.arange(s.shape[0])[:, None]
    local = jnp.where(here[:, :top_k], idx - first, held)    # absent: dropped
    weight = jnp.zeros((s.shape[0], held), F32).at[rows, local].set(
        w_top, mode="drop")
    grp = math.gcd(EXPERT_GROUP, held)

    def group(acc, xs):
        wg, wu, wd, wt = xs               # [grp, E, F] .. , wt [grp, T]
        g = jnp.einsum("te,xef->txf", m, wg.astype(F32))
        u = jnp.einsum("te,xef->txf", m, wu.astype(F32))
        y = jax.nn.silu(g) * u * wt.T[..., None]
        return acc + jnp.einsum("txf,xfe->te", y, wd.astype(F32)), None

    def split(a):
        return a.reshape(held // grp, grp, *a.shape[1:])

    routed, _ = jax.lax.scan(
        group, jnp.zeros_like(x1),
        (split(lp["we_gate"]), split(lp["we_up"]), split(lp["we_down"]),
         split(weight.T)))
    out = x1 + routed
    if "ws_gate" in lp:
        out = out + swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out, gap


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm, eps) @ out_proj.astype(F32)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           scalar_decay: bool = False, no_decay: bool = False,
           no_conv: bool = False, no_bias: bool = False,
           state_dtype: str | None = None, round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host, as `RoutedLogits`
    (``router_gap [T, sparse layers]`` attached). `spec` holds the
    published keys (``sizes`` lists them). `skip_layer` leaves one layer
    out, the switches each break one mechanism, and `round_to` rounds every
    weight through that type on its way in: the checks of the check."""
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % PAD)
    eps = float(spec["rms_norm_eps"])
    la = spec["linear_attn_config"]
    kda = set(la["kda_layers"])
    first = int(spec.get("experts_first") or 0)
    decay = "none" if no_decay else "scalar" if scalar_decay else "channel"

    def held(a):
        return a if round_to is None else a.astype(round_to).astype(a.dtype)

    gaps = []
    with jax.default_matmul_precision("highest"):
        x = held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i, tree in enumerate(params["layers"]):
            if i == skip_layer:
                continue
            lp = jax.tree_util.tree_map(held, tree)
            h = rms_norm(x, lp["attn_norm"], eps)
            mixer = {k: v for k, v in lp.items()
                     if k not in FFN_KEYS and k != "attn_norm"}
            if i + 1 in kda:
                x = x + kda_mixer(h, mixer, heads=la["num_heads"], eps=eps,
                                  decay=decay, conv=not no_conv,
                                  state_dtype=state_dtype)
            else:
                x = x + mla_mixer(h, mixer, heads=spec["num_attention_heads"],
                                  dn=spec["qk_nope_head_dim"],
                                  dr=spec["qk_rope_head_dim"], eps=eps)
            ffn = {k: v for k, v in lp.items() if k in FFN_KEYS}
            if i < spec["first_k_dense_replace"]:
                x = jnp.concatenate([dense_ffn(x[a:a + BLOCK], ffn, eps=eps)
                                     for a in range(0, x.shape[0], BLOCK)])
                continue
            out = [experts(
                x[a:a + BLOCK], ffn, eps=eps,
                top_k=spec["num_experts_per_token"],
                scaling=float(spec.get("routed_scaling_factor", 1.0)),
                norm=bool(spec.get("moe_renormalize", True)), first=first,
                bias=not no_bias) for a in range(0, x.shape[0], BLOCK)]
            x = jnp.concatenate([o for o, _ in out])
            gaps.append(np.concatenate([np.asarray(g) for _, g in out]))
        out = held(params["embed"].T if spec.get("tie_word_embeddings")
                   else params["lm_head"])
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
    kinds = cfg.layer_types
    first, held = cfg.held_experts
    return {
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "first_k_dense_replace": cfg.first_k_dense,
        "num_experts": held, "router_experts": cfg.num_experts,
        "experts_first": first,
        "num_experts_per_token": cfg.experts_per_token,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_renormalize": cfg.norm_topk_prob,
        "tie_word_embeddings": cfg.tie_embeddings,
        "linear_attn_config": {
            "num_heads": cfg.linear_num_heads,
            "kda_layers": [i + 1 for i, k in enumerate(kinds)
                           if k == "linear_attention"],
            "full_attn_layers": [i + 1 for i, k in enumerate(kinds)
                                 if k == "full_attention"]},
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
