"""Plain float32 reference of the SmallThinker decoder, written from the
model's published ``config.json`` (PowerInfer/SmallThinker-21BA3B-Instruct)
and the family's description ("SWA(4096); NoPE global; 64 experts, top-6,
0 shared; sparse ReGLU; router placed before attention"). Layer ``l``,
input ``x``, no bias anywhere, RMSNorm with ``rms_norm_eps``:

    h  = RMSNorm(x; attn_norm);  r = h            (the router's input)
    q, k, v = h Wq, h Wk, h Wv                    (heads x head_dim)
    rope_layout[l] == 1: rotate-half RoPE (theta) on q and k; 0: none
    a  = causal softmax attention, scale head_dim**-0.5, grouped query;
         sliding_window_layout[l] == 1: keys at distance < window only
    x1 = x + a Wo;  m = RMSNorm(x1; mlp_norm)
    s  = r W_router (64 logits);  (s_top, idx) = top-k(s);  p = softmax(s_top)
    x2 = x1 + sum_j p_j * W_down[idx_j](relu(W_gate[idx_j] m) * (W_up[idx_j] m))

then a final RMSNorm and the untied output head.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, float32
under ``default_matmul_precision("highest")``. One layer's weights are
upcast at a time and rows go through attention and the experts in blocks
of ``BLOCK``, so a bf16 tree that fills most of a chip can still be
checked at several thousand positions; every expert computes every row of
a block and the router's weights (0 for the unchosen) pick. Imports
nothing from the program.

Weights arrive in the program's layout, the only thing shared with it:
``embed [V, E]``, ``layers`` with every leaf stacked on a leading ``[L]``
axis (``attn_norm``, ``wq [E, H*D]``, ``wk``/``wv [E, KVH*D]``, ``wo [H*D,
E]``, ``mlp_norm``, ``router [E, X]``, ``we_gate``/``we_up [X, E, F]``,
``we_down [X, F, E]``, all applied as ``x @ W``), ``final_norm [E]``,
``lm_head [E, V]``. Inferences (the configuration file lists them under
``assumed``): rotate-half pairing over the whole head; the window counts
the query itself (distance 0 .. window-1); ``relu(gate) * up``.

``logits`` takes four switches of its own, each a model that is wrong in
one way, for the comparisons that have to fail: ``window=False`` (every
layer global), ``rope_everywhere=True`` (RoPE in the NoPE layers too),
``router_post_attn=True`` (the router fed ``m``), and ``round_to=<dtype>``
(every weight rounded through a lower precision, ``float8_e4m3fn`` being
the nearest below bfloat16: the contract's control).

**A router's tie is not judged** (``ROUTER_TIE``, ``margins``). A top-k
router is a discontinuity: where the k-th and (k+1)-th logits of a layer
lie closer together than the served precision resolves, the model in
bfloat16 and this one in float32 may each take a different expert, both
rightly, and the token then served is the one the other set prefers (a
shortfall of 0.1-0.6 at that one position where every other is under
0.05: PERF.md, PR 33). ``logits`` therefore returns its array with
``router_gap [T, layers]`` attached: each layer's k-th less (k+1)-th
router logit over the root mean square of that row's logits, and
``margins`` reads a position whose logits came through such a tie in any
layer as 0: it is counted and not judged. Every other position is held to
the configuration's limits as a dense model's are.
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
# a router's k-th and (k+1)-th logits nearer than this share of the row's
# root mean square are a tie: bfloat16's epsilon, the step between two
# neighbouring values it can hold (8 significant bits). The router reads a
# state that has been rounded to that step once or twice a layer on its
# way; the configuration's file has the readings that the choice rests on
ROUTER_TIE = 2.0 ** -7


class RoutedLogits(np.ndarray):
    """float32 logits [T, V] that carry ``router_gap`` [T, layers]."""

    router_gap = None


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, heads, D]; pairs lane i with lane i + D/2."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "eps", "theta", "use_rope"))
def qkv(x, lp, *, heads, kv_heads, head_dim, eps, theta, use_rope):
    """x [T, E] -> (h, q [T, H, D], k, v [T, H, D] with each KV head
    repeated for its group of query heads)."""
    t = x.shape[0]
    h = rms_norm(x, lp["attn_norm"].astype(F32), eps)
    q = (h @ lp["wq"].astype(F32)).reshape(t, heads, head_dim)
    k = (h @ lp["wk"].astype(F32)).reshape(t, kv_heads, head_dim)
    v = (h @ lp["wv"].astype(F32)).reshape(t, kv_heads, head_dim)
    if use_rope:
        pos = jnp.arange(t)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = heads // kv_heads
    return h, q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


@functools.partial(jax.jit, static_argnames=("first", "window"))
def attend(q, k, v, *, first, window):
    """Queries at positions first .. first + len(q) - 1 over all keys."""
    d = q.shape[-1]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(d))
    dist = (first + jnp.arange(q.shape[0]))[:, None] - jnp.arange(k.shape[0])[None, :]
    allowed = dist >= 0
    if window:
        allowed = allowed & (dist < window)
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return att.reshape(q.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def experts(x1, r, lp, *, eps, top_k):
    """x1 [T, E] (after attention), r [T, E] (the router's input) ->
    (x2, [T] the k-th less the (k+1)-th router logit over the row's rms)."""
    m = rms_norm(x1, lp["mlp_norm"].astype(F32), eps)
    s = r @ lp["router"].astype(F32)                         # [T, X]
    s_more, idx = jax.lax.top_k(s, top_k + 1)
    gap = ((s_more[:, top_k - 1] - s_more[:, top_k])
           / jnp.sqrt(jnp.mean(s * s, axis=-1)))
    s_top, idx = s_more[:, :top_k], idx[:, :top_k]
    p = jax.nn.softmax(s_top, axis=-1)
    rows = jnp.arange(s.shape[0])[:, None]
    weight = jnp.zeros_like(s).at[rows, idx].set(p)          # [T, X], 0 = unchosen
    g = jnp.einsum("te,xef->txf", m, lp["we_gate"].astype(F32))
    u = jnp.einsum("te,xef->txf", m, lp["we_up"].astype(F32))
    y = jax.nn.relu(g) * u * weight[..., None]
    return x1 + jnp.einsum("txf,xfe->te", y, lp["we_down"].astype(F32)), gap


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm.astype(F32), eps) @ out_proj.astype(F32)


def layer(x, lp, spec: dict, *, window: int, use_rope: bool,
          router_post_attn: bool):
    heads = spec["num_attention_heads"]
    eps = float(spec["rms_norm_eps"])
    h, q, k, v = qkv(x, lp, heads=heads, kv_heads=spec["num_key_value_heads"],
                     head_dim=spec["head_dim"], eps=eps,
                     theta=float(spec["rope_theta"]), use_rope=use_rope)
    out, gaps = [], []
    for a in range(0, x.shape[0], BLOCK):
        b = min(a + BLOCK, x.shape[0])
        x1 = x[a:b] + attend(q[a:b], k[:b], v[:b], first=a,
                             window=window) @ lp["wo"].astype(F32)
        r = (rms_norm(x1, lp["mlp_norm"].astype(F32), eps)
             if router_post_attn else h[a:b])
        x2, gap = experts(x1, r, lp, eps=eps,
                          top_k=spec["moe_num_active_primary_experts"])
        out.append(x2)
        gaps.append(gap)
    return jnp.concatenate(out), jnp.concatenate(gaps)


def logits(params, spec: dict, tokens, skip_layer: int | None = None, *,
           window: bool = True, rope_everywhere: bool = False,
           router_post_attn: bool = False, round_to: str | None = None):
    """tokens [T] -> float32 logits [T, V] on the host, as `RoutedLogits`
    (``router_gap [T, layers]`` attached). `spec` holds the published keys
    (``sizes`` lists them). `skip_layer` leaves one layer out, the three
    switches each break one mechanism, and `round_to` rounds every weight
    through that type on its way in: the checks of the check."""
    n_layers = params["layers"]["wq"].shape[0]
    n = len(tokens)
    tokens = list(tokens) + [0] * (-n % PAD)

    def held(a):
        return a if round_to is None else a.astype(round_to).astype(a.dtype)

    gaps = []
    with jax.default_matmul_precision("highest"):
        x = held(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i in range(n_layers):
            if i == skip_layer:
                continue
            lp = jax.tree_util.tree_map(lambda a: held(a[i]), params["layers"])
            slides = window and spec["sliding_window_layout"][i]
            x, gap = layer(
                x, lp, spec,
                window=int(spec["sliding_window_size"]) if slides else 0,
                use_rope=bool(rope_everywhere or spec["rope_layout"][i]),
                router_post_attn=router_post_attn)
            gaps.append(np.asarray(gap))
        out = held(params["embed"].T if spec.get("tie_word_embeddings")
                   else params["lm_head"])
        norm = held(params["final_norm"])
        # a block of rows at a time, gathered on the host: at 151,936 ids
        # the logits of 4,700 positions are 2.9 GB beside the tree
        rows = np.concatenate([
            np.asarray(head(x[a:a + BLOCK], norm, out,
                            eps=float(spec["rms_norm_eps"])))
            for a in range(0, n, BLOCK)])[:n].view(RoutedLogits)
    rows.router_gap = np.stack(gaps, axis=-1)[:n]
    return rows


def sizes(cfg) -> dict:
    """The published keys `logits` reads, from an object with the
    program's field names: in a rehearsal a tiny preset stands under the
    configuration file's name."""
    n = cfg.num_layers
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "sliding_window_layout": list(cfg.window_layout or (1,) * n),
        "rope_layout": list(cfg.rope_layout or (1,) * n),
        "moe_num_primary_experts": cfg.num_experts,
        "moe_num_active_primary_experts": cfg.experts_per_token,
        "moe_ffn_hidden_size": cfg.intermediate_size,
        "tie_word_embeddings": cfg.tie_embeddings,
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
    if gap is not None and tie:
        tied = (gap[n_prompt - 1: len(tokens) - 1] < tie).any(axis=-1)
        short = jnp.where(jnp.asarray(tied), 0.0, short)
    return short, jnp.abs(rows).max(axis=-1)
