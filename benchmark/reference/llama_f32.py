"""Plain float32 reference of the Llama/Mistral decoder, written from the
published description (Touvron et al. 2023; Jiang et al. 2023, "Mistral
7B"; the Hugging Face ``MistralForCausalLM`` layout): token embedding,
then per layer RMSNorm -> grouped-query causal self-attention with rotary
position embedding (split-half ``rotate_half`` pairing) -> residual ->
RMSNorm -> SwiGLU -> residual, then a final RMSNorm and the output head.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, float32
under ``default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes). One layer's weights are upcast at a time,
so a bf16 tree that fills most of a chip can still be checked. Imports
nothing from the program.

Weights arrive in the program's layout, which is the only thing shared
with it: ``embed [V, E]``, ``layers`` with every leaf stacked on a leading
``[L]`` axis (``attn_norm``, ``wq [E, H*D]``, ``wk``/``wv [E, KVH*D]``,
``wo [H*D, E]``, ``mlp_norm``, ``w_gate``/``w_up [E, F]``, ``w_down [F, E]``,
all applied as ``x @ W``), ``final_norm [E]`` and ``lm_head [E, V]`` (absent
when the embedding is tied). Sizes come from the configuration file's
published ``config.json`` keys. Departures from the publication: none.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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
                                             "eps", "theta", "window"))
def layer(x, lp, *, heads, kv_heads, head_dim, eps, theta, window):
    """One decoder layer over x [T, E]; `lp` is that layer's weights in
    their stored type, upcast here."""
    lp = jax.tree_util.tree_map(lambda a: a.astype(F32), lp)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"]).reshape(t, heads, head_dim)
    k = (h @ lp["wk"]).reshape(t, kv_heads, head_dim)
    v = (h @ lp["wv"]).reshape(t, kv_heads, head_dim)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
    dist = pos[:, None] - pos[None, :]
    allowed = dist >= 0
    if window:
        allowed = allowed & (dist < window)
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(t, heads * head_dim) @ lp["wo"]
    h = rms_norm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, out_proj, *, eps):
    return rms_norm(x, final_norm.astype(F32), eps) @ out_proj.astype(F32)


def logits(params, spec: dict, tokens, skip_layer: int | None = None):
    """tokens [T] -> float32 logits [T, V]. `spec` holds the published
    keys (num_attention_heads, num_key_value_heads, head_dim, rms_norm_eps,
    rope_theta, sliding_window, tie_word_embeddings). `skip_layer` leaves
    one layer out: the check of the check."""
    heads = spec["num_attention_heads"]
    head_dim = spec.get("head_dim") or spec["hidden_size"] // heads
    n_layers = params["layers"]["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(n_layers):
            if i == skip_layer:
                continue
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x = layer(x, lp, heads=heads,
                      kv_heads=spec["num_key_value_heads"], head_dim=head_dim,
                      eps=float(spec["rms_norm_eps"]),
                      theta=float(spec["rope_theta"]),
                      window=int(spec.get("sliding_window") or 0))
        out = (params["embed"].T if spec.get("tie_word_embeddings")
               else params["lm_head"])
        return head(x, params["final_norm"], out,
                    eps=float(spec["rms_norm_eps"]))


def penalized(rows, tokens, first: int, penalty: float, last_n: int):
    """llama.cpp's repeat penalty (Keskar et al. 2019, CTRL), as Ollama
    applies it by default: at the position that predicts ``tokens[p]``,
    every token among the last `last_n` of ``tokens[:p]`` has its logit
    divided by `penalty` if positive and multiplied by it if not.
    ``rows[i]`` are the logits that predict ``tokens[first + i]``."""
    if penalty == 1.0 or last_n <= 0:
        return rows
    # which tokens each row has seen is bookkeeping, kept on the host: one
    # mask, where a scatter a row on the device was a program a window
    # length (and, on rows sharded over chips, seconds each to compile)
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
    rows = penalized(ref_logits[n_prompt - 1: len(tokens) - 1], tokens,
                     n_prompt, penalty, last_n)
    served = jnp.asarray(tokens[n_prompt:])
    picked = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    return rows.max(axis=-1) - picked, jnp.abs(rows).max(axis=-1)
