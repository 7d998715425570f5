"""Ring attention: sequence-parallel causal prefill over the "sp" mesh axis.

Long-context design (SURVEY.md §5.7: the reference's only long-context
story was forwarding `num_ctx` to Ollama; sequence parallelism is new
capability). The sequence dimension is sharded across sp devices; each
device keeps its Q chunk resident and the K/V chunks rotate around the
ring via `jax.lax.ppermute` (neighbour hops ride ICI — mesh.py puts "sp"
innermost so ring neighbours are ICI-adjacent). Online-softmax merging
makes the result exactly equal to full causal attention: per rotation
step each device folds one K/V chunk into its running (max, denom, acc)
triple, fp32 throughout.

Communication cost: n-1 neighbour exchanges of the local K/V chunk
(2·T/n·KVH·D each) fully overlappable with the chunk's attention math;
peak memory is O(T/n) per device instead of the O(T) an all-gather of
K/V would need — the property that makes million-token contexts feasible
(PAPERS.md ring/blockwise attention — pattern reference only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gridllm_tpu.ops.kvcache import _shard_map_kernel

_NEG_INF = -1e30


def _chunk_attention(q, k, v, q_start, k_start, seq_lens, carry):
    """Fold one K/V chunk into the online-softmax carry.

    q: [B, C, KVH, G, D] (fp32, pre-scaled); k/v: [B, C, KVH, D];
    q_start/k_start: scalar global offsets of the chunks;
    carry: (m [B,C,KVH,G,1], l [B,C,KVH,G,1], acc [B,C,KVH,G,D]).
    """
    m, l, acc = carry
    # fp32 by the caller's contract (q pre-scaled, carries f32); the casts
    # are no-ops there and enforce the policy for any other caller
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    c = q.shape[1]
    logits = jnp.einsum(
        "btkgd,bskd->btkgs", q, k, precision=jax.lax.Precision.HIGHEST
    )  # [B, Cq, KVH, G, Ck]
    q_pos = q_start + jnp.arange(c)[:, None, None, None]        # [Cq,1,1,1]
    k_pos = k_start + jnp.arange(c)[None, None, None, :]        # [1,1,1,Ck]
    valid = k_pos < seq_lens[:, None, None, None, None]
    mask = (q_pos >= k_pos)[None] & valid
    logits = jnp.where(mask, logits, _NEG_INF)

    m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l * alpha + p.sum(axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "btkgs,bskd->btkgd", p, v, precision=jax.lax.Precision.HIGHEST
    )
    return m_new, l_new, acc_new


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,
    mesh: Mesh,
) -> jnp.ndarray:
    """Causal GQA attention with the T axis sharded over mesh axis "sp".

    Same contract as ops.attention.attention_prefill: q [B, T, H, D],
    k/v [B, T, KVH, D], seq_lens [B] → [B, T, H, D]. T must divide by
    sp. Callable inside jit; sharding constraints are applied here so the
    caller does not need pre-sharded operands.
    """
    n = mesh.shape["sp"]
    b, t, h, d = q.shape
    if n == 1 or t % n:
        # sp=1, or a bucket too ragged to split (trace-time check; every
        # standard prefill bucket divides by sp <= 64)
        if n > 1:
            import warnings

            warnings.warn(
                f"ring_attention: T={t} not divisible by sp={n}; falling "
                "back to full (quadratic-memory) attention for this bucket "
                "— fix the prefill bucket sizes", stacklevel=2,
            )
        from gridllm_tpu.ops.attention import attention_prefill_ref

        return attention_prefill_ref(q, k, v, seq_lens)

    kvh = k.shape[2]
    g = h // kvh
    c = t // n
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    # also split kv heads over "tp" when divisible — without this a tp x sp
    # mesh would all-gather heads at the shard_map boundary and compute all
    # H heads on every tp device (tp-fold redundant attention FLOPs)
    tp = mesh.shape["tp"]
    head_ax = "tp" if (tp > 1 and kvh % tp == 0) else None

    def local(q_loc, k_loc, v_loc, lens):
        # q_loc: [B, C, H/tp, D]; k_loc/v_loc: [B, C, KVH/tp, D]; lens: [B]
        i = jax.lax.axis_index("sp")
        kvh_l = k_loc.shape[2]
        qf = (q_loc.astype(jnp.float32) * scale).reshape(b, c, kvh_l, g, d)
        m = jnp.full((b, c, kvh_l, g, 1), _NEG_INF, jnp.float32)
        l = jnp.zeros((b, c, kvh_l, g, 1), jnp.float32)
        acc = jnp.zeros((b, c, kvh_l, g, d), jnp.float32)
        kv = (k_loc.astype(jnp.float32), v_loc.astype(jnp.float32))
        perm = [(p, (p + 1) % n) for p in range(n)]

        carry = (m, l, acc)
        for step in range(n):
            j = (i - step) % n  # chunk id this device currently holds
            carry = _chunk_attention(
                qf, kv[0], kv[1], i * c, j * c, lens, carry
            )
            if step != n - 1:
                # rotate AFTER compute so the transfer overlaps the next
                # step's math under XLA's async collectives
                kv = jax.lax.ppermute(kv, "sp", perm)
        _, l, acc = carry
        out = acc / jnp.maximum(l, 1e-30)
        return out.reshape(b, c, kvh_l * g, d).astype(q_loc.dtype)

    # vma checking off (the kernel wrapper's setting): ppermute's value
    # motion defeats the check
    sm = _shard_map_kernel(
        mesh, local,
        in_specs=(
            P(None, "sp", head_ax),
            P(None, "sp", head_ax),
            P(None, "sp", head_ax),
            P(),
        ),
        out_specs=P(None, "sp", head_ax),
    )
    return sm(q, k, v, seq_lens.astype(jnp.int32))
