"""The routed experts' grouped product (models/mixtral.py's third and
fourth forms): the dispatchers and the jnp references that are the
kernels' oracles (ops/kernels.py). The kernels are
`pallas_kernels.grouped_experts` (rows under the chip's ridge: all rows
against each touched expert) and `pallas_kernels.grouped_experts_sorted`
(rows past it: the rows sorted by expert, `sorted_layout`, each expert
against its own group)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from gridllm_tpu.ops.kvcache import _pallas_mode, record_kernel_path


def grouped_experts_ref(x, gates, touched, wg, wu, wd, layer=None, *,
                        act: str):
    """`pallas_kernels.grouped_experts` in plain jnp: every expert of the
    layer times every row, an expert no live row touched weighted zero.
    Operands as they come, float32 sums, one cast at the end."""
    if wg.ndim == 4:
        layer = 0 if layer is None else layer
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for w in (wg, wu, wd))
    p = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    g = jnp.einsum("te,xef->txf", x, wg, precision=p,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("te,xef->txf", x, wu, precision=p,
                   preferred_element_type=jnp.float32)
    g = jax.nn.silu(g) if act == "silu" else jnp.maximum(g, 0.0)
    on = (touched > 0).astype(jnp.float32)
    y = g * u * (gates.astype(jnp.float32) * on)[..., None]
    return jnp.einsum("txf,xfe->te", y.astype(wd.dtype), wd, precision=p,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def grouped_experts(x, gates, touched, wg, wu, wd, layer=None, *, act: str,
                    use_pallas: bool | None = None):
    """The touched experts' products of x [T, E] (the kernel has the
    contract), by the kernel where kernels are on and by the reference
    where they are not."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("grouped_experts", use)
    if not use:
        return grouped_experts_ref(x, gates, touched, wg, wu, wd, layer,
                                   act=act)
    from gridllm_tpu.ops.pallas_kernels import grouped_experts as kernel

    return kernel(x, gates, touched, wg, wu, wd, layer, act=act,
                  interpret=interpret)


def sorted_tile_rows(rows_a_group: float) -> int:
    """Rows of a row tile from the rows a group is expected to have: the
    next power of two (a group then mostly fits one tile, and a tile under
    128 rows costs the MXU what 128 do: its weights are the stationary
    operand), whole bfloat16 sublane tiles at the least, 256 at the most."""
    return int(min(256, max(16, 1 << max(0, math.ceil(rows_a_group) - 1
                                         ).bit_length())))


def _running_count(hot):
    """cumsum(hot, axis=0) of a 0 / 1 array [N, G], exactly: in blocks of
    128 rows by one triangular product (0 / 1 operands, float32 sums: the
    MXU does in microseconds what the chip's windowed cumulative sum over
    N rows took 0.1 ms a layer for), then the blocks' totals carried."""
    n, g = hot.shape
    blocks = -(-n // 128)
    h = jnp.pad(hot, ((0, blocks * 128 - n), (0, 0))
                ).astype(jnp.bfloat16).reshape(blocks, 128, g)
    r = jnp.arange(128)
    within = jnp.einsum("ij,bjg->big", (r[:, None] >= r[None, :]
                                        ).astype(jnp.bfloat16), h,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    totals = within[:, -1]
    before = jnp.cumsum(totals, axis=0) - totals
    return (within + before[:, None]).reshape(-1, g)[:n].astype(jnp.int32)


def sorted_layout(idx, groups: int, tm: int):
    """Where each pick goes when the picks are laid out by group, each
    group from a row that is a multiple of `tm`: idx [N] int32, a pick's
    group in [0, groups) or `groups` for a pick in no group. Returns
    (src [P] int32: the pick a laid-out row holds, N for a padding row;
    pos [N]: the row of a pick, 0 for a pick in no group; tile_group
    [P / tm]: the group of each tile of `tm` rows; used: the tiles that
    belong to a group, the first `used`). P is
    the static bound N + groups * (tm - 1), rounded down to whole tiles:
    no sort, a running count a group (`_running_count`) gives a pick its
    rank (stable: picks of a group keep their order)."""
    n = idx.shape[0]
    tiles = (n + groups * (tm - 1)) // tm
    hot = (idx[:, None] == jnp.arange(groups, dtype=jnp.int32)
           ).astype(jnp.int32)                                  # [N, G]
    upto = _running_count(hot)
    sizes = upto[-1]
    rank = (hot * upto).sum(axis=1) - 1             # -1: in no group
    ends = jnp.cumsum(-(-sizes // tm))              # a group's last tile + 1
    first = (ends + sizes // -tm) * tm              # a group's first row
    used = ends[-1]
    pos = jnp.where(rank >= 0, (hot * first).sum(axis=1) + rank, 0)
    src = jnp.full((tiles * tm,), n, jnp.int32).at[
        jnp.where(rank >= 0, pos, tiles * tm)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop", unique_indices=True)
    tile_group = jnp.minimum(
        (jnp.arange(tiles)[:, None] >= ends[None, :]).sum(axis=1), groups - 1)
    return src, pos, tile_group.astype(jnp.int32), used.astype(jnp.int32)


def sorted_experts_ref(x, top_w, idx, wg, wu, wd, layer=None, *, act: str):
    """`sorted_experts` in plain jnp, the all-experts way: every expert
    times every row, a pick's weight where the row picked the expert
    (idx [T, k] in [0, X), X = the pick is in no group: absent,
    zero-compute, or of a row that is not live). float32 sums."""
    nx = wg.shape[-3]
    gates = jnp.where(idx[..., None] == jnp.arange(nx),
                      top_w.astype(jnp.float32)[..., None], 0.0).sum(axis=1)
    return grouped_experts_ref(x, gates, jnp.ones((nx,), jnp.int32), wg, wu,
                               wd, layer, act=act)


def sorted_experts(x, top_w, idx, wg, wu, wd, layer=None, *, tm: int,
                   act: str, use_pallas: bool | None = None):
    """sum over a row's picks of weight * (act(x wg[j]) * (x wu[j])) wd[j],
    each expert multiplying only the rows that picked it: x [T, E]; top_w
    [T, k] float32; idx [T, k] int32, the pick's expert among the X of the
    leaves, or X for a pick that is in no group and adds nothing (an
    absent or zero-compute expert, a row that is not live or is padding).
    In XLA the picks' layout by expert (`sorted_layout`) and the rows'
    gather; in the kernel the products, an expert's slabs read once and an
    expert with no group not at all; in XLA again the picks' outputs
    gathered back a row, weighted in float32, summed in float32 and cast
    once. By the reference where kernels are off."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("grouped_experts_sorted", use)
    if not use:
        return sorted_experts_ref(x, top_w, idx, wg, wu, wd, layer, act=act)
    from gridllm_tpu.ops.pallas_kernels import grouped_experts_sorted

    t, k = idx.shape
    nx = wg.shape[-3]
    src, pos, tile_expert, used = sorted_layout(idx.reshape(-1), nx, tm)
    xs = x[jnp.minimum(src, t * k - 1) // k]     # a padding row: any row
    d = grouped_experts_sorted(xs, tile_expert, used, wg, wu, wd, layer,
                               tm=tm, act=act, interpret=interpret)
    # pick-major, so that a row's picks are k slabs of [T, E]: slices of
    # one gather, each cast, weighted and added inside one fusion (summed
    # as one [k, T, E] array the float32 copy of every pick stood in HBM
    # first). A tile past the groups is never written: what a pick in no
    # group points at is not read into the sum
    got = d[pos.reshape(t, k).T.reshape(-1)]
    w = top_w.astype(jnp.float32)
    out = jnp.zeros(x.shape, jnp.float32)
    for j in range(k):
        out += jnp.where((idx[:, j] < nx)[:, None],
                         got[j * t:(j + 1) * t].astype(jnp.float32),
                         0.0) * w[:, j, None]
    return out.astype(x.dtype)
