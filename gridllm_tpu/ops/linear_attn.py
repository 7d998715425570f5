"""The gated delta rule (linear attention with a recurrent state).

Per head, for a token with key k^ and query q^ (L2-normalised), value v,
write strength beta and log decay g (alpha = exp(g)), the state S [dk, dv]
(float32, zero at position 0) moves as

    S <- alpha S;  S <- S + k^ (beta (v - S^T k^))^T;  o = S^T q^

Two forms of it, each with a Pallas kernel (ops/pallas_kernels.py) and a
counted jnp form (`gridllm_kernel_dispatch_total{op}`):

- `gdn_chunk`: a prompt's rows of ONE slot from a carried state, in blocks
  of `block` rows (the WY / UT transform): inside a block the rows'
  corrections u solve a unit lower-triangular system that does not need
  the state (`_wy`, XLA: a batched triangular solve), and the blocks then
  chain through the state with three small matrix products each (the
  kernel). The state at the end of chosen blocks is handed back: the
  prefix cache's snapshots.
- `gdn_step`: a launch's 1 to K+1 rows of every LIVE slot. The state it
  is handed lags: the last launch's rows are `pending` (`n` of them a
  slot, the rest rejected by speculation's accept), so it first commits
  those, writes the committed state back in place, then runs the new rows
  on it without writing. One read and one write of a live slot's state a
  launch, whatever is accepted later; what does not count (a slot that is
  not live, a rejected row) is zeroed before any product.

Both are the same block update (`_wy` + `_chain`): a step's 5 rows are a
block of 8 whose padding has beta = 0, g = 0, which changes nothing.

A layer's states live packed as [slots, dk, H * dv]: the value lanes of
all heads side by side, so that the array's minor dimension is whole lane
tiles (30 x 192 = 45 x 128; a [.., 96, 192] array is stored at 256 lanes,
a third more bytes) and a kernel block of `head_pack` heads is lane-dense.

`gdn_recurrent` is the plain token-by-token form: the oracle of both.

THE STATE-SPACE SCAN (`ssd_recurrent`, `ssd_chunk`, `ssd_step`: Mamba-2's
SSD, at the end of this module) is the rule WITHOUT the delta, and with
keys and queries shared by all heads (one group: B and C): S <- alpha S +
k v^T, o = S^T q. No correction means no triangular system: a block's own
part is a masked product, and because k and q are every head's, the
state's products are ONE matrix product at the packed width (q [C, dk]
times the packed state [dk, H*dv]) with the heads' decays applied on the
lanes. Kernels of their own (`ssd_chunk`, `ssd_step` in pallas_kernels.py),
not `gdn_*`'s block update with beta switched off: that update takes k, q
and the rows' corrections a HEAD ([H, C, dk]), so it would read B and C
copied `heads` times (64 at the one family served) and spend two products
a head on a correction that is zero.

KIMI DELTA ATTENTION (`kda_recurrent`, `kda_chunk`, `kda_step`) is the same
rule with a log decay a head A KEY CHANNEL, g [.., H, dk]: S <- Diag(alpha)
S in the first line above. The two forms and the two kernels are the same
code, general over the decay's shape (the state's decay over a block is
one factor a head, or one a head and key row: the kernels apply the first
on the lanes and the second as Diag(gc) S on the MXU), and a trace finds
them under their own names. What changes is a block's pair terms,
`k_i . (exp(G_i - G_j) * k_j)` with G the running sum of g, a vector: no
longer one Gram matrix times a scalar, and the split `(k_i exp G_i) .
(k_j exp -G_j)` overflows float32 (one row's log decay can be below -5,
a block has 64). `_pairs` takes them in sub-blocks of `SUB_BLOCK` rows:
between two sub-blocks the decay is split at the boundary row r before
the later one, `exp(G_i - G_r) exp(G_r - G_j)` with i >= r >= j, two
matrix operands; inside a sub-block the 16 x 16 x dk exponents are formed
outright under the causal mask. THE BOUND: every exponent this module
forms for a channel decay is <= 0 (G falls along the rows), so no factor
exceeds 1 whatever the decay; a product that underflows is one the exact
arithmetic puts below 1e-38 of its operands.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from gridllm_tpu.ops.kvcache import _pallas_mode, record_kernel_path

HI = jax.lax.Precision.HIGHEST
STEP_ROWS = 8          # a step's rows padded to one float32 sublane tile
SUB_BLOCK = 16         # rows whose pair terms a channel decay forms outright


def head_pack(dv: int, heads: int) -> int:
    """Heads a kernel block holds side by side: the fewest whose values
    fill whole 128-lane tiles (192 -> 2), all of them where none does."""
    p = 128 // math.gcd(dv, 128)
    return p if heads % p == 0 else heads


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x_full: jnp.ndarray, w: jnp.ndarray,
                bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """Depthwise causal convolution (plus `bias` [C] where the layer has
    one) then SiLU. x_full [..., K-1+T, C]: the K-1 rows before the first,
    then the T rows; w [K, C], tap K-1 on the row itself. Returns
    [..., T, C] float32."""
    k = w.shape[0]
    t = x_full.shape[-2] - (k - 1)
    xf, wf = x_full.astype(jnp.float32), w.astype(jnp.float32)
    y = sum(xf[..., i:i + t, :] * wf[i] for i in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y)


def unpack(state: jnp.ndarray, heads: int) -> jnp.ndarray:
    """[..., dk, H*dv] -> [..., H, dk, dv]."""
    dk, hd = state.shape[-2:]
    s = state.reshape(*state.shape[:-2], dk, heads, hd // heads)
    return jnp.moveaxis(s, -2, -3)


def pack(state: jnp.ndarray) -> jnp.ndarray:
    """[..., H, dk, dv] -> [..., dk, H*dv]."""
    s = jnp.moveaxis(state, -3, -2)
    return s.reshape(*s.shape[:-2], -1)


def gdn_recurrent(state, q, k, v, b, g):
    """Token by token. state [H, dk, dv]; q, k [T, H, dk]; v [T, H, dv];
    b [T, H]; g [T, H], or [T, H, dk] for a decay a key channel. Returns
    (o [T, H, dv], state after the T rows)."""
    def one(s, row):
        qt, kt, vt, bt, gt = row
        s = s * (jnp.exp(gt)[:, None, None] if gt.ndim == 1
                 else jnp.exp(gt)[:, :, None])
        u = bt[:, None] * (vt - jnp.einsum("hkd,hk->hd", s, kt, precision=HI))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkd,hk->hd", s, qt, precision=HI)

    state, o = jax.lax.scan(one, state.astype(jnp.float32), (q, k, v, b, g))
    return o, state


kda_recurrent = gdn_recurrent      # g [T, H, dk]: the oracle of kda_*

SOLVE_BLOCK = 16       # rows of a diagonal block inverted row by row


def _forward_rows(m, rhs):
    """x with m x = rhs, m [..., c, c] unit lower triangular, row by row
    (c steps of a [.., i] x [.., i, R] product): forward substitution."""
    rows = []
    for i in range(m.shape[-1]):
        r = rhs[..., i, :]
        if i:
            r = r - jnp.einsum("...j,...jd->...d", m[..., i, :i],
                               jnp.stack(rows, axis=-2), precision=HI)
        rows.append(r)
    return jnp.stack(rows, axis=-2)


def _solve_unit_lower(m, rhs):
    """x with m x = rhs for m [..., C, C] unit lower triangular, rhs
    [..., C, R]. XLA's triangular solve on the chip inverts the diagonal
    blocks one system at a time (8.2 ms for 480 systems of 64, 1.5 ms for
    480 of 8: PERF.md, PR 42), so it is written out: forward substitution
    inside diagonal blocks of SOLVE_BLOCK rows (every block of every
    system at once, on the identity: their inverses), then the blocks in
    order with plain products. Stable as forward substitution is: no
    power of the strict part is formed."""
    c = m.shape[-1]
    if c <= SOLVE_BLOCK:
        return _forward_rows(m, rhs)
    b, nb = SOLVE_BLOCK, c // SOLVE_BLOCK
    assert c % b == 0, f"{c} rows are not whole blocks of {b}"
    lead = m.shape[:-2]
    mb = m.reshape(*lead, nb, b, nb, b)
    diag = jnp.stack([mb[..., i, :, i, :] for i in range(nb)], axis=-3)
    inv = _forward_rows(diag, jnp.broadcast_to(jnp.eye(b), diag.shape))
    rb = rhs.reshape(*lead, nb, b, rhs.shape[-1])
    out = []
    for i in range(nb):
        r = rb[..., i, :, :]
        for j in range(i):
            r = r - jnp.einsum("...ab,...bd->...ad", mb[..., i, :, j, :],
                               out[j], precision=HI)
        out.append(jnp.einsum("...ab,...bd->...ad", inv[..., i, :, :], r,
                              precision=HI))
    return jnp.concatenate(out, axis=-2)


def _pairs(q, k, cum):
    """A block's pair terms under a decay a key channel. q, k, cum
    [.., C, dk] (cum the running sum of the log decay, falling along the
    rows). Returns (kk, qk) [.., C, C] with `sum_c x_ic k_jc exp(cum_ic -
    cum_jc)` at j <= i and zeros above the diagonal; no exponent formed is
    positive (module docstring)."""
    c, dk = q.shape[-2:]
    sub = math.gcd(SUB_BLOCK, c)
    n = c // sub
    lead = q.shape[:-2]

    def subs(x):                         # [.., C, dk] -> [.., n, sub, dk]
        return x.reshape(*lead, n, sub, dk)

    # the boundary before each sub-block: the running sum at its last
    # earlier row (zeros before the first)
    ref = jnp.concatenate(
        [jnp.zeros((*lead, 1, dk), jnp.float32),
         subs(cum)[..., :-1, -1, :]], axis=-2)             # [.., n, dk]
    down = jnp.exp(subs(cum) - ref[..., None, :])          # rows from theirs
    before = (jnp.arange(c)[None] < (jnp.arange(n) * sub)[:, None])[..., None]
    up = jnp.where(before, k[..., None, :, :] * jnp.exp(jnp.where(
        before, ref[..., None, :] - cum[..., None, :, :], 0.0)), 0.0)
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]  # [sub, sub, 1]
    own = jnp.where(low, jnp.exp(jnp.where(
        low, subs(cum)[..., :, None, :] - subs(cum)[..., None, :, :], 0.0)),
        0.0)                                               # [.., n, i, j, dk]
    eye = jnp.eye(n, dtype=jnp.float32)

    def pairs(x):
        off = jnp.einsum("...nik,...njk->...nij", subs(x) * down, up,
                         precision=HI)                     # [.., n, sub, C]
        diag = jnp.einsum("...nik,...njk,...nijk->...nij", subs(x), subs(k),
                          own, precision=HI)
        diag = jnp.einsum("...nij,nm->...nimj", diag, eye, precision=HI)
        return off.reshape(*lead, c, c) + diag.reshape(*lead, c, c)

    return pairs(k), pairs(q)


def _wy_channel(q, k, v, b, g):
    """`_wy` for a log decay a key channel: q, k, g [nb, H, C, dk], v
    [nb, H, C, dv], b [nb, H, C], in blocks already. The same arrays, but
    gc [nb, H, dk]: the state's rows decay each at its own rate."""
    block = q.shape[-2]
    cum = jnp.cumsum(g, axis=-2)                           # [nb, H, C, dk]
    kk, aqk = _pairs(q, k, cum)
    strict = jnp.tril(jnp.ones((block, block), jnp.float32), -1)
    lmat = b[..., :, None] * kk * strict + jnp.eye(block)
    grow = jnp.exp(cum)
    rhs = jnp.concatenate([b[..., None] * v, b[..., None] * grow * k], axis=-1)
    w = _solve_unit_lower(lmat, rhs)
    dv = v.shape[-1]
    last = cum[..., -1:, :]
    return {
        "wv": w[..., :dv], "wk": w[..., dv:], "aqk": aqk,
        "qg": q * grow, "kd": k * jnp.exp(last - cum),
        "gc": jnp.exp(last[..., 0, :]),
    }


def _wy(q, k, v, b, g, block: int):
    """What a block's rows give without the state. q, k [T, H, dk],
    v [T, H, dv], b, g [T, H] (g [T, H, dk] for a decay a key channel:
    `_wy_channel`) with T a multiple of `block`. Returns, each
    with leading [nb, H]: wv [C, dv] and wk [C, dk] (the corrections are
    U = wv - wk S), aqk [C, C] (the rows' own part of the output:
    O = qg S + aqk U), qg [C, dk], kd [C, dk] and gc [1] (the state after:
    S' = gc S + kd^T U)."""
    t, h, dk = q.shape
    nb = t // block

    def blocks(x):                       # [T, H, ...] -> [nb, H, C, ...]
        return jnp.moveaxis(x.reshape(nb, block, *x.shape[1:]), 2, 1)

    q, k, v, b, g = (blocks(x.astype(jnp.float32)) for x in (q, k, v, b, g))
    if g.ndim == 4:
        return _wy_channel(q, k, v, b, g)
    cum = jnp.cumsum(g, axis=-1)                           # [nb, H, C]
    rel = cum[..., :, None] - cum[..., None, :]            # log gamma_i/gamma_j
    low = jnp.tril(jnp.ones((block, block), bool))
    gam = jnp.where(low, jnp.exp(jnp.where(low, rel, 0.0)), 0.0)
    kk = jnp.einsum("nhid,nhjd->nhij", k, k, precision=HI)
    strict = jnp.tril(jnp.ones((block, block), jnp.float32), -1)
    lmat = b[..., :, None] * kk * gam * strict + jnp.eye(block)
    rhs = jnp.concatenate(
        [b[..., None] * v, (b * jnp.exp(cum))[..., None] * k], axis=-1)
    w = _solve_unit_lower(lmat, rhs)
    dv = v.shape[-1]
    aqk = jnp.einsum("nhid,nhjd->nhij", q, k, precision=HI) * gam
    last = cum[..., -1:]
    return {
        "wv": w[..., :dv], "wk": w[..., dv:], "aqk": aqk,
        "qg": q * jnp.exp(cum)[..., None],
        "kd": k * jnp.exp(last - cum)[..., None],
        "gc": jnp.exp(last),
    }


def _chain(state, wy, keep):
    """The blocks one after another from `state` [H, dk, dv] (jnp form).
    `keep` [n] are block indices whose END state is handed back (-1:
    none, zeros). Returns (o [nb, H, C, dv], state, kept [n, H, dk, dv])."""
    nb = wy["wv"].shape[0]
    kept0 = jnp.zeros((keep.shape[0], *state.shape), jnp.float32)

    def one(carry, xs):
        s, kept = carry
        blk, i = xs
        u = blk["wv"] - jnp.einsum("hck,hkd->hcd", blk["wk"], s, precision=HI)
        o = (jnp.einsum("hck,hkd->hcd", blk["qg"], s, precision=HI)
             + jnp.einsum("hij,hjd->hid", blk["aqk"], u, precision=HI))
        # gc [H, 1] a head, or [H, dk] a key row of the state
        s = s * blk["gc"][..., None] + jnp.einsum(
            "hck,hcd->hkd", blk["kd"], u, precision=HI)
        kept = jnp.where((keep == i)[:, None, None, None], s[None], kept)
        return (s, kept), o

    (state, kept), o = jax.lax.scan(
        one, (state.astype(jnp.float32), kept0),
        (wy, jnp.arange(nb, dtype=jnp.int32)))
    return o, state, kept


def gdn_chunk(state, q, k, v, b, g, keep, block: int,
              use_pallas: bool | None = None):
    """ONE slot's rows from its carried state. state [dk, H*dv] (packed);
    q, k [T, H, dk]; v [T, H, dv]; b, g [T, H], rows that hold no token
    with b = g = 0; keep [n] block indices (see `_chain`). Returns
    (o [T, H, dv], state after, kept [n, dk, H*dv])."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("gdn_chunk", use)
    return _chunk("gdn_chunk", use, interpret, state, q, k, v, b, g, keep,
                  block)


def kda_chunk(state, q, k, v, b, g, keep, block: int,
              use_pallas: bool | None = None):
    """`gdn_chunk` with a log decay a key channel: g [T, H, dk]."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("kda_chunk", use)
    return _chunk("kda_chunk", use, interpret, state, q, k, v, b, g, keep,
                  block)


def _chunk(op: str, use: bool, interpret: bool, state, q, k, v, b, g, keep,
           block: int):
    t, h, _ = q.shape
    dv = v.shape[-1]
    wy = _wy(q, k, v, b, g, block)
    if use:
        from gridllm_tpu.ops.pallas_kernels import gdn_chunk as kernel

        o, state, kept = kernel(state, _lanes(wy, dv), keep,
                                heads=h, interpret=interpret, name=op)
        o = o.reshape(t, h, dv)                  # [nb, C, H*dv] rows
        return o, state, kept
    o, s, kept = _chain(unpack(state, h), wy, keep)
    o = jnp.moveaxis(o, 1, 2).reshape(t, h, dv)  # [nb, H, C, dv] -> rows
    return o, pack(s), pack(kept)


def _lanes(wy, dv: int):
    """`_wy`'s arrays as the kernels read them: what multiplies the state
    from the left stays a head ([.., H, C, dk] / [.., H, C, C]); what is
    added to a product with it lies packed on the lanes as the state does
    (wv [.., C, H*dv], gc [.., 1, H*dv]). A decay a key channel (gc
    [.., H, dk]) stays a head, as a row [.., H, 1, dk]: the kernels apply
    it from the left, Diag(gc) S."""
    wv = jnp.moveaxis(wy["wv"], -3, -2)                  # [.., C, H, dv]
    if wy["gc"].shape[-1] == 1:
        gc = jnp.repeat(jnp.moveaxis(wy["gc"], -2, -1), dv, axis=-1)
    else:
        gc = wy["gc"][..., None, :]
    return {**wy, "wv": wv.reshape(*wv.shape[:-2], -1), "gc": gc}


def _pad_rows(x, rows: int):
    return jnp.pad(x, [(0, 0), (0, rows - x.shape[1])]
                   + [(0, 0)] * (x.ndim - 2))


def gdn_step(states, layer, pend, n, q, k, v, b, g, live,
             use_pallas: bool | None = None):
    """A launch's rows of every slot (`_step` has the contract)."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("gdn_step", use)
    return _step("gdn_step", use, interpret, states, layer, pend, n, q, k,
                 v, b, g, live)


def kda_step(states, layer, pend, n, q, k, v, b, g, live,
             use_pallas: bool | None = None):
    """`gdn_step` with a log decay a key channel: g, and the pending g,
    [S, T, H, dk]."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("kda_step", use)
    return _step("kda_step", use, interpret, states, layer, pend, n, q, k,
                 v, b, g, live)


def _step(op: str, use: bool, interpret: bool, states, layer, pend, n, q, k,
          v, b, g, live):
    """A launch's rows of every slot. states [Ll, S, dk, H*dv] (every
    linear layer, packed), `layer` the one stepped; pend = (k, v, b, g) of
    the last launch's rows [S, Tp, ...] of which the first n [S] were
    kept; q, k [S, T, H, dk], v [S, T, H, dv], b, g [S, T, H] the new
    rows; live [S] bool: a slot that is not live keeps its state and reads
    zeros. Returns (states with layer's slots committed through
    the pending rows, o [S, T, H, dv])."""
    s, t, h, _ = q.shape
    dv = v.shape[-1]
    pk, pv, pb, pg = pend
    # what does not count is ZEROED, not multiplied by a zero beta: a slot
    # that was not live left junk pending (on the chip a kernel's output
    # for it is memory nobody wrote, NaN among it: PERF.md, PR 42), and
    # 0 x NaN in a block's system would poison the state of the next
    # request in that slot. A slot that is not live runs rows of zeros.
    took = (jnp.arange(pk.shape[1])[None] < n[:, None]) & live[:, None]
    pk, pv, pb, pg = (
        jnp.where(took.reshape(took.shape + (1,) * (z.ndim - 2)), z, 0.0)
        for z in (pk, pv, pb, pg))
    q, k, v, b, g = (
        jnp.where(live.reshape((s,) + (1,) * (z.ndim - 1)), z, 0.0)
        for z in (q, k, v, b, g))
    alive = live[:, None, None, None]
    if not use:
        st = unpack(jax.lax.dynamic_index_in_dim(states, layer, keepdims=False),
                    h)
        _, st = jax.vmap(gdn_recurrent)(st, pk, pk, pv, pb, pg)
        o, _ = jax.vmap(gdn_recurrent)(st, q, k, v, b, g)
        return jax.lax.dynamic_update_index_in_dim(
            states, pack(st), layer, 0), jnp.where(alive, o, 0.0)
    from gridllm_tpu.ops.pallas_kernels import gdn_step as kernel

    rows = STEP_ROWS * -(-max(t, pk.shape[1]) // STEP_ROWS)
    wy = [jax.vmap(lambda *a: _wy(*a, rows))(
        *(_pad_rows(x.astype(jnp.float32), rows) for x in part))
        for part in ((pk, pk, pv, pb, pg), (q, k, v, b, g))]
    # [S, 2, ...]: the pending block, then the new one (nb = 1 squeezed)
    wy = jax.tree.map(lambda a, c: jnp.stack([a[:, 0], c[:, 0]], axis=1), *wy)
    # live slots first: the kernel's grid walks them and moves no other's
    order = jnp.argsort(~live, stable=True)
    states, o = kernel(states, layer, order, live.sum(), _lanes(wy, dv),
                       heads=h, interpret=interpret, name=op)
    return states, jnp.where(alive, o[:, :t].reshape(s, t, h, dv), 0.0)


# ---------------------------------------------------------------------------
# the state-space scan (Mamba-2's SSD): no delta, keys and queries a group
# ---------------------------------------------------------------------------


def ssd_recurrent(state, q, k, v, g):
    """Token by token: S <- exp(g) S + k v^T, o = S^T q. state [H, dk, dv];
    q, k [T, dk] (one group: every head's C and B); v [T, H, dv] (dt x);
    g [T, H] the log decay. Returns (o [T, H, dv], state after the T rows).
    The oracle of `ssd_chunk` and `ssd_step`."""
    def one(s, row):
        qt, kt, vt, gt = row
        s = s * jnp.exp(gt)[:, None, None] + kt[None, :, None] * vt[:, None, :]
        return s, jnp.einsum("hkd,k->hd", s, qt, precision=HI)

    state, o = jax.lax.scan(
        one, state.astype(jnp.float32),
        tuple(x.astype(jnp.float32) for x in (q, k, v, g)))
    return o, state


def ssd_lane_tile(lanes: int) -> int:
    """Lanes of the packed state one kernel step holds: the widest of
    1024 .. 128 that divides H*dv, all of them where none does."""
    return next((t for t in (1024, 512, 256, 128) if lanes % t == 0), lanes)


def _ssd_blocks(q, k, v, g, block: int):
    """What a block's rows give without the state. q, k [T, dk]; v [T, H,
    dv]; g [T, H], T a multiple of `block`. Returns, each with leading
    [nb]: q, k [C, dk]; own [C, H*dv] (the rows' own part of the output);
    eg [C, H*dv] (O = (q S) * eg + own); vd [C, H*dv] and gc [1, H*dv]
    (the state after: S' = gc * S + k^T vd), the heads' factors repeated
    over their value lanes as the packed state lies. Every exponent formed
    is <= 0 (g <= 0, and only later-minus-earlier running sums)."""
    t, h, dv = v.shape
    nb = t // block
    q, k = (x.astype(jnp.float32).reshape(nb, block, -1) for x in (q, k))
    v = v.astype(jnp.float32).reshape(nb, block, h, dv)
    cum = jnp.cumsum(g.astype(jnp.float32).reshape(nb, block, h), axis=1)
    cum = jnp.moveaxis(cum, -1, 1)                          # [nb, H, C]
    low = jnp.tril(jnp.ones((block, block), bool))
    gam = jnp.where(low, jnp.exp(jnp.where(
        low, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    qk = jnp.einsum("nid,njd->nij", q, k, precision=HI)
    own = jnp.einsum("nhij,njhd->nihd", gam * qk[:, None], v, precision=HI)
    last = cum[..., -1:]                                    # [nb, H, 1]

    def lanes(x):                        # [nb, H, R] -> [nb, R, H*dv]
        return jnp.repeat(jnp.moveaxis(x, 1, 2), dv, axis=-1)

    return {
        "q": q, "k": k, "own": own.reshape(nb, block, h * dv),
        "eg": lanes(jnp.exp(cum)),
        "vd": v.reshape(nb, block, h * dv) * lanes(jnp.exp(last - cum)),
        "gc": lanes(jnp.exp(last)),
    }


def _ssd_chain(state, blocks, keep):
    """The blocks one after another from `state` [dk, H*dv] packed (jnp
    form). `keep` as in `_chain`. Returns (o [nb, C, H*dv], state, kept
    [n, dk, H*dv])."""
    nb = blocks["q"].shape[0]
    kept0 = jnp.zeros((keep.shape[0], *state.shape), jnp.float32)

    def one(carry, xs):
        s, kept = carry
        blk, i = xs
        o = jnp.dot(blk["q"], s, precision=HI) * blk["eg"] + blk["own"]
        s = s * blk["gc"] + jnp.einsum("ck,cl->kl", blk["k"], blk["vd"],
                                       precision=HI)
        kept = jnp.where((keep == i)[:, None, None], s[None], kept)
        return (s, kept), o

    (state, kept), o = jax.lax.scan(
        one, (state.astype(jnp.float32), kept0),
        (blocks, jnp.arange(nb, dtype=jnp.int32)))
    return o, state, kept


def ssd_chunk(state, q, k, v, g, keep, block: int,
              use_pallas: bool | None = None):
    """ONE slot's rows from its carried state. state [dk, H*dv] (packed);
    q, k [T, dk]; v [T, H, dv]; g [T, H]; rows that hold no token ZERO in
    all four; keep [n] block indices whose end state is handed back (-1:
    zeros). Returns (o [T, H, dv], state after, kept [n, dk, H*dv])."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("ssd_chunk", use)
    t, h, dv = v.shape
    blocks = _ssd_blocks(q, k, v, g, block)
    if use:
        from gridllm_tpu.ops.pallas_kernels import ssd_chunk as kernel

        o, state, kept = kernel(state, blocks, keep, interpret=interpret)
    else:
        o, state, kept = _ssd_chain(state, blocks, keep)
    return o.reshape(t, h, dv), state, kept


def ssd_step(states, layer, pend, n, q, k, v, g, live,
             use_pallas: bool | None = None):
    """A launch's rows of every slot over a lagging state (`_step`'s
    contract without the delta). states [Ll, S, dk, H*dv] (every linear
    layer, packed), `layer` the one stepped; pend = (k [S, Tp, dk], v [S,
    Tp, H, dv], g [S, Tp, H]) of the last launch's rows of which the first
    n [S] were kept; q, k [S, T, dk], v [S, T, H, dv], g [S, T, H] the new
    rows; live [S] bool. Returns (states with `layer`'s live slots
    committed through the pending rows, o [S, T, H, dv]; zeros for a slot
    that is not live)."""
    use, interpret = _pallas_mode(use_pallas)
    record_kernel_path("ssd_step", use)
    s, t, h, dv = v.shape
    pk, pv, pg = pend
    # what does not count is ZEROED before any product (see `_step`)
    took = (jnp.arange(pk.shape[1])[None] < n[:, None]) & live[:, None]
    pk, pv, pg = (
        jnp.where(took.reshape(took.shape + (1,) * (z.ndim - 2)), z, 0.0)
        for z in (pk, pv, pg))
    q, k, v, g = (
        jnp.where(live.reshape((s,) + (1,) * (z.ndim - 1)), z, 0.0)
        for z in (q, k, v, g))
    alive = live[:, None, None, None]
    if not use:
        st = unpack(jax.lax.dynamic_index_in_dim(states, layer, keepdims=False),
                    h)
        _, st = jax.vmap(ssd_recurrent)(st, pk, pk, pv, pg)
        o, _ = jax.vmap(ssd_recurrent)(st, q, k, v, g)
        return jax.lax.dynamic_update_index_in_dim(
            states, pack(st), layer, 0), jnp.where(alive, o, 0.0)
    from gridllm_tpu.ops.pallas_kernels import ssd_step as kernel

    rows = STEP_ROWS * -(-max(t, pk.shape[1]) // STEP_ROWS)
    old, new = (
        jax.tree.map(lambda a: a[:, 0], jax.vmap(
            lambda *a: _ssd_blocks(*a, rows))(
                *(_pad_rows(x.astype(jnp.float32), rows) for x in part)))
        for part in ((pk, pk, pv, pg), (q, k, v, g)))
    # live slots first: the kernel's grid walks them and moves no other's
    order = jnp.argsort(~live, stable=True)
    states, o = kernel(states, layer, order, live.sum(), old, new,
                       interpret=interpret)
    return states, jnp.where(alive, o[:, :t].reshape(s, t, h, dv), 0.0)
