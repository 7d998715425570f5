"""Time the gated delta rule's two forms on the chip at one hybrid model's
shapes: the CHUNKED form over a prompt's rows (`gdn_chunk`: the blocks'
triangular systems in XLA, the chain through the state in the Pallas
kernel) at the dense chunk widths, and the STEP form over a launch's rows
of every slot (`gdn_step`), each as the Pallas kernel and as its jnp form,
every linear layer of the model inside ONE `lax.scan` that carries the
states, as the model's layer scan does. What the step form must move is
each slot's state once in and once out; a scan that copied the carried
states at its custom call would read several times that.

    python deploy/tpu_gdn_forms.py [--model olmo-hybrid:7b] [--layers 15]
                                   [--rows 256,512,1024] [--slots 16] [--ops]

    python deploy/tpu_gdn_forms.py --commit [--periods 1] [--dtype bfloat16]

    python deploy/tpu_gdn_forms.py --model kimi-linear:48b --layers 6 \
                                   --rows 512 --oracle 4096

A model whose decay is a value a key channel (``linear_channel_decay``:
Kimi Delta Attention) runs the same forms under their own names
(`kda_chunk`, `kda_step`), with log decays drawn as the family's seeded
leaves give them (down to -48 a row). ``--oracle N`` first holds both
forms of the chunk over N rows, and of the step, to the token-by-token
recurrence on the device.

``--commit`` is speculation's commit through the MODEL at full width (the
benchmark's cell never accepts a draft: random weights on random bytes do
not repeat, so its verify launches keep one row and `correct` never sees
the deferred commit of several): a prompt through chunk launches, then a
verify launch of K + 1 forced rows of which 1, 3 and all 5 are kept
(`rollback_to_length`, `commit_verify`), then a decode step, beside that
many sequential decode steps and the same step; it prints how far the
logits, the state and the convolution's rows are apart, and the same with
one row too many committed (what a wrong count reads).

Random rows; each form jitted alone and timed over ``--reps`` calls after
one warm-up (host clock around ``block_until_ready``). ``--ops`` also
captures one profiler trace a form and prints its largest device
operations by name: how `benchmark/gdn.py`'s patterns were found. What it
read on the v5e is in PERF.md (PR 42).
"""

from __future__ import annotations

import argparse
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu.models.configs import get_config
from gridllm_tpu.ops import linear_attn as la


def timed(fn, args, reps: int) -> tuple[float, float]:
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        if isinstance(out, tuple) and out[0].shape == args[0].shape:
            args = (out[0], *args[1:])          # a donated state comes back
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts), 1e3 * min(ts)


def rows(rng, lead, h, dk, dv, channel=False):
    def n(*shape):
        return jnp.asarray(rng.normal(size=(*lead, *shape)), jnp.float32)

    q, k = la.l2norm(n(h, dk)) * dk ** -0.5, la.l2norm(n(h, dk))
    if not channel:
        return q, k, n(h, dv), 2 * jax.nn.sigmoid(n(h)), -0.1 * jnp.exp(n(h))
    # the family's seeded leaves: A in (0.001, 16) a head, a step through
    # softplus of a pre-activation that spreads about +-3 a channel
    a = jnp.asarray(rng.uniform(1e-3, 16.0, size=(h, 1)), jnp.float32)
    return q, k, n(h, dv), jax.nn.sigmoid(n(h)), -a * jax.nn.softplus(
        n(h, dk) - 3.0)


def oracle_check(args, cfg, chunk_op, step_op) -> None:
    """Both forms of each op against the token-by-token recurrence."""
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    rng = np.random.default_rng(1)
    r = rows(rng, (args.oracle,), h, dk, dv, cfg.linear_channel_decay)
    print(f"oracle: {args.oracle} rows, log decay down to "
          f"{float(r[4].min()):.1f} a row, below -5 in "
          f"{100 * float((r[4] < -5).mean()):.1f} % of its values")
    s0 = jnp.asarray(rng.normal(size=(h, dk, dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(la.gdn_recurrent)(s0, *r)
    keep = jnp.asarray([1, -1], jnp.int32)
    for kernel in (True, False):
        o, s1, _ = jax.jit(lambda s, *r, kernel=kernel: chunk_op(
            s, *r, keep, args.block, use_pallas=kernel))(la.pack(s0), *r)
        print(f"  chunk {'kernel' if kernel else 'jnp   '}: o apart "
              f"{float(jnp.abs(o - want_o).max()):.2e} (largest "
              f"{float(jnp.abs(want_o).max()):.2f}), state apart "
              f"{float(jnp.abs(la.unpack(s1, h) - want_s).max()):.2e} "
              f"(largest {float(jnp.abs(want_s).max()):.2f}), finite "
              f"{bool(jnp.isfinite(o).all())}")
    s, t = args.slots, args.step_rows
    new = rows(rng, (s, t), h, dk, dv, cfg.linear_channel_decay)
    pend = rows(rng, (s, t), h, dk, dv, cfg.linear_channel_decay)
    states = jnp.asarray(rng.normal(size=(2, s, dk, h * dv)), jnp.float32)
    n = jnp.asarray(rng.integers(0, t + 1, size=(s,)), jnp.int32)
    live = jnp.arange(s) % 4 != 3
    got = {}
    for kernel in (True, False):
        got[kernel] = jax.jit(lambda st, kernel=kernel: step_op(
            st, 1, pend[1:], n, *new, live, use_pallas=kernel))(states)
    print(f"  step kernel against jnp (the recurrence itself): states apart "
          f"{float(jnp.abs(got[True][0] - got[False][0]).max()):.2e}, o apart "
          f"{float(jnp.abs(got[True][1] - got[False][1]).max()):.2e} (largest "
          f"{float(jnp.abs(got[False][1]).max()):.2f})")


def commit_check(args) -> None:
    import dataclasses

    from gridllm_tpu.models import olmo_hybrid as oh
    from gridllm_tpu.ops.kvcache import (
        PageAllocator, PagedKVCache, rollback_to_length)

    cfg = get_config(args.model)
    cfg = dataclasses.replace(cfg, num_layers=args.periods * cfg.layer_period)
    dtype = jnp.dtype(args.dtype)
    params = oh.init_params(cfg, jax.random.PRNGKey(0), dtype)
    ps, k1, width, n0 = args.page, args.step_rows, args.chunk, args.prompt
    per_slot = -(-(n0 + 4 * k1) // ps)
    slots = args.slots                  # the cell's 16; slot 0 alone is live
    cache = PagedKVCache.create(
        cfg.cache_layers, 2 * per_slot, ps, cfg.cache_heads, cfg.head_dim_,
        slots, per_slot, dtype=dtype)
    cache = dataclasses.replace(
        cache, rec=oh.new_state(cfg, slots, k1, 2, dtype))
    alloc = PageAllocator(2 * per_slot, ps, per_slot)
    alloc.alloc(0, n0 + 4 * k1)
    row = jnp.asarray(alloc.table_row(0), jnp.int32)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, n0 + 2 * k1)
    active = jnp.arange(slots) == 0
    # admitted as the engine admits: the mixed step with no active slot
    idle = jnp.zeros((slots,), jnp.int32)
    chunk = jax.jit(lambda p, c, t, s0, n: oh.mixed_step(
        p, cfg, t, s0, n, jnp.int32(0), row, idle, c, idle > 0)[::2])
    verify = jax.jit(lambda p, c, t: oh.verify_step(p, cfg, t, c, active))
    decode = jax.jit(lambda p, c, t: oh.decode_step(p, cfg, t, c, active))
    for s0 in range(0, n0, width):
        part = toks[s0:min(s0 + width, n0)]
        padded = np.zeros((width,), np.int32)
        padded[:len(part)] = part
        _, cache = chunk(params, cache, padded, jnp.int32(s0),
                         jnp.int32(len(part)))
    drafts = np.zeros((slots, k1), np.int32)
    drafts[0] = toks[n0:n0 + k1]
    _, after = verify(params, cache, drafts)

    def one(tok):
        return np.asarray([tok] + [0] * (slots - 1), np.int32)

    def count(n):
        return jnp.asarray([n] + [0] * (slots - 1))

    def apart(a, b):       # relative to the largest value held, slot 0
        return max(float(jnp.abs(x[:, 0].astype(jnp.float32)
                                 - y[:, 0].astype(jnp.float32)).max()
                         / jnp.abs(y[:, 0].astype(jnp.float32)).max())
                   for x, y in ((a.rec.state, b.rec.state),
                                (a.rec.conv, b.rec.conv)))

    print(f"{cfg.name}: {cfg.num_layers} layers, {dtype.name}, "
          f"device {jax.devices()[0].device_kind}; prompt {n0} in "
          f"{width}-wide launches, a verify launch of {k1} forced rows")
    for kept in sorted({1, (k1 + 1) // 2, k1}):
        n_emit = count(kept)
        seq = cache
        for p in range(n0, n0 + kept):
            _, seq = decode(params, seq, one(toks[p]))
        want, want_c = decode(params, seq, one(toks[n0 + kept]))
        line = []
        for extra in (0, 1):
            if kept + extra > k1:
                continue
            got_c = oh.commit_verify(
                rollback_to_length(after, after.lengths + n_emit),
                n_emit + count(extra), active)
            got, got_c = decode(params, got_c, one(toks[n0 + kept]))
            line.append(
                f"{'one row too many: ' if extra else ''}logits apart by "
                f"{float(jnp.abs(got[0] - want[0]).max()):.4f} (largest "
                f"{float(jnp.abs(want[0]).max()):.2f}), state and "
                f"convolution rows by {apart(got_c, want_c):.2e} of their largest")
        print(f"  {kept} of {k1} rows kept against {kept} decode steps: "
              + "; ".join(line))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="olmo-hybrid:7b")
    ap.add_argument("--layers", type=int, default=15)
    ap.add_argument("--rows", default="256,512,1024")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--step-rows", type=int, default=5)
    ap.add_argument("--block", type=int, default=64)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--commit", action="store_true")
    ap.add_argument("--periods", type=int, default=1)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--prompt", type=int, default=300)
    ap.add_argument("--oracle", type=int, default=0)
    args = ap.parse_args()
    if args.commit:
        return commit_check(args)
    cfg = get_config(args.model)
    h, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    channel = cfg.linear_channel_decay
    chunk_op, step_op = ((la.kda_chunk, la.kda_step) if channel
                         else (la.gdn_chunk, la.gdn_step))
    if args.oracle:
        oracle_check(args, cfg, chunk_op, step_op)
    rng = np.random.default_rng(0)
    n_l, s, t = args.layers, args.slots, args.step_rows
    print(f"{cfg.name}: {h} heads, keys {dk}, values {dv}, {n_l} layers, "
          f"device {jax.devices()[0].device_kind}; a slot's states "
          f"{n_l * h * dk * dv * 4 / 1e6:.1f} MB")
    layers = jnp.arange(n_l, dtype=jnp.int32)
    keep = jnp.asarray([1, -1], jnp.int32)
    forms = []
    for width in (int(w) for w in args.rows.split(",")):
        r = rows(rng, (width,), h, dk, dv, channel)
        for kernel in (True, False):
            def chunk(states, *r, kernel=kernel):
                def one(states, li):
                    # rows that differ by layer: nothing hoists out
                    q, k, v, b, g = r
                    o, s1, kept = chunk_op(
                        states[li, 0], q, k, v * (1.0 + 0.01 * li), b, g,
                        keep, args.block, use_pallas=kernel)
                    return states.at[li, 0].set(s1), (o.sum(), kept.sum())
                return jax.lax.scan(one, states, layers)
            forms.append((f"chunk {width:5d} rows {'kernel' if kernel else 'jnp   '}",
                          jax.jit(chunk, donate_argnums=(0,)), r,
                          0.0, n_l * width * h * 7.0 * dk * dv))
    new = rows(rng, (s, t), h, dk, dv, channel)
    pend = rows(rng, (s, t), h, dk, dv, channel)[1:]
    for live in sorted({s, max(s // 2, 1)}, reverse=True):
        n = jnp.where(jnp.arange(s) < live, 3, 0)
        alive = jnp.arange(s) < live
        for kernel in (True, False):
            def step(states, *new, kernel=kernel, n=n, alive=alive):
                def one(states, li):
                    q, k, v, b, g = new
                    scale = 1.0 + 0.01 * li
                    states, o = step_op(
                        states, li, (pend[0], pend[1] * scale, *pend[2:]), n,
                        q, k, v * scale, b, g, alive, use_pallas=kernel)
                    return states, o.sum()
                return jax.lax.scan(one, states, layers)
            forms.append((f"step {live:2d} of {s} live   {'kernel' if kernel else 'jnp   '}",
                          jax.jit(step, donate_argnums=(0,)), new,
                          n_l * live * 2.0 * h * dk * dv * 4, 0.0))
    last = None
    for label, fn, r, need_bytes, need_flops in forms:
        states = jnp.zeros((n_l, s, dk, h * dv), jnp.float32)
        try:
            got = jax.block_until_ready(fn(
                jnp.full((n_l, s, dk, h * dv), 0.01, jnp.float32), *r))
            if label.endswith("jnp   ") and last is not None:
                # the kernel's states against the jnp form's, same inputs
                print(f"    kernel against jnp: states apart by at most "
                      f"{float(jnp.abs(got[0] - last).max()):.2e} (largest "
                      f"{float(jnp.abs(got[0]).max()):.2f})")
            last = got[0]
            med, best = timed(fn, (states, *r), args.reps)
        except Exception as e:  # noqa: BLE001 - a form the chip refuses is a reading
            print(f"{label}: FAILED {type(e).__name__}: {str(e)[:300]}")
            continue
        least = max(need_bytes / 819e9, need_flops / 197e12) * 1e3
        print(f"{label}: median {med:8.3f} ms, best {best:8.3f} ms; the "
              f"equations' least {least:6.3f} ms ({100 * least / best:5.1f} %)")
        if args.ops:
            from tpu_moe_forms import top_ops

            states = jnp.zeros((n_l, s, dk, h * dv), jnp.float32)
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                jax.block_until_ready(fn(states, *r))
                jax.profiler.stop_trace()
                for name, ms, count in top_ops(d):
                    print(f"    {ms:8.3f} ms x{count:3d}  {name[:150]}")


if __name__ == "__main__":
    main()
