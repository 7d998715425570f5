"""Time the sampler's candidate pass on the chip in its forms: one
``jax.lax.top_k(logits, 128)`` over the whole vocabulary beside the exact
stages of ``ops/sampling.py`` (block maxima, then the winning blocks; the
winning blocks' values again by smaller blocks), at the rows the step
programs give it (1: a chunk slot's row;
16: the decode rows; 16 x 5: what a verify launch's scan does in five
passes of 16) and at the vocabularies the benchmark's models have.

    python deploy/tpu_sampler_forms.py [--widths 32768,100352,...]
                                       [--rows 1,16,80]
                                       [--forms 128 256 512 128,16 128,32]
                                       [--accept] [--ops] [--out FILE]

Each form is jitted alone over float32 logits made from a seed and timed
over ``--reps`` calls after one warm-up: the host clock around
``block_until_ready`` and, from one profiler capture, the time the
device was busy a call (the union of its operations' intervals). Every
staged result is compared with ``lax.top_k``'s on the device, bit for
bit, on normal rows and on rows rounded to a quarter (hundreds of ties
across the 128th place). ``--accept`` also times ``spec_accept`` whole
(16 slots, K+1 = 5 rows, the scan a verify launch ends in) with the
candidates forced to each form, which is the sampler as
``verify_block`` compiles it; ``--ops`` prints the largest device
operations of each capture by name; ``--out`` also writes every line to
a file (the chip tool shows only the end of a long output). What it read on the v5e sets
``_TOPK_BLOCKS`` and is in PERF.md (PR 48). A form is its stages' blocks,
widest first: ``128`` is one stage of 128-wide blocks, ``128,16`` narrows
the 128 winning blocks again by blocks of 16.
``--widths 4096 --rows 1,4 --reps 2`` rehearses it on the CPU.
"""

from __future__ import annotations

import argparse
import glob
import os
import statistics
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp

from gridllm_tpu.ops import sampling

WIDTHS = "32768,100352,102400,131072,151936"


def device_ms(trace_dir: str, top: int = 0):
    """(ms the first device was busy in the capture, its `top` largest
    operations as (name, ms, count)): the union of the XLA operations'
    intervals, so an operation inside a loop is not counted twice. On the
    CPU backend there is no such plane and the answer is (None, [])."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans, total = [], {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                rec = total.setdefault(ev.name[:120], [0.0, 0])
                rec[0] += ev.duration_ns / 1e6
                rec[1] += 1
    if not spans:
        return None, []
    busy, end = 0.0, 0.0
    for a, b in sorted(spans):
        if b > end:
            busy += b - max(a, end)
            end = b
    ops = sorted(((k, v[0], v[1]) for k, v in total.items()),
                 key=lambda r: -r[1])[:top]
    return busy / 1e6, ops


def timed(fn, args, reps: int, ops: bool) -> str:
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(3):
                jax.block_until_ready(fn(*args))
        busy, top = device_ms(d, 6 if ops else 0)
    dev = "not measured" if busy is None else f"{busy / 3:.3f} ms"
    text = (f"host {1e3 * statistics.median(ts):.3f} ms a call "
            f"(min {1e3 * min(ts):.3f}), device busy {dev}")
    for op, ms, n in top:
        text += f"\n        {ms / 3:.3f} ms x{n // 3}  {op}"
    return text


def accept_args(slots: int, k1: int, v: int, seed: int):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    sp = sampling.SamplingParams.defaults(slots)
    return (jax.random.normal(keys[0], (slots, k1, v), jnp.float32) * 3.0,
            jax.random.randint(keys[1], (slots, k1), 0, v, jnp.int32),
            jnp.full((slots,), k1 - 1, jnp.int32), sp,
            jnp.zeros((slots, v), jnp.int32),
            jnp.zeros((slots, 64), jnp.int32), jnp.zeros((slots,), jnp.int32),
            jnp.ones((slots,), bool))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", default=WIDTHS)
    ap.add_argument("--rows", default="1,16,80")
    ap.add_argument("--forms", nargs="+",
                    default=["128", "256", "512", "128,16", "128,32"])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--accept", action="store_true")
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sink = open(args.out, "w") if args.out else None

    def say(line: str) -> None:
        print(line, flush=True)
        if sink is not None:
            print(line, file=sink, flush=True)

    dev = jax.devices()[0]
    k = sampling.TOPK
    forms = [tuple(int(b) for b in f.split(",")) for f in args.forms]
    say(f"device: {dev.platform} {dev.device_kind}; top-{k}; the program "
        f"takes stages of blocks {sampling._TOPK_BLOCKS} over "
        f"{k * sampling._TOPK_BLOCKS[0]} ids")
    one_pass = jax.jit(partial(jax.lax.top_k, k=k))
    staged = {f: jax.jit(partial(sampling._topk_staged, k=k, blocks=f))
              for f in forms}
    for v in (int(w) for w in args.widths.split(",")):
        for rows in (int(r) for r in args.rows.split(",")):
            x = jax.random.normal(jax.random.PRNGKey(v + rows), (rows, v),
                                  jnp.float32) * 3.0
            tied = jnp.round(x * 4.0) / 4.0
            say(f"V={v} rows={rows} one-pass: "
                f"{timed(one_pass, (x,), args.reps, args.ops)}")
            for f, fn in staged.items():
                if v <= k * f[0]:
                    say(f"V={v} rows={rows} stages {f}: skipped (the "
                        f"winning blocks are the whole row)")
                    continue
                same = all(
                    bool(jnp.array_equal(got, want))
                    for rows_ in (x, tied)
                    for got, want in zip(fn(rows_), one_pass(rows_)))
                say(f"V={v} rows={rows} stages {f}: "
                    f"{timed(fn, (x,), args.reps, args.ops)}; bit-equal to "
                    f"lax.top_k on normal and tied rows: {same}")
        if not args.accept:
            continue
        # the sampler as verify_block compiles it: the candidates forced to
        # each form in turn by replacing the module's chooser while the
        # program is traced
        a = accept_args(16, 5, v, v)
        chooser = sampling._topk_candidates
        choosers = {"one-pass": jax.lax.top_k}
        choosers.update({
            f"stages {f}": partial(sampling._topk_staged, blocks=f)
            for f in forms if v > k * f[0]})
        outs = {}
        for name, form in choosers.items():
            sampling._topk_candidates = form
            try:
                fn = jax.jit(partial(sampling.spec_accept, vocab=v))
                outs[name] = jax.device_get(fn(*a)[0])
                same = bool((outs[name] == outs["one-pass"]).all())
                say(f"V={v} spec_accept 16x5 {name}: "
                    f"{timed(fn, a, args.reps, args.ops)}; tokens as "
                    f"one-pass: {same}")
            finally:
                sampling._topk_candidates = chooser


if __name__ == "__main__":
    main()
