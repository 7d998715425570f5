"""Time the expert layer's forms on the chip at one routed model's shapes:
the all-experts einsum beside the sorted ``ragged_dot`` dispatch, at the
row counts the step programs have (a verify launch's slots x (K+1) rows,
a chunk's rows).

    python deploy/tpu_moe_forms.py [--model smallthinker:21b] [--rows 80,1040]
                                   [--ops]

One layer's weights, random; each form jitted alone and timed over
``--reps`` calls after one warm-up (host clock around
``block_until_ready``). Prints a line a (rows, form): the milliseconds a
call, the experts the rows touch, those experts' bytes and the bytes/s
that makes. ``--ops`` also captures one profiler
trace a form and prints its largest device operations by name, which is
how a reader's pattern for the grouped products is found. What it read
on the v5e is in models/mixtral.py's docstring and PERF.md (PR 33; PR 36
for ``--model deepseek-v2-lite:16b``: 64 experts of 2048 x 1408, whose
router is not renormalised; the shared experts are outside both forms
and are not timed here).
"""

from __future__ import annotations

import argparse
import glob
import os
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from gridllm_tpu.models import mixtral
from gridllm_tpu.models.configs import get_config


def top_ops(trace_dir: str, n: int = 8) -> list[tuple[str, float, int]]:
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    total: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                rec = total.setdefault(ev.name[:200], [0.0, 0])
                rec[0] += ev.duration_ns / 1e6
                rec[1] += 1
    return sorted(((k, v[0], v[1]) for k, v in total.items()),
                  key=lambda r: -r[1])[:n]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="smallthinker:21b")
    ap.add_argument("--rows", default="80,1040")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()
    cfg = get_config(args.model)
    e, f, x = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; {args.model}: "
          f"{x} experts of {e}x{f}, top-{cfg.experts_per_token}, "
          f"{cfg.expert_act}", flush=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def w(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(jnp.bfloat16)

    lp = {"router": (jax.random.normal(keys[0], (e, x), jnp.float32) * 0.02
                     ).astype(jnp.bfloat16),      # init_params' scale
          "we_gate": w(keys[1], x, e, f), "we_up": w(keys[2], x, e, f),
          "we_down": w(keys[3], x, f, e)}
    expert_bytes = 3 * e * f * 2

    def dense(lp, h, r):
        return mixtral._moe_mlp_dense(cfg, lp, h, *mixtral._route(cfg, lp, r))

    def ragged(lp, h, r):
        return mixtral._moe_mlp_ragged(cfg, lp, h, *mixtral._route(cfg, lp, r))

    for rows in (int(n) for n in args.rows.split(",")):
        h = jax.random.normal(jax.random.PRNGKey(rows), (rows, e)
                              ).astype(jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows, e)
                              ).astype(jnp.bfloat16)
        _, top_i = mixtral._route(cfg, lp, r)
        touched = int(mixtral._route_stats(cfg, top_i, None)[1])
        for name, fn in {"dense": dense, "ragged": ragged}.items():
            if name == "dense" and rows * x * f * 2 * 3 > 2e9:
                print(f"rows={rows} dense: skipped (its [rows, X, F] "
                      "intermediates pass 2 GB)", flush=True)
                continue
            jf = jax.jit(fn)
            jax.block_until_ready(jf(lp, h, r))
            ts = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(jf(lp, h, r))
                ts.append(time.perf_counter() - t0)
            ms = 1e3 * statistics.median(ts)
            print(f"rows={rows} {name}: {ms:.3f} ms a call "
                  f"(min {1e3 * min(ts):.3f}); the rows touch {touched} "
                  f"of {x} experts = {touched * expert_bytes / 1e6:.0f} MB "
                  f"-> {touched * expert_bytes / ms / 1e6:.0f} GB/s",
                  flush=True)
            if args.ops:
                with tempfile.TemporaryDirectory() as d:
                    with jax.profiler.trace(d):
                        for _ in range(3):
                            jax.block_until_ready(jf(lp, h, r))
                    for op, op_ms, n in top_ops(d):
                        print(f"    {op_ms / 3:.3f} ms x{n // 3}  {op}",
                              flush=True)


if __name__ == "__main__":
    main()
