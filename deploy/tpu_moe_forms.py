"""Time the expert layer's forms on the chip at one routed model's shapes:
the all-experts einsum, the sorted ``ragged_dot`` dispatch, the grouped
kernel (``grouped_experts``: the touched experts alone, every row against
each) and its sorted regime (``grouped_sorted``: each expert against the
rows that picked it), at the row counts the step programs have (a verify
launch's slots x (K+1) rows, a chunk's rows).

    python deploy/tpu_moe_forms.py [--model smallthinker:21b] [--rows 80,1040]
                                   [--touch 0.1,0.33,1] [--forms ...] [--ops]
                                   [--tile 512] [--tile-rows 64]

One layer's weights, random; each form jitted alone and timed over
``--reps`` calls after one warm-up (host clock around
``block_until_ready``) and, from one profiler trace of three calls, by the
device's own clock (the sum of the call's device operations: what a form
costs inside a step program). Prints a line a (rows, touched, form): the
milliseconds a call on both clocks, the experts the rows touch, those
experts' bytes and the bytes/s that makes of the device time.
``--touch`` is the share of the experts the rows' picks fall among (the
router's weights stay, its picks are drawn among the first experts: a
verify launch's candidate rows route alike, PERF.md PR 33); without it
the rows route as the random router says. ``--tile`` forces the grouped
kernel's F-tile, ``--tile-rows`` the sorted regime's row tile (by default
the shape's: ``ops/experts.py`` ``sorted_tile_rows``). ``--ops`` also
prints a form's largest device operations by name, which is how a reader's
pattern for the grouped products is found. What it read on the v5e is in
models/mixtral.py's docstring and PERF.md (PR 33; PR 36 for ``--model
deepseek-v2-lite:16b``: 64 experts of 2048 x 1408, whose router is not
renormalised; the shared experts are outside every form and are not timed
here; PR 58 for ``--rows 528,1040 --forms dense,ragged,grouped_sorted`` at
six models, the readings ``expert_form``'s rule past the ridge rests on).
"""

from __future__ import annotations

import argparse
import functools
import glob
import os
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp

from gridllm_tpu.models import mixtral
from gridllm_tpu.models.configs import get_config


def top_ops(trace_dir: str, n: int | None = 8) -> list[tuple[str, float, int]]:
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


def picks_among(cfg, top_w, share: float, key):
    """Picks drawn among the first `share` of the held experts (at least
    top-k of them), distinct a row; the router's weights as they came."""
    first, held = cfg.held_experts
    n = max(cfg.experts_per_token, round(share * held))
    rows = jax.random.split(key, top_w.shape[0])
    top_i = jax.vmap(lambda k: jax.random.permutation(k, n))(rows)
    return first + top_i[:, :cfg.experts_per_token].astype(jnp.int32)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="smallthinker:21b")
    ap.add_argument("--rows", default="80,1040")
    ap.add_argument("--touch", default="",
                    help="shares of the experts the picks fall among")
    ap.add_argument("--forms", default="dense,ragged,grouped,grouped_sorted")
    ap.add_argument("--tile", type=int, default=0)
    ap.add_argument("--tile-rows", type=int, default=0,
                    help="the sorted regime's row tile (default: by shape)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()
    cfg = get_config(args.model)
    e, f, x = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; {args.model}: "
          f"{x} experts of {e}x{f}, top-{cfg.experts_per_token}, "
          f"{cfg.expert_act}", flush=True)
    if args.tile:
        from gridllm_tpu.ops import pallas_kernels

        pallas_kernels.grouped_experts = functools.partial(
            pallas_kernels.grouped_experts, tile_f=args.tile)
        pallas_kernels.grouped_experts_sorted = functools.partial(
            pallas_kernels.grouped_experts_sorted, tile_f=args.tile)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    def w(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(jnp.bfloat16)

    lp = {"router": (jax.random.normal(
              keys[0], (e, cfg.router_width), jnp.float32) * 0.02
                     ).astype(jnp.bfloat16),      # init_params' scale
          "we_gate": w(keys[1], x, e, f), "we_up": w(keys[2], x, e, f),
          "we_down": w(keys[3], x, f, e)}
    if cfg.router_bias:
        lp["router_bias"] = jnp.zeros((cfg.router_width,), jnp.float32)
    expert_bytes = 3 * e * f * 2
    forms = {
        "dense": lambda lp, h, tw, ti: mixtral._moe_mlp_dense(
            cfg, lp, h, tw, ti),
        "ragged": lambda lp, h, tw, ti: mixtral._moe_mlp_ragged(
            cfg, lp, h, tw, ti),
        "grouped": lambda lp, h, tw, ti: mixtral._moe_mlp_grouped(
            cfg, lp, h, tw, ti, None),
        "grouped_sorted": lambda lp, h, tw, ti: mixtral._moe_mlp_grouped_sorted(
            cfg, lp, h, tw, ti, None, args.tile_rows or None),
    }
    shares = [float(p) for p in args.touch.split(",") if p] or [None]

    for rows in (int(n) for n in args.rows.split(",")):
        h = jax.random.normal(jax.random.PRNGKey(rows), (rows, e)
                              ).astype(jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows, e)
                              ).astype(jnp.bfloat16)
        for share in shares:
            top_w, top_i = mixtral._route(cfg, lp, r)
            if share is not None:
                top_i = picks_among(cfg, top_w, share,
                                    jax.random.PRNGKey(rows + 2))
            touched = int(mixtral._touched(cfg, top_i, None).sum())
            outs = {}
            for name in args.forms.split(","):
                if name == "dense" and rows * x * f * 2 * 3 > 2e9:
                    print(f"rows={rows} dense: skipped (its [rows, X, F] "
                          "intermediates pass 2 GB)", flush=True)
                    continue
                jf = jax.jit(forms[name])
                outs[name] = jax.block_until_ready(jf(lp, h, top_w, top_i))
                ts = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(jf(lp, h, top_w, top_i))
                    ts.append(time.perf_counter() - t0)
                ms = 1e3 * statistics.median(ts)
                with tempfile.TemporaryDirectory() as d:
                    with jax.profiler.trace(d):
                        for _ in range(3):
                            jax.block_until_ready(jf(lp, h, top_w, top_i))
                    ops = top_ops(d, None)
                dev_ms = sum(op_ms for _, op_ms, _ in ops) / 3
                rate = (f"{touched * expert_bytes / dev_ms / 1e6:.0f} GB/s"
                        if dev_ms else "no device trace: not measured")
                print(f"rows={rows} touch={touched}/{x} {name}: "
                      f"{ms:.3f} ms a call (min {1e3 * min(ts):.3f}), "
                      f"{dev_ms:.3f} ms on the device; the touched experts "
                      f"= {touched * expert_bytes / 1e6:.0f} MB -> {rate}",
                      flush=True)
                if args.ops:
                    for op, op_ms, n in ops[:8]:
                        print(f"    {op_ms / 3:.3f} ms x{n // 3}  {op}",
                              flush=True)
            for name in ("grouped", "grouped_sorted"):
                if "dense" in outs and name in outs:
                    d, g = (outs[k].astype(jnp.float32)
                            for k in ("dense", name))
                    print(f"    {name} against dense: max |diff| "
                          f"{float(jnp.abs(d - g).max()):.4f} of max "
                          f"{float(jnp.abs(d).max()):.3f}", flush=True)


if __name__ == "__main__":
    main()
