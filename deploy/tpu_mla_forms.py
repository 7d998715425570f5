"""Time latent attention's two forms on the chip at one MLA model's shapes
for a prefill chunk behind a paged prefix: ABSORBED (the ragged kernel
straight on latent pages, the `kv_b` up-projection folded into query and
output) beside EXPANDED (the prefix's latents gathered and up-projected to
K and V per head, plain attention: ``deepseek._expanded``, built here for
the timing alone, since the model's chunk region is absorbed), and the
decode / verify launch in the absorbed form.

    python deploy/tpu_mla_forms.py [--model deepseek-v2-lite:16b]
                                   [--prefix 0,2048,4096] [--chunk 512] [--ops]

One layer's attention weights, random, a latent pool of 640 pages; each
form jitted alone and timed over ``--reps`` calls after one warm-up (host
clock around ``block_until_ready``). ``--ops`` also captures one profiler
trace a form and prints its largest device operations by name: how the
``mla.*`` readers' patterns were found. What it read on the v5e is in
PERF.md (PR 36) and models/deepseek.py's docstring.
"""

from __future__ import annotations

import argparse
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu.models import deepseek
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.ops.kvcache import PagedKVCache, lane_pad_dim


def timed(fn, args, reps: int) -> tuple[float, float]:
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts), 1e3 * min(ts)


def main() -> None:
    from tpu_moe_forms import top_ops

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="deepseek-v2-lite:16b")
    ap.add_argument("--prefix", default="0,2048,4096")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--page", type=int, default=128)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()
    cfg = get_config(args.model)
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    width = cfg.cache_dim if interpret else lane_pad_dim(cfg.cache_dim)
    print(f"device: {dev.platform} {dev.device_kind}; {args.model}: "
          f"{cfg.num_heads} heads, row {cfg.cache_dim} stored at {width}, "
          f"chunk {args.chunk}", flush=True)
    ps, maxp = args.page, 8192 // args.page
    n_pages = 640 if not interpret else 64
    # the one leaf both forms read: the latent's up-projection (any latent
    # family's shapes, whatever else its layers hold)
    r, hv = cfg.kv_lora_rank, cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    one = {"w_kvb": (jax.random.normal(jax.random.PRNGKey(0), (r, hv))
                     * r ** -0.5).astype(jnp.bfloat16)}
    cache = PagedKVCache.create(1, n_pages, ps, 1, width, args.slots, maxp,
                                latent=True)
    cache = PagedKVCache(
        k=(jax.random.normal(jax.random.PRNGKey(1), cache.k.shape,
                             jnp.float32) * 0.5).astype(jnp.bfloat16),
        v=None, page_table=cache.page_table, lengths=cache.lengths,
        page_size=ps)
    c, h = args.chunk, cfg.num_heads
    row = np.full((maxp,), -1, np.int32)
    row[:min(maxp, n_pages // 2)] = np.arange(min(maxp, n_pages // 2))
    rng = jax.random.split(jax.random.PRNGKey(2), 4)
    q_nope = jax.random.normal(rng[0], (1, c, h, cfg.qk_nope_head_dim)
                               ).astype(jnp.bfloat16)
    q_pe = jax.random.normal(rng[1], (1, c, h, cfg.qk_rope_head_dim)
                             ).astype(jnp.bfloat16)
    rows = (jax.random.normal(rng[2], (1, c, cfg.cache_dim)) * 0.5
            ).astype(jnp.bfloat16)

    def absorbed(lp, cache, q_nope, q_pe, rows, start):
        attend = deepseek._chunk_attend(
            cfg, cache, jnp.asarray(row), start, start + c, c)
        return attend(lp, jnp.int32(0), q_nope, q_pe, rows)

    def expanded(lp, cache, q_nope, q_pe, rows, start):
        # the slot's whole page row gathered and up-projected
        prefix = cache.k[0][jnp.maximum(jnp.asarray(row), 0)].reshape(
            -1, cache.k.shape[-1])[:, :cfg.cache_dim]
        n = prefix.shape[0]
        at = start + jnp.arange(c, dtype=jnp.int32)
        return deepseek._expanded(
            cfg, lp, q_nope, q_pe, at[None],
            jnp.concatenate([prefix, rows[0]])[None],
            jnp.concatenate([jnp.arange(n, dtype=jnp.int32), at])[None],
            jnp.concatenate([jnp.arange(n) < start, at < start + c])[None])

    for form, fn in (("absorbed", jax.jit(absorbed)),
                     ("expanded", jax.jit(expanded))):
        for prefix in (int(p) for p in args.prefix.split(",")):
            a = (one, cache, q_nope, q_pe, rows, jnp.int32(prefix))
            ms, lo = timed(fn, a, args.reps)
            print(f"chunk {form} prefix={prefix}: {ms:.3f} ms a layer "
                  f"(min {lo:.3f})", flush=True)
            if args.ops and prefix == int(args.prefix.split(",")[-1]):
                with tempfile.TemporaryDirectory() as d:
                    with jax.profiler.trace(d):
                        for _ in range(3):
                            jax.block_until_ready(fn(*a))
                    for op, op_ms, n in top_ops(d):
                        print(f"    {op_ms / 3:.3f} ms x{n // 3}  {op}",
                              flush=True)

    # a decode / verify launch: `slots` slots at `ctx` tokens each
    pages_each = n_pages // args.slots
    table = np.full((args.slots, maxp), -1, np.int32)
    for s in range(args.slots):
        table[s, :pages_each] = np.arange(s * pages_each, (s + 1) * pages_each)
    for td in (1, 5):
        for ctx in (2048, 4096):
            ctx = min(ctx, pages_each * ps - td)
            gc = PagedKVCache(k=cache.k, v=None, page_table=jnp.asarray(table),
                              lengths=jnp.full((args.slots,), ctx, jnp.int32),
                              page_size=ps)
            qn = jax.random.normal(rng[3], (args.slots, td, h, cfg.qk_nope_head_dim)
                                   ).astype(jnp.bfloat16)
            qp = jax.random.normal(rng[3], (args.slots, td, h, cfg.qk_rope_head_dim)
                                   ).astype(jnp.bfloat16)
            rw = (jax.random.normal(rng[3], (args.slots, td, cfg.cache_dim)) * 0.5
                  ).astype(jnp.bfloat16)
            fn = jax.jit(lambda lp, gc, qn, qp, rw: deepseek._group_attend(
                cfg, gc, gc.lengths)(lp, jnp.int32(0), qn, qp, rw))
            ms, lo = timed(fn, (one, gc, qn, qp, rw), args.reps)
            read = args.slots * ctx * cfg.cache_dim * 2
            print(f"group Td={td} ctx={ctx} x{args.slots} slots: {ms:.3f} ms "
                  f"a layer (min {lo:.3f}); rows read {read / 1e6:.1f} MB -> "
                  f"{read / ms / 1e6:.0f} GB/s", flush=True)


if __name__ == "__main__":
    main()
