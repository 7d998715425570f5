"""Run each Pallas kernel standalone at the serving shapes and hold it to
its registry oracle (gridllm_tpu/ops/kernels.py) — what to reach for when
``chip_smoke.py`` fails inside a kernel.

Interpret-mode tests complete DMA copies synchronously and never run the
Mosaic compiler, so a kernel can pass every CPU test and still fail to
compile, hang on a semaphore, or compute garbage on the chip. This script
is the per-kernel check on real hardware: one case per process
(deploy/tpu_kernel_bisect.sh loops over them), each case compared with
the jnp reference inside the registry's tolerance, nonzero exit on the
first mismatch.

Usage:
    python deploy/tpu_kernel_bisect.py [--list] [--model NAME] [case ... | all]

Shapes are llama3.2:3b's under the worker's defaults: 24 query / 8 KV
heads of 128, 128-token pages, a 128-page table row per slot, 8 slots,
1024-token prefill chunks, speculation depth 4 (a 5-token verify block).

The ``g7_*`` cases are SmallThinker-21BA3B's attention: 28 query / 4 KV
heads of 128 (a query group of 7), a traced per-layer window of 4096, 16
slots, contexts of 3 k and 7 k tokens, in the ragged kernel's decode,
verify, chunk and mixed forms and in flash prefill.

The ``mla_*`` cases are DeepSeek-V2-Lite's latent attention (PR 36): ONE
cache head whose row is key and value at once, no V pool, 16 query heads
on it, 16 slots at contexts of 2 k and 4 k, in the ragged kernel's decode,
verify (Td = 5), chunk (512 rows behind prefixes of 0, 2 k and 4 k) and
mixed forms. The pool is stored at 640 lanes and the queries are 576 wide (the
dispatcher pads them: what the engine runs); a pool stored at 576 is what
Mosaic refuses (tests/test_deepseek_v2.py compiles both for the chip).

The case ``paths`` is the one whole-model comparison: a prompt
shorter than a prefill chunk is answered by bucketed flash prefill when
cold and by the chunk program behind its cached pages on a prefix-cache
hit — two kernels, so two roundings. Both programs' last-token logits are
held to the all-jnp model's, at ``--model``'s widths with the depth cut
to two layers. ``chip_smoke.py`` runs it as its reference check.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu.ops import attention as A
from gridllm_tpu.ops import kvcache as KV
from gridllm_tpu.ops import pallas_kernels as PK
from gridllm_tpu.ops.kernels import KERNELS

MODEL = "llama3.2:3b"                 # --model: the `paths` case's widths
H, KVH, D = 24, 8, 128
PS, MAXP, S = 128, 128, 8
L, NP = 2, 64
C = 1024
TD_VERIFY = 5
LAYER = 1
# one slot of every kind the engine produces: mid-page, inactive, a single
# cached token, exactly one full page, one past it, and long contexts
LENGTHS = (600, 0, 1, 128, 129, 1023, 2000, 300)
# the g7 cases' shapes: contexts on both sides of the window of 4096
G7 = {"H": 28, "KVH": 4, "NP": 384, "S": 16, "WINDOW": 4096,
      "LENGTHS": (7000, 0, 3000, 4095, 4097, 129, 6200, 300,
                  1, 128, 3071, 5000, 0, 600, 2000, 4096)}
WINDOW = 0
# the mla cases' shapes: one cache head, a row of 512 + 64, 16 query heads
MLA = {"H": 16, "KVH": 1, "NP": 640, "S": 16, "C": 512,
       "LENGTHS": (4000, 0, 2048, 4095, 4097, 129, 2200, 300,
                   1, 128, 3071, 2000, 0, 600, 2049, 4096)}
MLA_ROW, MLA_DV = 576, 512


def _interpret() -> bool:
    """GRIDLLM_PALLAS=interpret rehearses the script on the CPU; unset
    (the chip) the kernels compile through Mosaic."""
    return KV._env_mode()[1]


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


@dataclasses.dataclass
class Case:
    kernel: str                       # KERNELS registry name (tolerance)
    run: Callable[..., Any]           # kernel path, fn(*args)
    ref: Callable[..., Any]           # jnp oracle, fn(*args)
    args: tuple
    # per-output validity masks (None = every element is specified
    # output); broadcast against the output's leading dims
    valid: tuple = ()
    # rtol against the output's largest magnitude instead of element by
    # element: logits, where every element is a dot over the same hidden
    # state and carries the same absolute error whatever its own size
    rel_to_max: bool = False


def _rand(seed: int, shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32
                             ).astype(dtype)


def _pool(seed: int):
    return _rand(seed, (L, NP, PS, KVH, D))


def _page_table() -> np.ndarray:
    """Distinct shuffled pages per slot, -1 past each slot's need (the
    fresh tokens may straddle into one more page than the prefix)."""
    rng = np.random.default_rng(0)
    pages = rng.permutation(NP)
    table = np.full((S, MAXP), -1, np.int32)
    at = 0
    for s, ln in enumerate(LENGTHS):
        n = -(-(ln + TD_VERIFY) // PS)
        table[s, :n] = pages[at:at + n]
        at += n
    assert at <= NP, at
    return table


def _chunk_row() -> np.ndarray:
    """The admitting slot's table row: 16 pages, enough for a 1024-token
    prefix plus one 1024-token chunk."""
    row = np.full((MAXP,), -1, np.int32)
    n = 16 if not WINDOW else 56        # g7: a 6144-token prefix + a chunk
    row[:n] = np.arange(NP - 1, NP - 1 - n, -1)
    return row


def _sibling_tree():
    """The engine's width-2 draft-tree shape at five nodes: the root, a
    3-deep chain under it, and one depth-1 sibling."""
    parents = np.asarray([-1, 0, 1, 2, 0], np.int32)
    from gridllm_tpu.ops.spec import tree_ancestor_mask, tree_depths

    return tree_depths(parents), tree_ancestor_mask(parents)


def _g7(build: Callable[[], Case]) -> Case:
    """`build` under SmallThinker's shapes (the helpers read the module's
    globals when a case is built, never when it runs)."""
    saved = {k: globals()[k] for k in G7}
    globals().update(G7)
    try:
        return build()
    finally:
        globals().update(saved)


def _mla(chunk: bool, td: int, chunk_start: int = 0, stored: int = 640):
    """A latent read under DeepSeek-V2-Lite's shapes: queries as wide as
    the row (576), a pool stored at 640 lanes, no V anywhere."""
    saved = {k: globals()[k] for k in MLA}
    globals().update(MLA)
    try:
        kp = _rand(1, (L, NP, PS, 1, stored))
        if stored != MLA_ROW:     # the padding lanes hold zeros, as written
            kp = kp.at[..., MLA_ROW:].set(0)
        kw: dict[str, Any] = {}
        valid: list = []
        if chunk:
            start, total = chunk_start, chunk_start + C - 60
            row = np.full((MAXP,), -1, np.int32)
            row[:40] = np.arange(NP - 1, NP - 41, -1)
            kw.update(
                q_chunk=_rand(3, (1, C, H, MLA_ROW)),
                chunk_row=jnp.asarray(row),
                chunk_start=jnp.int32(start), chunk_total=jnp.int32(total),
                k_chunk=_rand(4, (C, 1, MLA_ROW)))
            valid.append(np.arange(C)[None, :] < total - start)
        if td:
            lens = np.asarray(LENGTHS, np.int32)
            kw.update(
                q_group=_rand(6, (S, td, H, MLA_ROW)),
                page_table=jnp.asarray(_page_table()),
                group_lengths=jnp.asarray(lens),
                k_group=_rand(7, (S, td, 1, MLA_ROW)))
            valid.append(lens > 0)
    finally:
        globals().update(saved)
    names = sorted(kw)

    def pick(outs):
        return tuple(o for o in outs if o is not None)

    def run(kp, *dyn):
        return pick(A.ragged_paged_attention(
            kp, None, PS, layer=jnp.int32(LAYER), use_pallas=True,
            latent_dv=MLA_DV, **dict(zip(names, dyn))))

    def ref(kp, *dyn):
        return pick(A.ragged_paged_attention_ref(
            kp[..., :MLA_ROW], None, PS, layer=jnp.int32(LAYER),
            latent_dv=MLA_DV, **dict(zip(names, dyn))))

    return Case("ragged_attention", run, ref,
                (kp, *(kw[n] for n in names)), tuple(valid))


def _ragged(chunk: bool, td: int, quant: bool = False, tree: bool = False,
            chunk_start: int = 1024):
    kp, vp = _pool(1), _pool(2)
    if quant:
        kq, ks = KV.quantize_kv_rows(kp)
        vq, vs = KV.quantize_kv_rows(vp)
        kp, vp = KV.QuantPages(kq, ks), KV.QuantPages(vq, vs)
    kw: dict[str, Any] = {}
    valid: list = []
    if WINDOW:
        # a traced scalar, as the layer scan hands it to the kernel
        kw["window"] = jnp.int32(WINDOW)
    if chunk:
        start, total = chunk_start, chunk_start + 900
        kw.update(
            q_chunk=_rand(3, (1, C, H, D)),
            chunk_row=jnp.asarray(_chunk_row()),
            chunk_start=jnp.int32(start), chunk_total=jnp.int32(total),
            k_chunk=_rand(4, (C, KVH, D)), v_chunk=_rand(5, (C, KVH, D)),
        )
        valid.append(np.arange(C)[None, :] < total - start)
    if td:
        lens = np.asarray(LENGTHS, np.int32)
        kw.update(
            q_group=_rand(6, (S, td, H, D)),
            page_table=jnp.asarray(_page_table()),
            group_lengths=jnp.asarray(lens),
            k_group=_rand(7, (S, td, KVH, D)),
            v_group=_rand(8, (S, td, KVH, D)),
        )
        valid.append(lens > 0)
    names = sorted(kw)
    tree_kw = {}
    if tree:
        pos, mask = _sibling_tree()
        tree_kw = {"tree_pos": pos, "tree_mask": mask}

    def pick(outs):
        return tuple(o for o in outs if o is not None)

    def run(kp, vp, *dyn):
        return pick(A.ragged_paged_attention(
            kp, vp, PS, layer=jnp.int32(LAYER), use_pallas=True,
            **dict(zip(names, dyn)), **tree_kw))

    def ref(kp, vp, *dyn):
        return pick(A.ragged_paged_attention_ref(
            kp, vp, PS, layer=jnp.int32(LAYER),
            **dict(zip(names, dyn)), **tree_kw))

    return Case("ragged_attention", run, ref,
                (kp, vp, *(kw[n] for n in names)), tuple(valid))


def _flash(fn, t: int, window: int = 0):
    lens = jnp.asarray([t - 100], jnp.int32)
    args = (_rand(1, (1, t, H, D)), _rand(2, (1, t, KVH, D)),
            _rand(3, (1, t, KVH, D)), lens)
    if window:
        args += (jnp.int32(window),)      # traced, as the layer scan's

    def run(q, k, v, lens, *win):
        return fn(q, k, v, lens, interpret=_interpret(),
                  **({"window": win[0]} if win else {}))

    def ref(q, k, v, lens, *win):
        return A.attention_prefill_ref(
            q, k, v, lens, **({"window": win[0]} if win else {}))

    return Case(fn.__name__, run, ref, args,
                (np.arange(t)[None, :] < t - 100,))


def _write_decode(rows: int):
    """rows = S for a decode step, S * TD_VERIFY for the flattened verify
    append (write_multi_all) — the same kernel either way."""
    kn, vn = _rand(3, (L, rows, KVH, D)), _rand(4, (L, rows, KVH, D))
    rng = np.random.default_rng(1)
    page_idx = rng.permutation(NP)[:rows].astype(np.int32)
    page_idx[1] = NP                     # the "skip this row" sentinel
    offset = rng.integers(0, PS, rows).astype(np.int32)

    def ref(kp, vp, kn, vn, pi, off):
        return (kp.at[:, pi, off].set(kn, mode="drop"),
                vp.at[:, pi, off].set(vn, mode="drop"))

    def run(kp, vp, kn, vn, pi, off):
        return PK.paged_write_decode(kp, vp, kn, vn, pi, off,
                                     interpret=_interpret())

    return Case("paged_write_decode", run, ref,
                (_pool(1), _pool(2), kn, vn, jnp.asarray(page_idx),
                 jnp.asarray(offset)))


def _write_chunk():
    start, length = 1024, 900
    row = _chunk_row()
    kn, vn = _rand(3, (L, C, KVH, D)), _rand(4, (L, C, KVH, D))

    def run(kp, vp, kn, vn, row):
        return PK.paged_write_chunk(kp, vp, kn, vn, row, jnp.int32(start),
                                    jnp.int32(length), page_size=PS,
                                    interpret=_interpret())

    def ref(kp, vp, kn, vn, row):
        # the kernel writes whole pages (the tail of the last one holds
        # padding rows that attention masks); compare the valid rows only
        t = jnp.arange(C, dtype=jnp.int32)
        pos = start + t
        pi = jnp.where(t < length, row[pos // PS], NP)
        return (kp.at[:, pi, pos % PS].set(kn, mode="drop"),
                vp.at[:, pi, pos % PS].set(vn, mode="drop"))

    # mask: every pool row except the padding tail of the last written page
    last_page = int(row[(start + length - 1) // PS])
    mask = np.ones((L, NP, PS), bool)
    mask[:, last_page, (start + length) % PS:] = False
    return Case("paged_write_chunk", run, ref,
                (_pool(1), _pool(2), kn, vn, jnp.asarray(row)),
                (mask, mask))


def _paths():
    """Cold and cached, a short prompt takes two programs: flash prefill
    over its padded bucket, then — on a prefix-cache hit — the chunk
    program over the uncached tail, reading the pages the first one
    wrote. Kernels on against kernels off, same weights, same prompt."""
    from gridllm_tpu.models import llama
    from gridllm_tpu.models.configs import get_config

    cfg = dataclasses.replace(get_config(MODEL), num_layers=L)
    ps = PS if cfg.head_dim_ % 128 == 0 else 16
    n = min(300, cfg.max_seq_len // 2)
    cached = (n - 1) // ps * ps
    bucket = 1 << (n - 1).bit_length()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, min(cfg.vocab_size, 256), n).astype(np.int32)
    toks = np.zeros((bucket,), np.int32)
    toks[:n] = ids
    tail = np.zeros((bucket,), np.int32)
    tail[:n - cached] = ids[cached:]
    row = np.full((MAXP,), -1, np.int32)
    row[:-(-(n + 1) // ps)] = np.arange(-(-(n + 1) // ps))
    cache = KV.PagedKVCache.create(L, NP, ps, cfg.num_kv_heads,
                                   cfg.head_dim_, 1, MAXP)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))

    def both(cfg):
        def fn(params, toks, tail, cache, row):
            cold, cache = llama.prefill(
                params, cfg, toks, jnp.int32(n), cache, jnp.int32(0), row)
            hit, _ = llama.prefill_chunk(
                params, cfg, tail, jnp.int32(cached), jnp.int32(n - cached),
                cache, jnp.int32(0), row)
            return cold, hit
        return fn

    return Case("flash_prefill",
                both(dataclasses.replace(cfg, use_pallas=True)),
                both(dataclasses.replace(cfg, use_pallas=False)),
                (params, jnp.asarray(toks), jnp.asarray(tail), cache,
                 jnp.asarray(row)), rel_to_max=True)


# The engine's path first (ragged attention in every form a step
# launches, the write kernels, bucketed flash prefill), then the kernel
# only a long bucket reaches.
CASES: dict[str, Callable[[], Case]] = {
    "ragged_chunk": lambda: _ragged(True, 0),
    "ragged_decode": lambda: _ragged(False, 1),
    "ragged_verify": lambda: _ragged(False, TD_VERIFY),
    "ragged_tree": lambda: _ragged(False, TD_VERIFY, tree=True),
    "ragged_mixed": lambda: _ragged(True, 1),
    "ragged_int8": lambda: _ragged(True, TD_VERIFY, quant=True),
    "wdecode": lambda: _write_decode(S),
    "wmulti": lambda: _write_decode(S * TD_VERIFY),
    "wchunk": _write_chunk,
    "flash512": lambda: _flash(PK.flash_prefill, 512),
    "flash1024": lambda: _flash(PK.flash_prefill, 1024),
    "streamed": lambda: _flash(PK.flash_prefill_streamed, 1024),
    "paths": _paths,
    "g7_decode": lambda: _g7(lambda: _ragged(False, 1)),
    "g7_verify": lambda: _g7(lambda: _ragged(False, TD_VERIFY)),
    "g7_chunk3k": lambda: _g7(lambda: _ragged(True, 0, chunk_start=2048)),
    "g7_chunk7k": lambda: _g7(lambda: _ragged(True, 0, chunk_start=6144)),
    "g7_mixed7k": lambda: _g7(lambda: _ragged(True, 1, chunk_start=6144)),
    "mla_decode": lambda: _mla(False, 1),
    "mla_verify": lambda: _mla(False, TD_VERIFY),
    "mla_chunk0": lambda: _mla(True, 0, chunk_start=0),
    "mla_chunk2k": lambda: _mla(True, 0, chunk_start=2048),
    "mla_chunk4k": lambda: _mla(True, 0, chunk_start=4096),
    "mla_mixed4k": lambda: _mla(True, 1, chunk_start=4096),
    "g7_flash1024": lambda: _g7(
        lambda: _flash(PK.flash_prefill, 1024, window=4096)),
    "g7_flash1024_w256": lambda: _g7(
        lambda: _flash(PK.flash_prefill, 1024, window=256)),
}


def _tolerance(kernel: str) -> tuple[float, float]:
    spec = next(k for k in KERNELS if k.name == kernel)
    return spec.rtol, spec.atol


def _leaves(x) -> list:
    return [np.asarray(a, np.float32) for a in jax.tree.leaves(x)]


def compare(name: str, case: Case, got, want) -> bool:
    rtol, atol = _tolerance(case.kernel)
    ok = True
    got_l, want_l = _leaves(got), _leaves(want)
    assert len(got_l) == len(want_l), (len(got_l), len(want_l))
    for i, (g, w) in enumerate(zip(got_l, want_l)):
        assert g.shape == w.shape, (g.shape, w.shape)
        mask = case.valid[i] if i < len(case.valid) else None
        if mask is not None:
            mask = np.asarray(mask).reshape(
                mask.shape + (1,) * (g.ndim - mask.ndim))
            mask = np.broadcast_to(mask, g.shape)
            g, w = g[mask], w[mask]
        err = np.abs(g - w)
        ref_mag = np.abs(w).max() if case.rel_to_max and w.size else np.abs(w)
        bad = ~(err <= atol + rtol * ref_mag)     # NaN counts as bad
        line = (f"{name}[{i}] shape={got_l[i].shape} compared={g.size} "
                f"max_err={float(np.nanmax(err)) if g.size else 0.0:.4g} "
                f"rtol={rtol} atol={atol} bad={int(bad.sum())}")
        log(("OK   " if not bad.any() else "FAIL ") + line)
        ok &= not bad.any()
    return ok


def run_case(name: str) -> bool:
    case = CASES[name]()
    t0 = time.perf_counter()
    got = jax.block_until_ready(jax.jit(case.run)(*case.args))
    log(f"{name}: kernel ran in {time.perf_counter() - t0:.1f}s "
        "(set-up: compile included)")
    want = jax.block_until_ready(jax.jit(case.ref)(*case.args))
    return compare(name, case, got, want)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", default=["all"])
    ap.add_argument("--model", default=MODEL,
                    help="the `paths` case's model config (default "
                         f"{MODEL}; tiny-llama rehearses it on the CPU)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    globals()["MODEL"] = args.model
    if args.list:
        print(" ".join(CASES))
        return 0
    names = list(CASES) if args.cases == ["all"] else args.cases
    unknown = [n for n in names if n not in CASES]
    if unknown:
        ap.error(f"unknown case(s) {unknown}; known: {' '.join(CASES)}")
    dev = jax.devices()[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    results = {n: run_case(n) for n in names}
    failed = [n for n, ok in results.items() if not ok]
    log(f"{len(results) - len(failed)}/{len(results)} cases passed"
        + (f"; FAILED: {' '.join(failed)}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
