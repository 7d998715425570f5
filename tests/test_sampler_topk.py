"""The sampler's top-128 candidates in exact stages (ops/sampling.py,
PR 48): each 128-wide block's maximum, then the 128 winning blocks laid
out in ascending order, and those 16,384 values again by blocks of 16.
Held bit for bit to `jax.lax.top_k`, values and
ids, at the vocabularies the benchmark's models have and on the rows that
could part the two (ties across the 128th place, all-equal rows, `-inf`,
every winner in one block); then the callers, token for token against the
same program traced with the one pass in `_topk_candidates`' place (a
monkeypatch in the test: the program has no switch); then what the
engine's `verify_block` lowers to at a wide vocabulary and a narrow one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.ops import sampling
from gridllm_tpu.ops.sampling import (
    TOPK,
    SamplingParams,
    _sampler_dists,
    _topk_candidates,
    _topk_staged,
    sample_tokens,
    spec_accept,
    spec_accept_tree,
    topk_stages,
)

# 1,000 has fewer than 128 blocks; 100,352 is no multiple of 512
WIDTHS = (1_000, 32_768, 100_352, 102_400, 131_072, 151_936)
ROWS = 3


def _rows(kind: str, v: int) -> jnp.ndarray:
    x = np.random.default_rng(v % 9973 + len(kind)).normal(
        0.0, 3.0, (ROWS, v)).astype(np.float32)
    if kind == "quarter":       # hundreds of ties across the 128th place
        x = np.round(x) / 4.0
    elif kind == "equal":
        x[:] = 1.5
    elif kind == "neg_inf":     # fewer than 128 finite values in row 0
        x[0, 100:] = -np.inf
        x[1, ::2] = -np.inf
        x[2] = -np.inf
    elif kind == "one_block":   # the 128 largest lie in one block
        at = (v // 2) // 128 * 128
        x[:, at:at + 128] += 100.0
    return jnp.asarray(x)


KINDS = ("normal", "quarter", "equal", "neg_inf", "one_block")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("v", WIDTHS)
def test_the_stages_are_lax_top_k_bit_for_bit(v, kind):
    x = _rows(kind, v)
    want_vals, want_idx = jax.lax.top_k(x, TOPK)
    vals, idx = _topk_staged(x, TOPK, sampling._TOPK_BLOCKS)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))


@pytest.mark.parametrize("blocks", [
    (128,), (256,), (512,), (16,), (128, 16), (128, 32), (512, 16, 4)])
@pytest.mark.parametrize("v", WIDTHS)
def test_the_stages_under_jit_in_every_form(v, blocks):
    """The forms deploy/tpu_sampler_forms.py times, compiled: ties across
    the 128th place are where a wrong order of the winning blocks shows."""
    x = _rows("quarter", v)
    want_vals, want_idx = jax.jit(lambda a: jax.lax.top_k(a, TOPK))(x)
    vals, idx = jax.jit(lambda a: _topk_staged(a, TOPK, blocks))(x)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))


def test_the_ascending_order_of_the_winning_blocks_is_what_breaks_ties():
    """Without the sort the next stage would order equal values by the
    rank of their blocks' maxima, not by id: the check that the sort is
    load-bearing, on a row where a later block has the larger maximum."""
    x = np.zeros((1, 128 * 200), np.float32)
    x[0, 128 * 150] = 2.0           # the largest maximum, a late block
    x[0, 128 * 3: 128 * 3 + 128] = 1.0   # 128 ties in an early block
    x[0, 128 * 150 + 1: 128 * 150 + 128] = 1.0
    want = jax.lax.top_k(jnp.asarray(x), TOPK)
    got = _topk_staged(jnp.asarray(x), TOPK, (128,))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(want[1][0, 1]) == 128 * 3    # ties go to the lower ids


def _lowered(fn, *shapes) -> str:
    """StableHLO for the TPU platform: nothing is compiled, so no chip
    and no described topology is needed."""
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _sorting_ops(stablehlo: str) -> list[str]:
    """The lines that name a `top_k` or a sort with its operand's type
    (`chlo.top_k(..) : tensor<..>`, `call @sort(..) : (tensor<..>)`)."""
    return [line.strip() for line in stablehlo.splitlines()
            if "chlo.top_k" in line or "call @sort" in line]


def _is_wide(op: str, v: int) -> bool:
    operand = op.split(" : ", 1)[1].split("->")[0]
    return f"x{v}x" in operand


@pytest.mark.parametrize("v,stages", [
    (256, 1), (2_049, 1), (16_384, 1), (16_385, 3), (32_768, 3),
    (100_352, 3), (102_400, 3), (131_072, 3), (151_936, 3),
])
def test_the_form_is_a_rule_of_the_width_alone(v, stages):
    """A row of 128 blocks of 128 or fewer keeps the one pass; a wider one
    takes a top_k of its block maxima, one of the 1,024 maxima of the
    winning blocks' blocks of 16, and one of the 2,048 values left, with a
    sort of 128 block ids between them: none over the row."""
    assert topk_stages(v) == stages
    ops = _sorting_ops(_lowered(lambda a: _topk_candidates(a, TOPK),
                                jax.ShapeDtypeStruct((2, v), jnp.float32)))
    assert any(_is_wide(op, v) for op in ops) == (stages == 1)
    tops = [op for op in ops if "chlo.top_k" in op]
    assert len(tops) == stages and len(ops) == 2 * stages - 1
    if stages == 3:
        assert [_is_wide(op, w) for op, w in zip(
            tops, (-(-v // 128), 1_024, 2_048))] == [True] * 3


def _params(s: int, top_k: int, seed: int) -> SamplingParams:
    sp = SamplingParams.defaults(s)
    return dataclasses.replace(
        sp, temperature=jnp.full((s,), 0.8, jnp.float32),
        top_k=jnp.full((s,), top_k, jnp.int32),
        top_p=jnp.full((s,), 0.9, jnp.float32),
        min_p=jnp.full((s,), 0.05, jnp.float32),
        seed=jnp.arange(s, dtype=jnp.int32) + 1000 * seed,
        step=jnp.arange(s, dtype=jnp.int32) * 3)


V_WIDE = 70_001          # a made-up vocabulary, no multiple of 128
S, K1, W = 4, 5, 16
PARENTS = (-1, 0, 1, 0, 3)   # a tree of five nodes: two chains from the root


def _case(seed: int):
    """Logits whose candidates tie (rounded to a quarter, so `keep` cuts
    through ties), counts that penalise some of the top, drafts that are
    each row's likeliest ids so that some are accepted."""
    rng = np.random.default_rng(seed)
    logits = (np.round(rng.normal(0.0, 2.0, (S, K1, V_WIDE)) * 4.0) / 4.0
              ).astype(np.float32)
    top = np.argsort(-logits, axis=-1, kind="stable")[..., :4]
    logits[..., 0] += 2.0 * (rng.random((S, K1)) < 0.5)
    cand = np.zeros((S, K1), np.int32)
    cand[:, 0] = rng.integers(0, V_WIDE, S)
    cand[:, 1:] = top[:, :-1, 0]
    counts = np.zeros((S, V_WIDE), np.int32)
    for s in range(S):      # the repeat penalty reorders the top
        counts[s, top[s, :, rng.integers(0, 4)]] = 1
        counts[s, rng.integers(0, V_WIDE, 40)] += 1
    return logits, cand, counts


_one_pass = jax.lax.top_k     # what `_topk_candidates` was before PR 48


def _run_sample(lg, sp, counts):
    return sample_tokens(lg[:, 0], sp, counts)


def _run_accept(lg, cand, sp, counts):
    out, n_emit, last, counts, *_ = spec_accept(
        lg, cand, jnp.full((S,), K1 - 1, jnp.int32), sp, counts,
        jnp.zeros((S, W), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), bool), V_WIDE)
    return out, n_emit, last, counts


def _run_tree(lg, cand, sp, counts):
    out, path, n_emit, last, counts, *_ = spec_accept_tree(
        lg, cand, PARENTS, jnp.ones((S, K1), bool), sp, counts,
        jnp.zeros((S, W), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), bool), V_WIDE)
    return out, path, n_emit, last, counts


@pytest.fixture(scope="module")
def callers():
    """Each caller compiled twice: as the program has it (three stages at
    this width) and with `_topk_candidates` replaced by the one pass while
    it is traced."""
    assert topk_stages(V_WIDE) == 3
    lg = jax.ShapeDtypeStruct((S, K1, V_WIDE), jnp.float32)
    cand = jax.ShapeDtypeStruct((S, K1), jnp.int32)
    counts = jax.ShapeDtypeStruct((S, V_WIDE), jnp.int32)
    sp = jax.eval_shape(lambda: SamplingParams.defaults(S))
    sigs = {"sample_tokens": (_run_sample, (lg, sp, counts)),
            "spec_accept": (_run_accept, (lg, cand, sp, counts)),
            "spec_accept_tree": (_run_tree, (lg, cand, sp, counts))}
    built = {}
    with pytest.MonkeyPatch.context() as mp:
        for form in ("program", "one_pass"):
            if form == "one_pass":
                mp.setattr(sampling, "_topk_candidates", _one_pass)
            for name, (fn, args) in sigs.items():
                built[name, form] = jax.jit(fn).lower(*args).compile()
    return built


@pytest.mark.parametrize("seed", range(20))
@pytest.mark.parametrize("top_k", (40, 128))
@pytest.mark.parametrize("caller", ("sample_tokens", "spec_accept",
                                    "spec_accept_tree"))
def test_callers_emit_what_they_emit_with_the_one_pass(callers, caller,
                                                       top_k, seed):
    logits, cand, counts = _case(seed)
    sp = _params(S, top_k, seed)
    args = ((logits, sp, counts) if caller == "sample_tokens"
            else (logits, cand, sp, counts))
    got = callers[caller, "program"](*args)
    want = callers[caller, "one_pass"](*args)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if caller == "spec_accept":     # the case is no walk-over: drafts are
        n_emit = np.asarray(got[1])  # accepted and refused over the seeds
        assert n_emit.min() >= 1 and n_emit.max() <= K1


@pytest.mark.parametrize("v", (100_352, 151_936))
def test_a_repeat_penalty_that_reorders_the_top(v):
    """`_sampler_dists` under jit with counts on the row's largest ids:
    the candidates are those of the PENALISED logits in either form."""
    x = _rows("quarter", v)[:2] + 4.0
    top = np.asarray(jax.lax.top_k(x, 8)[1])
    counts = np.zeros((2, v), np.int32)
    counts[0, top[0, ::2]] = 2
    counts[1, top[1, :5]] = 1
    sp = dataclasses.replace(SamplingParams.defaults(2),
                             repeat_penalty=jnp.full((2,), 1.6, jnp.float32))
    fn = jax.jit(_sampler_dists)
    got = fn(x, sp, jnp.asarray(counts))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sampling, "_topk_candidates", _one_pass)
        # a jit of its own: `fn` holds the trace made with the stages
        want = jax.jit(lambda *a: _sampler_dists(*a))(x, sp, jnp.asarray(counts))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.array_equal(np.asarray(got[1])[:, :8], top)


@pytest.mark.parametrize("caller", ("sample_tokens", "spec_accept"))
def test_logits_sharded_over_the_vocabulary_on_four_devices(caller):
    """`tp:4` leaves the head's logits sharded over the vocabulary and GSPMD
    gathers them for the sampler where it sees fit: the stages over such
    logits emit what the one pass emits on one device (32,768 ids: 256
    blocks of 128, 64 a device)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    v = 32_768
    assert topk_stages(v) == 3
    rng = np.random.default_rng(5)
    logits = (np.round(rng.normal(0.0, 2.0, (S, K1, v)) * 4.0) / 4.0
              ).astype(np.float32)
    cand = np.asarray(jax.lax.top_k(logits, 1)[1][..., 0], np.int32)
    counts = np.zeros((S, v), np.int32)
    sp = _params(S, 40, 3)
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    sharded = jax.device_put(logits, NamedSharding(mesh, P(None, None, "tp")))

    def run(lg):
        if caller == "sample_tokens":
            return sample_tokens(lg[:, 0], sp, counts)
        return spec_accept(
            lg, cand, jnp.full((S,), K1 - 1, jnp.int32), sp, counts,
            jnp.zeros((S, W), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.ones((S,), bool), v)[:3]

    got = jax.jit(run)(sharded)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sampling, "_topk_candidates", _one_pass)
        want = jax.jit(lambda lg: run(lg))(logits)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- what the engine's verify program lowers to ---------------------------------


def _verify_lowering(monkeypatch, vocab: int | None) -> tuple[str, object]:
    """The StableHLO of the engine's own `verify_block` for the
    tiny-smallthinker preset, lowered for the TPU platform (no chip and no
    topology needed: nothing is compiled), its vocabulary widened to
    `vocab` ids where one is given."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine import engine as engine_mod
    from gridllm_tpu.models.configs import get_config

    if vocab is not None:
        monkeypatch.setattr(
            engine_mod, "get_config",
            lambda name: dataclasses.replace(get_config(name), vocab_size=vocab))
    eng = InferenceEngine(EngineConfig(
        model="tiny-smallthinker", max_slots=2, page_size=8, num_pages=32,
        max_pages_per_slot=8, prefill_buckets=(16,), prefill_chunk=16,
        seed=0, spec_decode=True))
    drafts = jnp.zeros((2, 4), jnp.int32)
    traced = eng._verify_fn._fn.trace(
        eng.params, eng.cache, eng.tokens, eng.active, eng.counts,
        eng.window, eng.wlen, eng.sampling, drafts,
        jnp.zeros((2,), jnp.int32), k1=5)
    return traced.lower(lowering_platforms=("tpu",)).as_text(), eng


def test_the_wide_verify_program_sorts_nothing_vocabulary_wide(monkeypatch):
    from gridllm_tpu.engine.engine import _SAMPLER_TOPK_STAGES

    v = 151_936
    text, eng = _verify_lowering(monkeypatch, v)
    assert eng.cfg.vocab_size == v
    assert _SAMPLER_TOPK_STAGES.value(model="tiny-smallthinker") == 3
    ops = _sorting_ops(text)
    assert ops, "the sampler's top_k is in the program"
    assert [op for op in ops if _is_wide(op, v)] == []
    # what it holds in their place: a top_k of the 1,187 block maxima,
    # of the 1,024 maxima of the winners' blocks of 16, of 2,048 values
    for width in (-(-v // 128), 1_024, 2_048):
        assert any(_is_wide(op, width) for op in ops)


def test_the_narrow_verify_program_lowers_as_the_parents_does(monkeypatch):
    """At 128 blocks or fewer `_topk_candidates` IS `lax.top_k`: the preset's
    verify program holds the one pass over its 256 ids, no sort, and the
    same text as with the one pass put in its place by hand."""
    from gridllm_tpu.engine.engine import _SAMPLER_TOPK_STAGES

    text, _ = _verify_lowering(monkeypatch, None)
    assert _SAMPLER_TOPK_STAGES.value(model="tiny-smallthinker") == 1
    # (the router's own top_k of 3 among 8 experts is in the program too)
    ops = [op for op in _sorting_ops(text) if "k = 3)" not in op]
    assert ops and all(_is_wide(op, 256) and "sort" not in op for op in ops)
    monkeypatch.setattr(sampling, "_topk_candidates", _one_pass)
    forced, _ = _verify_lowering(monkeypatch, None)
    assert forced == text
