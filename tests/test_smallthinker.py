"""SmallThinker (routed ReGLU experts behind a router that reads the
pre-attention state; window-with-RoPE and global-NoPE layers mixed 3:1)
against its plain float32 reference, benchmark/reference/smallthinker_f32.py,
on seeded tiny-smallthinker weights. Logits, not tokens; every context is
longer than the tiny window (8), so the window, NoPE and the router's tap
each decide the result, and the reference with one of them broken must
fail the tolerance that the sound one passes."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import llama, mixtral
from gridllm_tpu.models.configs import (
    _config_from_hf_dict,
    get_config,
)
from gridllm_tpu.ops.kvcache import PageAllocator, PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-smallthinker")
# float32 on both sides in another operation order: rounding only. The
# largest difference seen is 5e-7 (logits up to 0.6); bf16 weights in
# float32's place read 0.3, an expert chosen otherwise among them
# (test_bf16_fails_the_tolerance), and each broken mechanism 0.5 to 0.7
TOL = 5e-5


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/smallthinker_f32.py", "smallthinker_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module")
def params():
    return mixtral.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def _ref(params, toks, **kw):
    return np.asarray(REF.logits(params, SIZES, list(toks), **kw))


def _cache():
    return PagedKVCache.create(
        CFG.num_layers, num_pages=16, page_size=8,
        num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim_,
        max_slots=2, max_pages_per_slot=8, dtype=jnp.float32,
    )


def _rows(n_tokens=64):
    alloc = PageAllocator(16, 8, 8)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def test_published_keys_read_as_the_registry_entry():
    with open(os.path.join(
            ROOT, "benchmark/configs/smallthinker-21b-a3b-L12.json")) as f:
        spec = json.load(f)
    whole = {**spec, **{k: v["from"] for k, v in spec["reduced"].items()}}
    got = _config_from_hf_dict("smallthinker:21b", whole, "x")
    assert got == get_config("smallthinker:21b")
    cut = _config_from_hf_dict("cut", spec, "x")
    assert cut.num_layers == 12 and cut.layer_windows == (0, 4096, 4096, 4096) * 3
    assert cut.rope_layout == (0, 1, 1, 1) * 3
    with pytest.raises(ValueError, match="52 entries for 53"):
        _config_from_hf_dict("bad", {**spec, "num_hidden_layers": 53}, "x")
    with pytest.raises(ValueError, match="router"):
        _config_from_hf_dict(
            "bad", {**spec, "moe_primary_router_apply_softmax": False}, "x")


def test_forward_matches_the_reference(params):
    toks = _tokens(48)
    got = np.asarray(mixtral.forward(params, CFG, jnp.asarray(toks)[None]))[0]
    assert np.abs(got - _ref(params, toks)).max() < TOL


@pytest.mark.parametrize("broken", [
    {"window": False}, {"rope_everywhere": True}, {"router_post_attn": True},
    {"skip_layer": 2},
])
def test_a_reference_broken_in_one_mechanism_fails(params, broken):
    toks = _tokens(48)
    got = np.asarray(mixtral.forward(params, CFG, jnp.asarray(toks)[None]))[0]
    assert np.abs(got - _ref(params, toks, **broken)).max() > 100 * TOL


def test_bf16_fails_the_tolerance(params):
    toks = _tokens(48)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                       params)
    got = np.asarray(mixtral.forward(low, CFG, jnp.asarray(toks)[None]))[0]
    assert np.abs(got - _ref(params, toks)).max() > 10 * TOL


def test_prefill_then_decode_through_the_paged_cache(params):
    toks = _tokens(28, seed=1)
    n = 20                                   # > window 8
    row = _rows()[0]
    padded = jnp.zeros((32,), jnp.int32).at[:n].set(jnp.asarray(toks[:n]))
    logits, cache = mixtral.prefill(
        params, CFG, padded, jnp.int32(n), _cache(), jnp.int32(0), row)
    want = _ref(params, toks)
    assert np.abs(np.asarray(logits) - want[n - 1]).max() < TOL
    active = jnp.asarray([True, False])
    for p in range(n, len(toks)):
        tok = jnp.zeros((2,), jnp.int32).at[0].set(int(toks[p]))
        dec, cache, stats = mixtral.decode_step(
            params, CFG, tok, cache, active, with_stats=True)
        assert np.abs(np.asarray(dec[0]) - want[p]).max() < TOL
        # one live row a layer; it touches its top-k experts in each
        assert stats.tolist() == [CFG.num_layers,
                                  CFG.num_layers * CFG.experts_per_token]


def test_chunked_prefill_and_the_mixed_step(params):
    a, b = _tokens(21, seed=2), _tokens(16, seed=3)
    rows = _rows()
    cache = _cache()
    # slot 0: a whole prompt in two chunks of the one chunk program
    for s0, ln in ((0, 16), (16, 4)):
        chunk = jnp.zeros((16,), jnp.int32).at[:ln].set(
            jnp.asarray(a[s0:s0 + ln]))
        logits, cache = mixtral.prefill_chunk(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(ln), cache,
            jnp.int32(0), rows[0])
    want_a = _ref(params, a)
    assert np.abs(np.asarray(logits) - want_a[19]).max() < TOL
    # slot 1 admits its first chunk alone, its second beside slot 0's
    # decode token: one ragged launch a layer
    _, cache = mixtral.prefill_chunk(
        params, CFG, jnp.asarray(b[:8]), jnp.int32(0), jnp.int32(8), cache,
        jnp.int32(1), rows[1])
    tokens = jnp.zeros((2,), jnp.int32).at[0].set(int(a[20]))
    chunk_logits, dec, cache = mixtral.mixed_step(
        params, CFG, jnp.asarray(b[8:16]), jnp.int32(8), jnp.int32(8),
        jnp.int32(1), rows[1], tokens, cache, jnp.asarray([True, False]))
    assert np.abs(np.asarray(chunk_logits) - _ref(params, b)[15]).max() < TOL
    assert np.abs(np.asarray(dec[0]) - want_a[20]).max() < TOL


def test_verify_step(params):
    toks = _tokens(24, seed=4)
    n, t = 20, 4
    row = _rows()[0]
    padded = jnp.zeros((32,), jnp.int32).at[:n].set(jnp.asarray(toks[:n]))
    _, cache = mixtral.prefill(
        params, CFG, padded, jnp.int32(n), _cache(), jnp.int32(0), row)
    cand = jnp.zeros((2, t), jnp.int32).at[0].set(jnp.asarray(toks[n:]))
    logits, cache, stats = mixtral.verify_step(
        params, CFG, cand, cache, jnp.asarray([True, False]), with_stats=True)
    want = _ref(params, toks)
    assert np.abs(np.asarray(logits[0]) - want[n:n + t]).max() < TOL
    # the inactive slot's rows are routed nowhere and counted nowhere
    assert int(stats[0]) == CFG.num_layers * t
    assert CFG.experts_per_token <= int(stats[1]) / CFG.num_layers <= CFG.num_experts


@pytest.mark.parametrize("rows", [5, 40, 80])
def test_the_expert_layers_forms_agree(params, rows, interpreted_kernels):
    """The all-experts einsum, the sorted ragged dispatch and the grouped
    kernel (interpreted) are one function; rows that are not live are
    counted by neither statistic and read by no form's kernel."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, CFG.hidden_size))
    r = jax.random.normal(jax.random.PRNGKey(rows + 1), (rows, CFG.hidden_size))
    top_w, top_i = mixtral._route(CFG, lp, r)
    dense = np.asarray(mixtral._moe_mlp_dense(CFG, lp, x, top_w, top_i))
    ragged = np.asarray(mixtral._moe_mlp_ragged(CFG, lp, x, top_w, top_i))
    np.testing.assert_allclose(ragged, dense, rtol=2e-5, atol=2e-5)
    grouped = np.asarray(
        mixtral._moe_mlp_grouped(CFG, lp, x, top_w, top_i, None))
    np.testing.assert_allclose(grouped, dense, rtol=2e-5, atol=2e-5)
    # the rule of the shape: under the ridge on one chip, nowhere else
    assert mixtral.expert_form(CFG, rows, backend="tpu") == "grouped"
    assert mixtral.expert_form(CFG, rows) == "all_experts"
    assert mixtral.expert_form(dataclasses.replace(CFG, use_pallas=False),
                               rows, backend="tpu") == "all_experts"
    # ReGLU, not SwiGLU: the same weights under mixtral's activation differ
    silu = dataclasses.replace(CFG, expert_act="silu")
    other = np.asarray(mixtral._moe_mlp_dense(silu, lp, x, top_w, top_i))
    assert np.abs(other - dense).max() > 1e-3
    live = jnp.arange(rows) % 2 == 0
    half = np.asarray(mixtral._moe_mlp_grouped(CFG, lp, x, top_w, top_i, live))
    np.testing.assert_allclose(half[::2], dense[::2], rtol=2e-5, atol=2e-5)
    assert not half[1::2].any()
    stats = mixtral._route_stats(CFG, top_i, live)
    touched = len(set(np.asarray(top_i)[::2].ravel().tolist()))
    assert stats.tolist() == [(rows + 1) // 2, touched]


def test_the_router_reads_the_pre_attention_state(params):
    """llama._ffn hands the hook the pre-attention normed state for this
    family and the post-attention one for mixtral."""
    seen = []

    def hook(lp, hx, r):
        seen.append(r is hx)
        return hx, None

    lp, h, pre = {}, jnp.ones((2, 4)), jnp.zeros((2, 4))
    llama._ffn(CFG, hook, lp, h, pre)
    llama._ffn(get_config("tiny-mixtral"), hook, lp, h, pre)
    assert seen == [False, True]
    assert llama._ffn(get_config("tiny-mistral"), lambda lp, hx: hx, lp, h,
                      pre)[1] is None


# sha256 of the jaxpr text of llama.verify_step for tiny-mistral, [2, 5]
# candidates. A dense family's programs carry no per-layer kind, no router
# tap and no statistics: SmallThinker's threading of them must not touch
# what the accepted cells run. A later PR that changes the dense verify
# program on purpose computes the hash anew: PR 49 (one layer body, one
# scan) did, for the same equations as the commit before SmallThinker
# (affe6c5, 3dd20173...) traced, the RoPE frequencies computed after the
# positions and not before.
DENSE_VERIFY_JAXPR = (
    "52ffbf9397e154bc2d4a55f51337386b008fd6ad9cbc0b88d05bf8ef6774de38")


def dense_verify_jaxpr() -> str:
    cfg = get_config("tiny-mistral")
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        cfg.num_layers, num_pages=16, page_size=8,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
        max_slots=2, max_pages_per_slot=8, dtype=jnp.float32))
    cfg = dataclasses.replace(cfg, use_pallas=False)
    text = str(jax.make_jaxpr(
        lambda p, c, t, a: llama.verify_step(p, cfg, t, c, a))(
            params, cache, jax.ShapeDtypeStruct((2, 5), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.bool_)))
    return hashlib.sha256(text.encode()).hexdigest()


def test_a_dense_familys_verify_program_is_unchanged():
    assert dense_verify_jaxpr() == DENSE_VERIFY_JAXPR


@pytest.mark.parametrize("spec", [True, False])
def test_the_engine_serves_it_and_counts_what_it_routed(spec):
    """The normal path: prewarm, a request past the window through the
    verify program (speculation on) or the decode block (off). The
    launches' statistics reach gridllm_moe_* from the fetch they make
    anyway, and the window counter falls behind the context counter once
    contexts pass the window (3 of 4 layers slide over 8)."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
    from gridllm_tpu.obs.perf import (
        MOE_EXPERT_ROWS_TOTAL,
        MOE_EXPERTS_TOUCHED_TOTAL,
        PHASE_SECONDS,
        VERIFY_CTX_TOKENS_TOTAL,
        VERIFY_WINDOW_TOKENS_TOTAL,
    )

    eng = InferenceEngine(EngineConfig(
        model="tiny-smallthinker", max_slots=2, page_size=8, num_pages=64,
        max_pages_per_slot=16, prefill_buckets=(16, 32), prefill_chunk=32,
        seed=0, spec_decode=spec,
    ))
    eng.prewarm()
    m = "tiny-smallthinker"
    before = [c.value(model=m) for c in (
        MOE_EXPERT_ROWS_TOTAL, MOE_EXPERTS_TOUCHED_TOTAL,
        VERIFY_CTX_TOKENS_TOTAL, VERIFY_WINDOW_TOKENS_TOTAL)]
    n0 = PHASE_SECONDS.count(model=m, phase="dispatch_verify")
    res = eng.generate(GenerationRequest(
        id="s1", prompt="a prompt that is longer than the window of eight",
        options={"temperature": 0.0, "num_predict": 12}))
    assert res.done_reason in ("length", "stop") and res.eval_count > 0
    rows, touched, ctx, win = (c.value(model=m) - b for c, b in zip((
        MOE_EXPERT_ROWS_TOTAL, MOE_EXPERTS_TOUCHED_TOTAL,
        VERIFY_CTX_TOKENS_TOTAL, VERIFY_WINDOW_TOKENS_TOTAL), before))
    launches = PHASE_SECONDS.count(model=m, phase="dispatch_verify") - n0
    assert launches > 0
    # one live slot: 1 row a layer a decode launch, K+1 = 5 a verify launch
    assert rows == launches * CFG.num_layers * (5 if spec else 1)
    assert (CFG.experts_per_token * CFG.num_layers * launches <= touched
            <= min(CFG.num_experts, CFG.experts_per_token * (5 if spec else 1))
            * CFG.num_layers * launches)
    # every context is past 8: the global layer reads it all, three read 8
    assert win == pytest.approx(ctx / 4 + launches * 8 * 3 / 4)


@pytest.mark.parametrize("spec", [True, False])
@pytest.mark.parametrize("model", ["tiny-smallthinker", "tiny-mixtral"])
def test_a_one_slot_routed_engine_serves_and_counts(model, spec):
    """One slot (GRIDLLM_MAX_BATCH_SLOTS=1), with speculation and without:
    a launch's statistics are an output of their own beside the block, so
    no width of the block is too narrow to carry them."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
    from gridllm_tpu.obs.perf import MOE_EXPERT_ROWS_TOTAL, PHASE_SECONDS

    cfg = get_config(model)
    eng = InferenceEngine(EngineConfig(
        model=model, max_slots=1, page_size=8, num_pages=32,
        max_pages_per_slot=8, prefill_buckets=(16,), seed=0,
        spec_decode=spec))
    r0 = MOE_EXPERT_ROWS_TOTAL.value(model=model)
    n0 = PHASE_SECONDS.count(model=model, phase="dispatch_verify")
    res = eng.generate(GenerationRequest(
        id="one", prompt="one slot", options={"temperature": 0.0,
                                              "num_predict": 6}))
    assert res.done_reason in ("length", "stop") and res.eval_count > 0
    launches = PHASE_SECONDS.count(model=model, phase="dispatch_verify") - n0
    assert launches > 0
    assert (MOE_EXPERT_ROWS_TOTAL.value(model=model) - r0
            == launches * cfg.num_layers * (5 if spec else 1))


def _served(model, chunk, prompts):
    """(does it admit through the mixed step, a last chunk's width,
    launches by program, the texts) of an engine whose chunk is `chunk`
    wide, and 16 at a prompt's end where the family has two widths."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine

    eng = InferenceEngine(EngineConfig(
        model=model, max_slots=2, page_size=8, num_pages=64,
        max_pages_per_slot=16, prefill_buckets=(16, 32), prefill_chunk=chunk,
        prefill_chunk_narrow=16, seed=0))
    calls = {"prefill": 0, "mixed": 0}

    def counted(name, fn):
        def call(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return call

    eng._prefill_fn = counted("prefill", eng._prefill_fn)
    eng._mixed_chunk_fn = counted("mixed", eng._mixed_chunk_fn)
    texts = [eng.generate(GenerationRequest(
        id=f"r{i}", prompt=p, options={"temperature": 0.0, "num_predict": 10})
    ).text for i, p in enumerate(prompts)]
    # the width of a one-token chunk, first and behind a prefix
    return eng._chunk_width(1, 0), eng._chunk_width(1, chunk), calls, texts


def test_a_routed_family_admits_every_prompt_through_the_mixed_step():
    """A routed family's launch reads every expert whatever rows it
    carries, so its prompts are admitted through the mixed step at one
    width, the chunk's (running streams decode in the admission's launch,
    where a bucketed prefill would stall them for a launch of its own),
    and the bucketed prefill is never called; the tokens are the same at
    another width. A dense family admits the same way since ISSUE 39, with
    the widths of its own: a longer prompt in chunks of 32 with its last
    chunk at 16."""
    from gridllm_tpu.engine import engine

    assert engine.ROUTED_CHUNK == 512             # the width the chip read
    prompts = ["short one", "a prompt of more than sixteen tokens"]   # 10, 37
    first, end, calls, texts = _served("tiny-smallthinker", 32, prompts)
    assert (first, end) == (32, 32)               # one width: no narrow end
    assert calls == {"prefill": 0, "mixed": 1 + 2}
    first, end, calls_narrow, texts_narrow = _served("tiny-smallthinker", 16, prompts)
    assert (first, end) == (16, 16)
    assert calls_narrow == {"prefill": 0, "mixed": 1 + 3}
    assert texts == texts_narrow
    first, end, calls_dense, _ = _served("tiny-llama", 32, prompts)
    assert (first, end) == (32, 16)
    assert calls_dense == {"prefill": 0, "mixed": 1 + 2}


def test_a_dense_family_counts_its_whole_context_as_window_tokens():
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
    from gridllm_tpu.obs.perf import (
        MOE_EXPERT_ROWS_TOTAL,
        VERIFY_CTX_TOKENS_TOTAL,
        VERIFY_WINDOW_TOKENS_TOTAL,
    )

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=2, page_size=8, num_pages=32,
        max_pages_per_slot=8, prefill_buckets=(16,), seed=0))
    c0, w0 = (c.value(model="tiny-llama") for c in (
        VERIFY_CTX_TOKENS_TOTAL, VERIFY_WINDOW_TOKENS_TOTAL))
    eng.generate(GenerationRequest(
        id="d1", prompt="hello", options={"temperature": 0.0, "num_predict": 6}))
    ctx = VERIFY_CTX_TOKENS_TOTAL.value(model="tiny-llama") - c0
    assert ctx > 0
    assert VERIFY_WINDOW_TOKENS_TOTAL.value(model="tiny-llama") - w0 == ctx
    assert MOE_EXPERT_ROWS_TOTAL.value(model="tiny-llama") == 0
