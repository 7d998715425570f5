"""Granite 4.0-H (Mamba-2 state-space layers around one attention layer a
period, the family's four multipliers; a recurrent state a slot beside the
pages) against its plain float32 reference,
benchmark/reference/granite_hybrid_f32.py, on seeded tiny-granite-hybrid
weights. Logits, not tokens. What olmo_hybrid's tests hold for the delta
rule is held here for the scan without the delta: a prompt's chunk launches
carry the state (chunked = token by token), speculation's commit leaves the
state at the accepted row, a re-asked prefix is admitted from pages AND a
snapshot; and every one of the family's own mechanisms (each multiplier,
NoPE, the 1/64-style scale, the convolution, the decay, D x) is shown to
matter: the reference with one switched fails the comparison."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import granite_hybrid as gh
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops import linear_attn as la
from gridllm_tpu.ops.kvcache import (
    PageAllocator,
    PagedKVCache,
    rollback_to_length,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-granite-hybrid")
# float32 on both sides in another operation order (the chunked form takes
# a block's rows at once; the reference runs token by token): rounding
# only. The largest difference seen is 4e-7 (logits up to 0.11); each
# broken mechanism reads 0.016 to 0.16
TOL = 2e-5
PS = 16                                  # page size of the test pools


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/granite_hybrid_f32.py", "granite_hybrid_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return gh.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


TOKENS = np.random.default_rng(0).integers(0, CFG.vocab_size, 96)


@pytest.fixture(scope="module")
def ref_logits(params):
    return np.asarray(REF.logits(params, SIZES, list(TOKENS)))


def _cache(slots=2, rows=5, snapshots=4):
    c = PagedKVCache.create(
        CFG.cache_layers, num_pages=24, page_size=PS,
        num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim_,
        max_slots=slots, max_pages_per_slot=8, dtype=jnp.float32)
    return dataclasses.replace(
        c, rec=gh.new_state(CFG, slots, rows, snapshots, jnp.float32))


def _rows(n_tokens=128):
    alloc = PageAllocator(24, PS, 8)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def _chunks(params, toks, cache, slot, row, width, start=0, state_io=None):
    """A prompt admitted as the engine admits it, through `mixed_step`
    with no active slot, `width` rows a launch."""
    idle = jnp.zeros(cache.lengths.shape, jnp.int32)
    for s0 in range(start, len(toks), width):
        part = toks[s0:s0 + width]
        chunk = jnp.zeros((width,), jnp.int32).at[:len(part)].set(
            jnp.asarray(part))
        logits, _, cache = gh.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0, state_io=state_io)
    return logits, cache


_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {       # ibm-granite/granite-4.0-h-micro config.json
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": _PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def test_published_keys_read_as_the_registry_entry():
    got = _config_from_hf_dict("granite4:h-micro", PUBLISHED, "config.json")
    assert got == get_config("granite4:h-micro")
    assert (got.linear_layers, got.cache_layers) == (36, 4)
    assert gh._period(got) == (10, 5) and gh._period(CFG) == (4, 2)
    assert got.conv_channels == 4352 and got.cache_kinds == ("kv", "state")
    assert got.attention_multiplier == 1 / 64


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("mamba_n_groups", 2),
    ("position_embedding_type", "rope"), ("mamba_proj_bias", True),
    ("attention_bias", True), ("mamba_conv_bias", False),
    ("mamba_expand", 3), ("attention_multiplier", 0)])
def test_what_no_configuration_proves_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        _config_from_hf_dict("x", {**PUBLISHED, key: value}, "config.json")


def test_a_pattern_that_is_not_periods_is_refused():
    odd = dataclasses.replace(
        CFG, layer_types=("linear_attention",) * 7 + ("full_attention",))
    assert gh._period(odd) == (8, 7)
    with pytest.raises(ValueError, match="whole periods"):
        gh._period(dataclasses.replace(
            CFG, layer_types=("full_attention",) * 2
            + ("linear_attention",) * 6))


def test_the_configuration_file_reads_back_as_its_base(monkeypatch):
    """benchmark/configs/granite-4.0-h-micro.json is the registry's
    granite4:h-micro in every field: nothing is reduced."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    lw = _load("benchmark/launch_worker.py", "launch_worker_for_granite")
    with open(os.path.join(
            ROOT, "benchmark/configs/granite-4.0-h-micro.json")) as f:
        spec = json.load(f)
    cfg = lw.model_config(spec, "granite-4.0-h-micro", rehearse=False)
    assert dataclasses.replace(cfg, name="granite4:h-micro") == get_config(
        "granite4:h-micro")
    assert spec["reduced"] == {}
    assert {k: spec[k] for k in PUBLISHED} == PUBLISHED
    assert lw.model_config(spec, "x", rehearse=True).family == "granite_hybrid"


def test_forward_matches_the_reference(params, ref_logits):
    got = np.asarray(gh.forward(params, CFG, jnp.asarray(TOKENS)[None]))[0]
    assert np.abs(got - ref_logits).max() < TOL


@pytest.mark.parametrize("broken", [
    {"skip_layer": 1}, {"skip_layer": 2}, {"no_conv": True},
    {"no_decay": True}, {"no_skip": True}, {"attn_scale_rsqrt": True},
    {"rope_theta": 10000.0}, {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"logits_scaling": 1.0},
    {"round_to": "float8_e4m3fn"}], ids=lambda b: next(iter(b)))
def test_a_reference_broken_in_one_mechanism_fails(params, ref_logits, broken):
    wrong = np.asarray(REF.logits(params, SIZES, list(TOKENS), **broken))
    assert np.abs(wrong - ref_logits).max() > 100 * TOL


# -- the scan's forms ---------------------------------------------------------


def _scan_rows(t, heads=4, dk=16, dv=32, seed=1):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(t, dk)), jnp.float32)
    k = jnp.asarray(r.normal(size=(t, dk)), jnp.float32)
    v = jnp.asarray(r.normal(size=(t, heads, dv)), jnp.float32)
    # log decays from a thousandth to several a token: a block of 16 rows
    # reaches exp(-60), which the split form would overflow on
    g = -jnp.exp(jnp.asarray(2 * r.normal(size=(t, heads)) - 1, jnp.float32))
    return q, k, v, g


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    from gridllm_tpu.ops.kvcache import _env_mode
    _env_mode.cache_clear()
    yield
    _env_mode.cache_clear()


@pytest.mark.parametrize("kernel", [False, True])
def test_ssd_chunk_matches_recurrent(kernel, interpreted):
    """The chunked form (the jnp chain, and the Pallas kernel interpreted)
    from a CARRIED state = token by token, the state at chosen blocks'
    ends too."""
    rows = _scan_rows(64)
    s0 = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 32)), jnp.float32)
    want_o, want_s = la.ssd_recurrent(s0, *rows)
    _, mid = la.ssd_recurrent(s0, *(z[:32] for z in rows))
    o, s, kept = la.ssd_chunk(la.pack(s0), *rows, jnp.asarray([1, -1, 3]), 16,
                              use_pallas=kernel)
    assert float(jnp.abs(o - want_o).max()) < 1e-4
    assert float(jnp.abs(la.unpack(s, 4) - want_s).max()) < 1e-5
    assert float(jnp.abs(la.unpack(kept[0], 4) - mid).max()) < 1e-5
    assert float(jnp.abs(kept[1]).max()) == 0.0
    assert float(jnp.abs(la.unpack(kept[2], 4) - want_s).max()) < 1e-5


@pytest.mark.parametrize("kernel", [False, True])
def test_ssd_step_matches_recurrent(kernel, interpreted):
    """A step launch: the pending rows that count are committed and
    written, the new rows run on top and are not; a slot that is not live
    keeps its state and is zeroed before any product; the other layer's
    states are untouched."""
    from gridllm_tpu.ops.kvcache import _env_mode

    slots, t = 3, 5
    states = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, slots, 16, 128)), jnp.float32)
    pend = [jnp.stack(z) for z in zip(*[_scan_rows(t, seed=10 + s)[1:]
                                        for s in range(slots)])]
    new = [jnp.stack(z) for z in zip(*[_scan_rows(t, seed=20 + s)
                                       for s in range(slots)])]
    n = jnp.asarray([0, 2, 5])
    live = jnp.asarray([True, True, False])
    got, o = la.ssd_step(states, jnp.int32(1), tuple(pend), n, *new, live,
                         use_pallas=kernel)
    assert float(jnp.abs(got[0] - states[0]).max()) == 0.0
    for s, kept in enumerate((0, 2, 0)):
        _, want = la.ssd_recurrent(
            la.unpack(states[1, s], 4), pend[0][s][:kept],
            *(z[s][:kept] for z in pend))
        assert float(jnp.abs(la.unpack(got[1, s], 4) - want).max()) < 1e-5
        want_o, _ = la.ssd_recurrent(want, *(z[s] for z in new))
        if live[s]:
            assert float(jnp.abs(o[s] - want_o).max()) < 1e-4
    # junk where nothing counts (a slot that was not live, rows that were
    # rejected: NaN on the chip) reaches neither a state nor a live output
    nan = jnp.float32(jnp.nan)
    dirty_pend = [z.at[0].set(nan).at[1, 2:].set(nan).at[2].set(nan)
                  for z in pend]
    dirty_new = [z.at[2].set(nan) for z in new]
    _env_mode.cache_clear()
    clean, o2 = la.ssd_step(states, jnp.int32(1), tuple(dirty_pend), n,
                            *dirty_new, live, use_pallas=kernel)
    assert float(jnp.abs(clean - got).max()) == 0.0
    assert float(jnp.abs(o2[:2] - o[:2]).max()) == 0.0
    assert float(jnp.abs(o2[2]).max()) == 0.0
    # nothing live (a mixed launch into an idle engine): nothing moves
    _env_mode.cache_clear()
    idle, _ = la.ssd_step(states, jnp.int32(1), tuple(pend), n, *new,
                          jnp.zeros((slots,), bool), use_pallas=kernel)
    assert float(jnp.abs(idle - states).max()) == 0.0


def test_the_convolution_takes_a_bias():
    x = jnp.asarray(np.random.default_rng(4).normal(size=(7, 6)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(5).normal(size=(4, 6)), jnp.float32)
    b = jnp.arange(6, dtype=jnp.float32)
    plain = la.causal_conv(x, w)
    assert plain.shape == (4, 6)
    want = jax.nn.silu(sum(x[i:i + 4] * w[i] for i in range(4)) + b)
    assert float(jnp.abs(la.causal_conv(x, w, b) - want).max()) < 1e-6
    assert float(jnp.abs(la.causal_conv(x, w, 0 * b) - plain).max()) == 0.0


# -- through the cache -------------------------------------------------------


def test_prefill_then_decode_through_both_caches(params, ref_logits):
    """Chunked prefill then decode steps = the reference's full forward,
    logits at every position."""
    row = _rows()[0]
    lg, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    assert np.abs(np.asarray(lg) - ref_logits[69]).max() < TOL
    active = jnp.asarray([True, False])
    step = jax.jit(lambda c, t: gh.decode_step(params, CFG, t, c, active))
    for p in range(70, 96):
        lg, cache = step(cache, jnp.asarray([TOKENS[p], 0]))
        assert np.abs(np.asarray(lg[0]) - ref_logits[p]).max() < TOL


def test_three_chunk_launches_equal_one(params, ref_logits):
    row = _rows()[0]
    three, c3 = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    one, c1 = _chunks(params, TOKENS[:70], _cache(), 0, row, 96)
    assert np.abs(np.asarray(three) - np.asarray(one)).max() < TOL
    assert float(jnp.abs(c3.rec.state[:, 0] - c1.rec.state[:, 0]).max()) < TOL
    assert float(jnp.abs(c3.rec.conv[:, 0] - c1.rec.conv[:, 0]).max()) < TOL


@pytest.mark.parametrize("accepted", [0, 2, 4])
def test_verify_then_commit_equals_sequential_decode(params, ref_logits, accepted):
    """A verify launch of K + 1 = 5 rows of which speculation accepts
    `accepted` drafts (so 1 + accepted rows count, the others are
    REJECTED): the next step reads the state and the convolution rows of
    exactly that many decode steps."""
    row = _rows()[0]
    _, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    active = jnp.asarray([True, False])
    lg, after = gh.verify_step(
        params, CFG, jnp.asarray([TOKENS[70:75], [0] * 5]), cache, active)
    assert np.abs(np.asarray(lg[0]) - ref_logits[70:75]).max() < TOL
    n_emit = jnp.asarray([1 + accepted, 0])
    after = rollback_to_length(after, after.lengths + n_emit)
    after = gh.commit_verify(after, n_emit, active)
    seq = cache
    for p in range(70, 71 + accepted):
        _, seq = gh.decode_step(params, CFG, jnp.asarray([TOKENS[p], 0]), seq,
                                active)
    nxt = jnp.asarray([TOKENS[71 + accepted], 0])
    got, got_c = gh.decode_step(params, CFG, nxt, after, active)
    want, want_c = gh.decode_step(params, CFG, nxt, seq, active)
    assert np.abs(np.asarray(got[0]) - ref_logits[71 + accepted]).max() < TOL
    assert np.abs(np.asarray(got[0] - want[0])).max() < TOL

    def apart(a, b):       # relative to the largest value held
        return max(float(jnp.abs(x[:, 0] - y[:, 0]).max() / jnp.abs(y[:, 0]).max())
                   for x, y in ((a.rec.state, b.rec.state),
                                (a.rec.conv, b.rec.conv)))

    assert apart(got_c, want_c) < 5 * TOL
    if accepted < 4:
        over = gh.commit_verify(after, n_emit + 1, active)
        _, over_c = gh.decode_step(params, CFG, nxt, over, active)
        assert apart(over_c, want_c) > 150 * TOL


def test_a_chunk_launch_saves_and_a_restore_resumes(params, ref_logits):
    """A chunk launch hands back the state at page boundaries it passes;
    a slot restored from one and given the rest of the prompt (its pages
    the first asker's) says what the cold admission says."""
    rows = _rows()
    io = (jnp.asarray([32, 48], jnp.int32), jnp.asarray([2, 0], jnp.int32))
    cold, cache = _chunks(params, TOKENS[:70], _cache(), 0, rows[0], 96,
                          state_io=io)
    shared = rows[0].at[3:].set(rows[1][3:])
    cache = dataclasses.replace(cache, rec=cache.rec.restore(1, 0))
    warm, _ = _chunks(params, TOKENS[:70], cache, 1, shared, 32, start=48)
    assert np.abs(np.asarray(warm) - np.asarray(cold)).max() < TOL
    # the other entry holds the state at 32: another past, another answer
    cache = dataclasses.replace(cache, rec=cache.rec.restore(1, 2))
    wrong, _ = _chunks(params, TOKENS[:70], cache, 1, shared, 32, start=48)
    assert np.abs(np.asarray(wrong) - np.asarray(cold)).max() > 100 * TOL


def test_the_mixed_step_serves_a_chunk_beside_running_slots(params, ref_logits):
    """Slot 0 decodes while slot 1's prompt is admitted over two mixed
    launches: both read what the reference reads."""
    rows = _rows()
    _, cache = _chunks(params, TOKENS[:40], _cache(), 0, rows[0], 64)
    active = jnp.asarray([True, False])
    for i, s0 in enumerate((0, 32)):
        part = TOKENS[s0:min(s0 + 32, 50)]
        chunk = jnp.zeros((32,), jnp.int32).at[:len(part)].set(jnp.asarray(part))
        cl, dl, cache = gh.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(1), rows[1], jnp.asarray([TOKENS[40 + i], 0]), cache,
            active)
        assert np.abs(np.asarray(dl[0]) - ref_logits[40 + i]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[49]).max() < TOL
    lg, _ = gh.decode_step(
        params, CFG, jnp.asarray([TOKENS[42], TOKENS[50]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[50]).max() < TOL


def test_junk_left_by_an_idle_slot_reaches_nothing(params, ref_logits):
    """NaN in every place that does not count (slot 1's state, convolution
    rows and pending rows; slot 0's rejected pending rows) changes no
    logit (PERF.md, PR 42 finding 3: on the chip a kernel's output for a
    slot that is not live is memory nobody wrote)."""
    rows = _rows()
    _, cache = _chunks(params, TOKENS[:40], _cache(), 0, rows[0], 64)
    only0 = jnp.asarray([True, False])
    _, cache = gh.verify_step(
        params, CFG, jnp.asarray([TOKENS[40:45], [0] * 5]), cache, only0)
    n_emit = jnp.asarray([2, 0])
    cache = gh.commit_verify(
        rollback_to_length(cache, cache.lengths + n_emit), n_emit, only0)
    rec = cache.rec
    nan = jnp.nan
    ch = rec.pend_x.shape[-1] // rec.step_rows
    rec = dataclasses.replace(
        rec, state=rec.state.at[:, 1].set(nan), conv=rec.conv.at[:, 1].set(nan),
        pend_x=rec.pend_x.at[:, 1].set(nan).at[:, 0, 2 * ch:].set(nan),
        pend_v=rec.pend_v.at[:, 1].set(nan).at[:, 0, 2 * 4 * 32:].set(nan),
        **{f: getattr(rec, f).at[:, 1].set(nan).at[:, 0, 2:].set(nan)
           for f in ("pend_k", "pend_g")},
        pend_n=rec.pend_n.at[1].set(3))
    cache = dataclasses.replace(cache, rec=rec)
    chunk = jnp.zeros((32,), jnp.int32).at[:20].set(jnp.asarray(TOKENS[:20]))
    cl, dl, cache = gh.mixed_step(
        params, CFG, chunk, jnp.int32(0), jnp.int32(20), jnp.int32(1),
        rows[1], jnp.asarray([TOKENS[42], 0]), cache, only0)
    assert np.abs(np.asarray(dl[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[19]).max() < TOL
    lg, cache = gh.decode_step(
        params, CFG, jnp.asarray([TOKENS[43], TOKENS[20]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[43]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[20]).max() < TOL
    assert bool(jnp.isfinite(cache.rec.state).all())


# -- the engine ---------------------------------------------------------------


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    kw = {"max_slots": 2, "num_pages": 48, **kw}
    return InferenceEngine(EngineConfig(
        model="tiny-granite-hybrid", dtype="float32", page_size=PS,
        max_pages_per_slot=12, prefill_buckets=(32, 128),
        prefill_chunk=64, prefill_chunk_narrow=32, seed=0, **kw))


def _ask(eng, rid, prompt, n=8):
    from gridllm_tpu.engine import GenerationRequest

    return eng.generate(GenerationRequest(
        id=rid, prompt=prompt, options={"temperature": 0.0, "num_predict": n}))


WORDS = ("the quick brown fox jumps over the lazy dog and keeps running "
         "through the field until night falls on the hills beyond it ")


@pytest.fixture(scope="module")
def cold_engine():
    return _engine(prefix_cache=False)


def _hits():
    from gridllm_tpu.obs import default_registry

    return default_registry().get("gridllm_state_prefix_total").value(
        model="tiny-granite-hybrid", outcome="hit")


@pytest.mark.parametrize("doc_len", [99, 107])
def test_a_reasked_prefix_is_admitted_from_pages_and_a_snapshot(
        cold_engine, doc_len):
    """The re-ask restores the state at 96 beside the pages and says what
    a cold admission says (greedy tokens of two engines, one program)."""
    eng = _engine()
    doc = (WORDS * 2)[:doc_len]
    hits = _hits()
    first = _ask(eng, "a", doc + " one two")
    again = _ask(eng, "b", doc + " six ten")
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert _hits() == hits + 1
    assert again.token_ids == _ask(cold_engine, "c", doc + " six ten").token_ids
    assert first.token_ids == _ask(cold_engine, "d", doc + " one two").token_ids


def test_the_engine_accounts_for_the_state_at_48_slots():
    """48-slot shapes construct: the state's keys are a group, the
    convolution's channels x, B, C, and the gauges say the bytes."""
    from gridllm_tpu.obs import default_registry

    eng = _engine(max_slots=48, num_pages=96)
    shape = eng.batch_state()["shape"]
    assert shape["cacheRow"] == "kv+state"
    assert eng.cache.k.shape[0] == CFG.cache_layers == 2
    rec = eng.cache.rec
    assert rec.state.shape == (6, 48, 16, 128) and rec.step_rows == 5
    assert rec.pend_k.shape == (6, 48, 5, 1, 16)
    assert rec.pend_b.shape == (6, 48, 5, 0)
    assert rec.pend_v.shape == (6, 48, 5 * 4 * 32)
    assert rec.conv.shape == (6, 48, 3 * CFG.conv_channels)
    assert CFG.conv_channels == 4 * 32 + 2 * 16
    mem = eng.memory_arrays()
    assert mem["alloc"]["stateBytes"]["slots"] == rec.slot_nbytes
    reg = default_registry()
    assert reg.get("gridllm_state_bytes").value(
        model="tiny-granite-hybrid", kind="snapshot") == rec.snap_nbytes
    out = _ask(eng, "many", "forty-eight slots, one asker", n=6)
    assert len(out.token_ids) == 6


def test_a_mesh_and_a_tree_of_drafts_are_refused(params):
    with pytest.raises(ValueError, match="one device only"):
        gh.validate_mesh(CFG, object())
    with pytest.raises(NotImplementedError, match="tree verification"):
        gh.verify_step(params, CFG, jnp.zeros((2, 5), jnp.int32), _cache(),
                       jnp.asarray([True, False]), tree_pos=jnp.arange(5))


def test_checkpoints_are_not_read():
    from gridllm_tpu.engine.loader import load_checkpoint

    with pytest.raises(NotImplementedError, match="seeded weights"):
        load_checkpoint(CFG, "/nonexistent")


# -- compiled for the chip, without the chip --------------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_scan_kernels_compile_for_the_chip(one_chip):
    """Mosaic takes both kernels at granite-4.0-h-micro's geometry (64
    heads of 64 over a state of 128, B and C one group: the packed state
    [128, 4096] in lane tiles of 1,024), and the step kernel updates the
    3.6 GB of states of 48 slots in place."""
    def real(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    h, dk, dv, t, s = 64, 128, 64, 1024, 48
    assert la.ssd_lane_tile(h * dv) == 1024
    jax.jit(lambda st, q, k, v, g, keep: la.ssd_chunk(
        st, q, k, v, g, keep, 64, use_pallas=True)).lower(
            real((dk, h * dv)), real((t, dk)), real((t, dk)),
            real((t, h, dv)), real((t, h)), real((2,), jnp.int32)).compile()
    rows = [real((s, 5, dk)), real((s, 5, dk)), real((s, 5, h, dv)),
            real((s, 5, h))]
    step = jax.jit(lambda st, li, pend, n, new, live: la.ssd_step(
        st, li, pend, n, *new, live, use_pallas=True),
        donate_argnums=(0,)).lower(
            real((36, s, dk, h * dv)), real((), jnp.int32), tuple(rows[1:]),
            real((s,), jnp.int32), tuple(rows), real((s,), jnp.bool_)).compile()
    assert step.memory_analysis().alias_size_in_bytes >= 36 * s * dk * h * dv * 4


def _page_kernels(pool_d: int, one_chip):
    """The ragged kernel (a mixed launch; a verify launch of 5 rows) and
    the three page writes at granite-4.0-h-micro's attention geometry (32
    heads of 64 over 8 KV heads, 48 slots, pages of 128, four layers that
    own pages), over a pool whose head is `pool_d` lanes wide, called as
    the dispatchers call them: name -> a function that compiles it."""
    from gridllm_tpu.ops import pallas_kernels as pk

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, d = jnp.int32, pool_d
    s, c, h, kvh, ps, t = 48, 512, 32, 8, 128, 5
    pool = real((4, 300, ps, kvh, d))
    table, lens = real((s, 64), i32), real((s,), i32)

    def compiles(f, *args):
        return lambda: jax.jit(f).lower(*args).compile()

    return {
        "ragged_attention, mixed": compiles(
            lambda k, v, li, qc, row, a, b, kc, vc, qg, tb, ln, kg, vg:
            pk.ragged_attention(
                k, v, ps, q_chunk=qc, chunk_row=row, chunk_start=a,
                chunk_total=b, k_chunk=kc, v_chunk=vc, q_group=qg,
                page_table=tb, group_lengths=ln, k_group=kg, v_group=vg,
                layer=li),
            pool, pool, real((), i32), real((1, c, h, d)), real((64,), i32),
            real((), i32), real((), i32), real((c, kvh, d)),
            real((c, kvh, d)), real((s, 1, h, d)), table, lens,
            real((s, 1, kvh, d)), real((s, 1, kvh, d))),
        "ragged_attention, verify": compiles(
            lambda k, v, li, qg, tb, ln, kg, vg: pk.ragged_attention(
                k, v, ps, q_group=qg, page_table=tb, group_lengths=ln,
                k_group=kg, v_group=vg, layer=li),
            pool, pool, real((), i32), real((s, t, h, d)), table, lens,
            real((s, t, kvh, d)), real((s, t, kvh, d))),
        "paged_write_chunk": compiles(
            lambda k, v, kn, vn, row, a, b: pk.paged_write_chunk(
                k, v, kn, vn, row, a, b, ps),
            pool, pool, real((4, c, kvh, d)), real((4, c, kvh, d)),
            real((64,), i32), real((), i32), real((), i32)),
        "paged_write_decode, a row a slot": compiles(
            pk.paged_write_decode, pool, pool, real((4, s, kvh, d)),
            real((4, s, kvh, d)), real((s,), i32), real((s,), i32)),
        "paged_write_decode, verify rows": compiles(
            pk.paged_write_decode, pool, pool, real((4, 128, kvh, d)),
            real((4, 128, kvh, d)), real((128,), i32), real((128,), i32)),
    }


def test_a_64_wide_kv_head_is_stored_at_128_lanes_because_mosaic_refuses_it_flat(
        one_chip):
    """Why `engine._pool_head_dim` knows one layout where kernels compile:
    over a [.., 8, 64] pool (page rows lane-aligned viewed flat, 8 x 64 =
    512) Mosaic refuses EVERY kernel that touches a page, and over the
    same pool at 128 lanes (the model's 64 zero-padded at the dispatchers'
    boundary) it takes them all."""
    for compile_it in _page_kernels(128, one_chip).values():
        compile_it()
    for compile_it in _page_kernels(64, one_chip).values():
        with pytest.raises(
                Exception, match=r"must be aligned to tiling \(128\), but is 64"):
            compile_it()
