"""Olmo-Hybrid (gated delta-rule layers 3:1 with full attention; a
recurrent state a slot beside the pages) against its plain float32
reference, benchmark/reference/olmo_hybrid_f32.py, on seeded
tiny-olmo-hybrid weights. Logits, not tokens. Three properties decide
whether the design is sound, and each is held here: a prompt's chunk
launches carry the state (chunked = recurrent), speculation's commit
leaves the state at the accepted row (verify with n kept = n decode
steps), and a re-asked prefix is admitted from pages AND a snapshot (or
computed again: never from a wrong state)."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import olmo_hybrid as oh
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops import linear_attn as la
from gridllm_tpu.ops.kvcache import (
    PageAllocator,
    PagedKVCache,
    rollback_to_length,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-olmo-hybrid")
# float32 on both sides in another operation order (the chunked form
# solves a block's corrections at once; the reference runs token by
# token): rounding only. The largest difference seen is 1e-5 (logits up to
# 0.7); each broken mechanism reads 0.02 to 0.6
TOL = 1e-4
PS = 16                                  # page size of the test pools


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/olmo_hybrid_f32.py", "olmo_hybrid_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return oh.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


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
        c, rec=oh.new_state(CFG, slots, rows, snapshots, jnp.float32))


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
        logits, _, cache = oh.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0, state_io=state_io)
    return logits, cache


PUBLISHED = {       # allenai/Olmo-Hybrid-7B config.json
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
PUBLISHED["layer_types"] = PUBLISHED["layer_types"] * 8


def test_published_keys_read_as_the_registry_entry():
    got = _config_from_hf_dict("olmo-hybrid:7b", PUBLISHED, "config.json")
    assert got == get_config("olmo-hybrid:7b")
    assert (got.linear_layers, got.cache_layers, got.layer_period) == (24, 8, 4)
    assert got.conv_channels == 11520 and got.cache_kinds == ("kv", "state")
    cut = _config_from_hf_dict(
        "cut", {**PUBLISHED, "num_hidden_layers": 20}, "config.json")
    assert (cut.linear_layers, cut.cache_layers) == (15, 5)
    with pytest.raises(ValueError, match="rope_theta"):
        _config_from_hf_dict("x", {**PUBLISHED, "rope_parameters": {
            "rope_theta": 500000.0}}, "config.json")
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(got, num_layers=18).layer_period


def test_the_configuration_file_reads_back_as_its_base(monkeypatch):
    """benchmark/configs/olmo-hybrid-7b-L20.json with `reduced` put back
    is the registry's olmo-hybrid:7b; as run it is 20 layers, 5 of them
    with pages."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    lw = _load("benchmark/launch_worker.py", "launch_worker_for_olmo")
    with open(os.path.join(ROOT, "benchmark/configs/olmo-hybrid-7b-L20.json")) as f:
        spec = json.load(f)
    cfg = lw.model_config(spec, "olmo-hybrid-7b-L20", rehearse=False)
    assert (cfg.num_layers, cfg.cache_layers, cfg.linear_layers) == (20, 5, 15)
    assert dataclasses.replace(
        cfg, name="olmo-hybrid:7b", num_layers=32,
        layer_types=get_config("olmo-hybrid:7b").layer_types) == get_config(
            "olmo-hybrid:7b")
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    assert lw.model_config(spec, "x", rehearse=True).family == "olmo_hybrid"


def test_forward_matches_the_reference(params, ref_logits):
    got = np.asarray(oh.forward(params, CFG, jnp.asarray(TOKENS)[None]))[0]
    assert np.abs(got - ref_logits).max() < TOL


@pytest.mark.parametrize("broken", [
    {"skip_layer": 1}, {"skip_layer": 3}, {"beta_single": True},
    {"no_decay": True}, {"no_conv": True}, {"rope_theta": 500000.0},
    {"round_to": "float8_e4m3fn"}])
def test_a_reference_broken_in_one_mechanism_fails(params, ref_logits, broken):
    wrong = np.asarray(REF.logits(params, SIZES, list(TOKENS), **broken))
    assert np.abs(wrong - ref_logits).max() > 100 * TOL


# -- the delta rule's forms --------------------------------------------------


def _delta_rows(t, heads=4, dk=16, dv=32, seed=1, alike=False):
    r = np.random.default_rng(seed)
    q = la.l2norm(jnp.asarray(r.normal(size=(t, heads, dk)), jnp.float32))
    k = jnp.asarray(r.normal(size=(t, heads, dk)), jnp.float32)
    if alike:       # keys all but equal: the triangular system at its worst
        k = jnp.broadcast_to(k[:1], k.shape) + 0.01 * k
    v = jnp.asarray(r.normal(size=(t, heads, dv)), jnp.float32)
    b = 2 * jax.nn.sigmoid(jnp.asarray(2 * r.normal(size=(t, heads)), jnp.float32))
    g = -0.1 * jnp.exp(jnp.asarray(r.normal(size=(t, heads)), jnp.float32))
    return q * dk ** -0.5, la.l2norm(k), v, b, g


@pytest.mark.parametrize("alike", [False, True])
@pytest.mark.parametrize("kernel", [False, True])
def test_gdn_chunk_matches_recurrent(kernel, alike, monkeypatch):
    """The chunked form (the jnp chain, and the Pallas kernel interpreted)
    from a carried state = token by token, the state at chosen blocks'
    ends too."""
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    from gridllm_tpu.ops.kvcache import _env_mode
    _env_mode.cache_clear()
    rows = _delta_rows(64, alike=alike)
    s0 = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 32)), jnp.float32)
    want_o, want_s = la.gdn_recurrent(s0, *rows)
    _, mid = la.gdn_recurrent(s0, *(z[:32] for z in rows))
    o, s, kept = la.gdn_chunk(la.pack(s0), *rows, jnp.asarray([1, -1, 3]), 16,
                              use_pallas=kernel)
    _env_mode.cache_clear()
    assert float(jnp.abs(o - want_o).max()) < 1e-5
    assert float(jnp.abs(la.unpack(s, 4) - want_s).max()) < 1e-5
    assert float(jnp.abs(la.unpack(kept[0], 4) - mid).max()) < 1e-5
    assert float(jnp.abs(kept[1]).max()) == 0.0
    assert float(jnp.abs(la.unpack(kept[2], 4) - want_s).max()) < 1e-5


@pytest.mark.parametrize("kernel", [False, True])
def test_gdn_step_matches_recurrent(kernel, monkeypatch):
    """A step launch: the pending rows that count are committed and
    written, the new rows run on top and are not; a slot that is not live
    keeps its state; the other layer's states are untouched."""
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    from gridllm_tpu.ops.kvcache import _env_mode
    _env_mode.cache_clear()
    slots, t = 3, 5
    states = jnp.asarray(
        np.random.default_rng(3).normal(size=(2, slots, 16, 128)), jnp.float32)
    pend = [jnp.stack(z) for z in zip(*[_delta_rows(t, seed=10 + s)[1:]
                                        for s in range(slots)])]
    new = [jnp.stack(z) for z in zip(*[_delta_rows(t, seed=20 + s)
                                       for s in range(slots)])]
    n = jnp.asarray([0, 2, 5])
    live = jnp.asarray([True, True, False])
    got, o = la.gdn_step(states, jnp.int32(1), tuple(pend), n, *new, live,
                         use_pallas=kernel)
    _env_mode.cache_clear()
    assert float(jnp.abs(got[0] - states[0]).max()) == 0.0
    for s, kept in enumerate((0, 2, 0)):
        _, want = la.gdn_recurrent(
            la.unpack(states[1, s], 4), pend[0][s][:kept],
            *(z[s][:kept] for z in pend))
        assert float(jnp.abs(la.unpack(got[1, s], 4) - want).max()) < 1e-5
        want_o, _ = la.gdn_recurrent(want, *(z[s] for z in new))
        if live[s]:
            assert float(jnp.abs(o[s] - want_o).max()) < 1e-5
    # junk where nothing counts (a slot that was not live, rows that were
    # rejected: NaN on the chip) reaches neither a state nor a live output
    nan = jnp.float32(jnp.nan)
    dirty_pend = [z.at[0].set(nan).at[1, 2:].set(nan).at[2].set(nan)
                  for z in pend]
    dirty_new = [z.at[2].set(nan) for z in new]
    _env_mode.cache_clear()
    clean, o2 = la.gdn_step(states, jnp.int32(1), tuple(dirty_pend), n,
                            *dirty_new, live, use_pallas=kernel)
    _env_mode.cache_clear()
    assert float(jnp.abs(clean - got).max()) == 0.0
    assert float(jnp.abs(o2[:2] - o[:2]).max()) == 0.0
    assert float(jnp.abs(o2[2]).max()) == 0.0
    # nothing live (a mixed launch into an idle engine): nothing moves
    _env_mode.cache_clear()
    idle, _ = la.gdn_step(states, jnp.int32(1), tuple(pend), n, *new,
                          jnp.zeros((slots,), bool), use_pallas=kernel)
    _env_mode.cache_clear()
    assert float(jnp.abs(idle - states).max()) == 0.0


# -- through the cache -------------------------------------------------------


def test_prefill_then_decode_through_both_caches(params, ref_logits):
    """Chunked prefill then decode steps = the reference's full forward,
    logits at every position."""
    row = _rows()[0]
    lg, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    assert np.abs(np.asarray(lg) - ref_logits[69]).max() < TOL
    active = jnp.asarray([True, False])
    step = jax.jit(lambda c, t: oh.decode_step(params, CFG, t, c, active))
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
    `accepted` drafts (so 1 + accepted rows count): the next step reads
    the state and the convolution rows of exactly that many decode
    steps."""
    row = _rows()[0]
    _, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    active = jnp.asarray([True, False])
    lg, after = oh.verify_step(
        params, CFG, jnp.asarray([TOKENS[70:75], [0] * 5]), cache, active)
    assert np.abs(np.asarray(lg[0]) - ref_logits[70:75]).max() < TOL
    n_emit = jnp.asarray([1 + accepted, 0])
    after = rollback_to_length(after, after.lengths + n_emit)
    after = oh.commit_verify(after, n_emit, active)
    seq = cache
    for p in range(70, 71 + accepted):
        _, seq = oh.decode_step(params, CFG, jnp.asarray([TOKENS[p], 0]), seq,
                                active)
    nxt = jnp.asarray([TOKENS[71 + accepted], 0])
    got, got_c = oh.decode_step(params, CFG, nxt, after, active)
    want, want_c = oh.decode_step(params, CFG, nxt, seq, active)
    assert np.abs(np.asarray(got[0]) - ref_logits[71 + accepted]).max() < TOL
    assert np.abs(np.asarray(got[0] - want[0])).max() < TOL
    # the rows went through another program's products (5 rows a launch
    # against 1): rounding; a row too many reads thirty times that and up
    def apart(a, b):       # relative to the largest value held
        return max(float(jnp.abs(x[:, 0] - y[:, 0]).max() / jnp.abs(y[:, 0]).max())
                   for x, y in ((a.rec.state, b.rec.state),
                                (a.rec.conv, b.rec.conv)))

    assert apart(got_c, want_c) < 5 * TOL
    if accepted < 4:
        over = oh.commit_verify(after, n_emit + 1, active)
        _, over_c = oh.decode_step(params, CFG, nxt, over, active)
        assert apart(over_c, want_c) > 150 * TOL


def test_a_chunk_launch_saves_and_a_restore_resumes(params, ref_logits):
    """A chunk launch hands back the state at page boundaries it passes;
    a slot restored from one and given the rest of the prompt says what
    the cold admission says."""
    rows = _rows()
    io = (jnp.asarray([32, 48], jnp.int32), jnp.asarray([2, 0], jnp.int32))
    cold, cache = _chunks(params, TOKENS[:70], _cache(), 0, rows[0], 96,
                          state_io=io)
    # slot 1 reads slot 0's pages for the first 48 tokens
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
        cl, dl, cache = oh.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(1), rows[1], jnp.asarray([TOKENS[40 + i], 0]), cache,
            active)
        assert np.abs(np.asarray(dl[0]) - ref_logits[40 + i]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[49]).max() < TOL
    lg, _ = oh.decode_step(
        params, CFG, jnp.asarray([TOKENS[42], TOKENS[50]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[50]).max() < TOL


def test_junk_left_by_an_idle_slot_reaches_nothing(params, ref_logits):
    """On the chip a kernel's output for a slot that is not live is memory
    nobody wrote, NaN among it, and it lands in that slot's pending rows
    and state. The next request in the slot must not read it: NaN in
    every place that does not count (slot 1's state, convolution rows and
    pending rows; slot 0's rejected pending rows) changes no logit."""
    rows = _rows()
    _, cache = _chunks(params, TOKENS[:40], _cache(), 0, rows[0], 64)
    only0 = jnp.asarray([True, False])
    _, cache = oh.verify_step(
        params, CFG, jnp.asarray([TOKENS[40:45], [0] * 5]), cache, only0)
    n_emit = jnp.asarray([2, 0])
    cache = oh.commit_verify(
        rollback_to_length(cache, cache.lengths + n_emit), n_emit, only0)
    rec = cache.rec
    nan = jnp.nan
    ch = rec.pend_x.shape[-1] // rec.step_rows
    rec = dataclasses.replace(
        rec, state=rec.state.at[:, 1].set(nan), conv=rec.conv.at[:, 1].set(nan),
        pend_x=rec.pend_x.at[:, 1].set(nan).at[:, 0, 2 * ch:].set(nan),
        **{f: getattr(rec, f).at[:, 1].set(nan).at[:, 0, 2:].set(nan)
           for f in ("pend_k", "pend_v", "pend_b", "pend_g")},
        pend_n=rec.pend_n.at[1].set(3))
    cache = dataclasses.replace(cache, rec=rec)
    # slot 1 admitted beside slot 0's decode row, then both decode
    chunk = jnp.zeros((32,), jnp.int32).at[:20].set(jnp.asarray(TOKENS[:20]))
    cl, dl, cache = oh.mixed_step(
        params, CFG, chunk, jnp.int32(0), jnp.int32(20), jnp.int32(1),
        rows[1], jnp.asarray([TOKENS[42], 0]), cache, only0)
    assert np.abs(np.asarray(dl[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[19]).max() < TOL
    lg, cache = oh.decode_step(
        params, CFG, jnp.asarray([TOKENS[43], TOKENS[20]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[43]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[20]).max() < TOL
    assert bool(jnp.isfinite(cache.rec.state).all())


# -- the allocator's snapshots -----------------------------------------------


def _chain(alloc, ids):
    return alloc.chain_keys(ids, n_pages=len(ids) // alloc.page_size)


def test_a_match_is_cut_to_the_deepest_boundary_with_a_snapshot():
    alloc = PageAllocator(16, 4, 8, cache_pages=-1, snapshots=3)
    ids = list(range(18))
    alloc.alloc(0, 18)
    keys = _chain(alloc, ids)
    assert alloc.snapshot_entries([keys[1], keys[3]]) == [0, 1]
    alloc.free(0, ids)                      # four full pages registered
    assert alloc.match_prefix(1, ids) == 16 and alloc.state_match(1) == (16, 16, 1)
    alloc.alloc(1, 18)
    alloc.free(1)
    # another question behind three shared pages: the pages match to 12,
    # the deepest snapshot under that stands at 8
    other = ids[:12] + [99] * 6
    assert alloc.match_prefix(2, other) == 8
    assert alloc.state_match(2) == (12, 8, 0)
    assert len(alloc._owned[2]) == 2        # the third page was given back
    alloc.alloc(2, 18)
    alloc.free(2)
    # no snapshot at all: pages match, the admission is cold
    bare = PageAllocator(16, 4, 8, cache_pages=-1, snapshots=3)
    bare.alloc(0, 18)
    bare.free(0, ids)
    assert bare.match_prefix(1, ids) == 0 and bare.state_match(1) == (16, 0, -1)
    assert bare._owned[1] == []


def test_snapshots_leave_with_their_page_and_by_age():
    alloc = PageAllocator(4, 4, 4, cache_pages=-1, snapshots=2)
    ids = list(range(8))
    alloc.alloc(0, 8)
    k = _chain(alloc, ids)
    assert alloc.snapshot_entries([k[0], k[1]]) == [0, 1]
    assert alloc.snapshot_entries([k[1]]) == [-1]          # held already
    alloc.free(0, ids)
    # a third boundary takes the entry of the least recently used (k[0])
    assert alloc.snapshot_entries([b"another boundary"]) == [0]
    assert k[0] not in alloc._snap_by_key and alloc.snapshots_used == 2
    # the pool runs dry: cached pages are evicted, k[1]'s snapshot with its page
    alloc.alloc(1, 16)
    assert k[1] not in alloc._snap_by_key and alloc.snapshots_used == 1


# -- the engine ---------------------------------------------------------------


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    kw = {"max_slots": 2, **kw}
    return InferenceEngine(EngineConfig(
        model="tiny-olmo-hybrid", dtype="float32", page_size=PS,
        num_pages=48, max_pages_per_slot=12, prefill_buckets=(32, 128),
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


def _state_counts(outcome=None, event=None):
    from gridllm_tpu.obs import default_registry

    reg = default_registry()
    if outcome:
        return reg.get("gridllm_state_prefix_total").value(
            model="tiny-olmo-hybrid", outcome=outcome)
    return reg.get("gridllm_state_snapshots_total").value(
        model="tiny-olmo-hybrid", event=event)


@pytest.mark.parametrize("doc_len", [99, 107])
def test_a_reasked_prefix_is_admitted_from_pages_and_a_snapshot(
        cold_engine, doc_len):
    """Shared prefixes (BOS + document) of 100 and 108 tokens: L mod 16 on
    both sides of 8 with a 8-byte question, so the first asker's own last
    page boundary is the match's (96) or the one after (112). Either way
    the re-ask restores at 96 and says what a cold admission says."""
    from gridllm_tpu.engine.engine import _SEED_LAUNCHES

    eng = _engine()
    doc = (WORDS * 2)[:doc_len]
    hits = _state_counts(outcome="hit")
    seeds = _SEED_LAUNCHES.value(model="tiny-olmo-hybrid")
    first = _ask(eng, "a", doc + " one two")
    again = _ask(eng, "b", doc + " six ten")
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert _state_counts(outcome="hit") == hits + 1
    # the seed stage's jitted calls: admit_seed twice, the one restore
    assert _SEED_LAUNCHES.value(model="tiny-olmo-hybrid") - seeds == 3
    assert again.token_ids == _ask(cold_engine, "c", doc + " six ten").token_ids
    assert first.token_ids == _ask(cold_engine, "d", doc + " one two").token_ids


def test_an_evicted_snapshot_degrades_to_a_cold_prefill(cold_engine):
    """The pages are found, the snapshot is gone: the whole prompt runs
    again (`miss`, its tokens counted as replayed) and says the same."""
    eng = _engine()
    doc = (WORDS * 2)[:99]
    _ask(eng, "a", doc + " one two")
    with eng._alloc_lock:
        for key in list(eng.alloc._snap_by_key):
            eng.alloc._drop_snapshot(key)
    misses = _state_counts(outcome="miss")
    again = _ask(eng, "b", doc + " six ten")
    assert again.cached_tokens == 0
    assert _state_counts(outcome="miss") == misses + 1
    assert again.token_ids == _ask(cold_engine, "c", doc + " six ten").token_ids
    # that admission saved the state where its match had ended: the next hits
    third = _ask(eng, "d", doc + " and how")
    assert third.cached_tokens == 96
    assert third.token_ids == _ask(cold_engine, "e", doc + " and how").token_ids


def test_a_third_boundary_in_one_launch_is_never_registered_unwritten(cold_engine):
    """A launch hands back two states. A 62-token prompt whose first page
    is cached without a snapshot plans 16 (where its match ended), 48 and
    32, all inside one 64-wide launch: the third is not planned, so no
    snapshot entry stands that no launch wrote, and a re-ask whose pages
    match to 32 restores at 16 (`short`) and says what a cold admission
    says (REVIEW of PR 42: it restored the unwritten entry as a `hit`)."""
    eng = _engine()
    system = WORDS[:15]
    _ask(eng, "a", system + " asked first, briefly")
    with eng._alloc_lock:
        for key in list(eng.alloc._snap_by_key):
            eng.alloc._drop_snapshot(key)
    saved = _state_counts(event="saved")
    body = system + WORDS[40:86]
    assert len(body) == 61
    first = _ask(eng, "b", body)
    assert first.cached_tokens == 0
    assert _state_counts(event="saved") == saved + 2
    short = _state_counts(outcome="short")
    again = _ask(eng, "c", body[:40] + " and then another end")
    assert again.cached_tokens == 16
    assert _state_counts(outcome="short") == short + 1
    assert again.token_ids == _ask(
        cold_engine, "d", body[:40] + " and then another end").token_ids
    assert first.token_ids == _ask(cold_engine, "e", body).token_ids


def test_a_slot_reused_after_a_longer_occupant_starts_clean(cold_engine):
    eng = _engine(max_slots=1, prefix_cache=False)
    _ask(eng, "long", (WORDS * 2)[:150], n=12)
    short = _ask(eng, "short", "a short one")
    assert short.token_ids == _ask(cold_engine, "c", "a short one").token_ids


def test_the_engine_accounts_for_the_state():
    from gridllm_tpu.obs import default_registry

    eng = _engine()
    eng.prewarm()
    shape = eng.batch_state()["shape"]
    assert (shape["cacheRow"], shape["attnForm"]) == ("kv+state",
                                                      "per_head+delta")
    assert eng.cache.k.shape[0] == CFG.cache_layers == 2
    rec = eng.cache.rec
    assert rec.state.shape == (6, 2, 16, 128) and rec.step_rows == 5
    assert rec.snap_state.shape[1] == 8        # SNAPSHOTS_PER_SLOT x 2 slots
    mem = eng.memory_arrays()
    assert any(a is rec.snap_state for a in mem["kv"])
    assert mem["alloc"]["cacheRow"] == "kv+state"
    assert mem["alloc"]["stateBytes"]["slots"] == rec.slot_nbytes
    assert mem["alloc"]["rowBytes"] == 2 * CFG.num_kv_heads * CFG.head_dim_ * 4
    reg = default_registry()
    assert reg.get("gridllm_state_bytes").value(
        model="tiny-olmo-hybrid", kind="snapshot") == rec.snap_nbytes
    assert reg.get("gridllm_state_snapshot_pool_capacity").value(
        model="tiny-olmo-hybrid") == 8
    assert not eng.kv_transfer_supported()
    assert eng.export_prefix_pages(list(range(40))) is None
    assert eng.park_to_host(list(range(40))) == 0


@pytest.mark.parametrize("refused,message", [
    ({"kv_int8": True}, "int8 KV pool is not served beside a recurrent"),
    ({"kv_host_bytes": 1 << 20}, "park_to_host"),
])
def test_int8_pages_and_the_host_tier_are_refused(refused, message):
    with pytest.raises(ValueError, match=message):
        _engine(**refused)


def test_a_mesh_and_a_tree_of_drafts_are_refused(params):
    with pytest.raises(ValueError, match="one device only"):
        oh.validate_mesh(CFG, object())
    with pytest.raises(NotImplementedError, match="tree verification"):
        oh.verify_step(params, CFG, jnp.zeros((2, 5), jnp.int32), _cache(),
                       jnp.asarray([True, False]), tree_pos=jnp.arange(5))


def test_hf_names_assemble_the_two_trees(params):
    """`from_getter` on tensors under the published names (the linear
    layers' three convolutions apart, [channels, 1, K]) gives this
    program's tree."""
    per = CFG.layer_period
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    h, dk = CFG.linear_num_heads, CFG.linear_key_head_dim
    for i in range(CFG.num_layers):
        pi, j = divmod(i, per)
        linear = j < per - 1
        tree = params["linear"][j] if linear else params["full"]
        for leaf, (tmpl, tr) in (oh.LINEAR_HF_MAP if linear
                                 else oh.FULL_HF_MAP).items():
            a = np.asarray(tree[leaf][pi])
            sd[tmpl.format(i)] = a.T if tr else a
        if linear:
            w = np.asarray(tree["conv_w"][pi])               # [K, C]
            for name, part in zip(oh._CONVS, np.split(
                    w, [h * dk, 2 * h * dk], axis=1)):
                sd[name.format(i)] = part.T[:, None, :]
    got = oh.from_getter(CFG, sd.__getitem__, jnp.float32)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        assert a.shape == b.shape and bool((a == b).all())
    assert jax.tree.structure(got) == jax.tree.structure(params)


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


def test_thirty_heads_are_stored_as_thirty_two_and_compile_for_the_chip(one_chip):
    """Mosaic slices a page's head axis in sublane tiles of eight and
    refuses 30 (PR 42, call 2), so the pool holds 32 heads, two of them
    zero; at that geometry a 1,024-row chunk's K and V (16 MiB) stay in
    VMEM and the ragged kernel and both write kernels compile."""
    from gridllm_tpu.ops.attention import ragged_paged_attention
    from gridllm_tpu.ops.kvcache import write_decode_all, write_prefill_all

    full = get_config("olmo-hybrid:7b")
    assert (full.num_kv_heads, full.cache_heads) == (30, 32)
    assert (CFG.num_kv_heads, CFG.cache_heads) == (4, 4)
    assert get_config("mistral:7b").cache_heads == 8
    # a dense family writes num_kv_heads heads: its pool has as many
    assert dataclasses.replace(
        get_config("mistral:7b"), num_heads=24, num_kv_heads=12).cache_heads == 12

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, s, c, h = jnp.int32, 16, 1024, 32
    pool = real((5, 300, 128, h, 128))
    jax.jit(lambda k, v, li, kw: ragged_paged_attention(
        k, v, 128, layer=li, use_pallas=True, **kw)).lower(
            pool, pool, real((), i32), dict(
                q_chunk=real((1, c, h, 128)), chunk_row=real((64,), i32),
                chunk_start=real((), i32), chunk_total=real((), i32),
                k_chunk=real((c, h, 128)), v_chunk=real((c, h, 128)),
                q_group=real((s, 1, h, 128)), page_table=real((s, 64), i32),
                group_lengths=real((s,), i32), k_group=real((s, 1, h, 128)),
                v_group=real((s, 1, h, 128)))).compile()
    new = real((5, c, h, 128))
    jax.jit(lambda k, v, kn, vn, row, a, b: write_prefill_all(
        k, v, kn, vn, row, a, b, 128, use_pallas=True)).lower(
            pool, pool, new, new, real((64,), i32), real((), i32),
            real((), i32)).compile()
    rows = real((5, s, h, 128))
    jax.jit(lambda k, v, kn, vn, t, p, a: write_decode_all(
        k, v, kn, vn, t, p, a, 128, use_pallas=True)).lower(
            pool, pool, rows, rows, real((s, 64), i32), real((s,), i32),
            real((s,), jnp.bool_)).compile()


def test_the_delta_rule_kernels_compile_for_the_chip(one_chip):
    """Mosaic takes both kernels at Olmo-Hybrid-7B's geometry (30 heads,
    keys of 96, values of 192 packed two a lane block of 384), and the
    step kernel updates the 531 MB of states in place."""
    def real(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    h, dk, dv, t, s = 30, 96, 192, 1024, 16
    assert la.head_pack(dv, h) == 2
    jax.jit(lambda st, q, k, v, b, g, keep: la.gdn_chunk(
        st, q, k, v, b, g, keep, 64, use_pallas=True)).lower(
            real((dk, h * dv)), real((t, h, dk)), real((t, h, dk)),
            real((t, h, dv)), real((t, h)), real((t, h)),
            real((2,), jnp.int32)).compile()
    rows = [real((s, 5, h, dk)), real((s, 5, h, dk)), real((s, 5, h, dv)),
            real((s, 5, h)), real((s, 5, h))]
    step = jax.jit(lambda st, li, pend, n, new, live: la.gdn_step(
        st, li, pend, n, *new, live, use_pallas=True),
        donate_argnums=(0,)).lower(
            real((15, s, dk, h * dv)), real((), jnp.int32), tuple(rows[1:]),
            real((s,), jnp.int32), tuple(rows), real((s,), jnp.bool_)).compile()
    assert step.memory_analysis().alias_size_in_bytes >= 15 * s * dk * h * dv * 4


def test_draftless_launches_back_to_back_leave_the_serial_state(
        cold_engine, monkeypatch):
    """The runner running ahead (nothing proposed; the window cut to two
    launches): verify launches are dispatched behind verify launches with
    no fetch between, each committing one row of the recurrent state, and
    the tokens are the serial steps'. A later asker of the prefix restores
    the snapshot beside the pages and says what a cold admission says."""
    from tests.helpers import turns_running_ahead

    doc = (WORDS * 2)[:107]
    (first, again), behind = turns_running_ahead(
        _engine(), monkeypatch,
        [("a", doc + " one two", 24), ("b", doc + " six ten", 24)])
    assert sum(behind) >= 20          # a verify launch behind a verify launch
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert first.token_ids == _ask(cold_engine, "c", doc + " one two", 24).token_ids
    assert again.token_ids == _ask(cold_engine, "d", doc + " six ten", 24).token_ids
