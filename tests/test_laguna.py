"""Laguna (attention layers of two shapes stacked by kind, a sigmoid gate
a head, a sigmoid router with a shared expert, and window layers that
hold a RING of rows a slot beside the pages) against its plain float32
reference, benchmark/reference/laguna_f32.py, on seeded tiny-laguna
weights. Logits, not tokens. What decides whether the design is sound is
held here: every phase through both caches equals the reference past the
window and through several wraps of the ring, a ring never holds more
than a window, a launch and a page, and a re-asked prefix is admitted
from pages AND a snapshot of the rings (or computed again: never from a
ring that holds another context's rows)."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import laguna as lg
from gridllm_tpu.models import mixtral
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops.kvcache import PageAllocator, PagedKVCache, rollback_to_length

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-laguna")
# float32 on both sides in another operation order: rounding only. The
# largest difference seen is 4e-6 (logits up to 0.6); each broken
# mechanism reads 0.3 to 0.7
TOL = 1e-4
PS = 16                                  # page size of the test pools
CHUNK = 32                               # rows of a chunk launch


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/laguna_f32.py", "laguna_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return lg.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


# 200 tokens: 25 windows of 8, and three wraps of a 64-row ring
TOKENS = np.random.default_rng(0).integers(0, CFG.vocab_size, 200)


@pytest.fixture(scope="module")
def ref_logits(params):
    return np.asarray(REF.logits(params, SIZES, list(TOKENS)))


def _cache(slots=2, snapshots=4, launch=CHUNK):
    c = PagedKVCache.create(
        CFG.cache_layers, num_pages=40, page_size=PS,
        num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim_,
        max_slots=slots, max_pages_per_slot=16, dtype=jnp.float32)
    return dataclasses.replace(c, win=lg.new_ring(
        CFG, slots, launch, snapshots, PS, CFG.head_dim_, jnp.float32))


def _rows(n_tokens=256):
    alloc = PageAllocator(40, PS, 16)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def _chunks(params, toks, cache, slot, row, width=CHUNK, start=0,
            state_io=None):
    """A prompt admitted as the engine admits it, through `mixed_step`
    with no active slot, `width` rows a launch; every launch's last
    logits."""
    out = []
    idle = jnp.zeros(cache.lengths.shape, jnp.int32)
    for s0 in range(start, len(toks), width):
        part = toks[s0:s0 + width]
        chunk = jnp.zeros((width,), jnp.int32).at[:len(part)].set(
            jnp.asarray(part))
        logits, _, cache = lg.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0, state_io=state_io)
        out.append(np.asarray(logits))
    return out, cache


# -- the configuration ---------------------------------------------------------

_KINDS = ["full_attention"] + ["sliding_attention"] * 3
PUBLISHED = {       # poolside/Laguna-XS.2 config.json
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": _KINDS * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


def test_published_keys_read_as_the_registry_entry():
    got = _config_from_hf_dict("laguna-xs2:33b", PUBLISHED, "config.json")
    assert got == get_config("laguna-xs2:33b")
    assert (got.ring_layers, got.cache_layers) == (30, 10)
    assert got.cache_kinds == ("kv", "window")
    assert lg.layout(got) == (1, 4, 9, 3)
    # a window, a launch of 512 and a page: nine pages of 128
    assert got.ring_pages(512, 128) * 128 == 512 + 512 + 128
    cut = _config_from_hf_dict(
        "cut", {**PUBLISHED, "num_hidden_layers": 5}, "config.json")
    assert (cut.ring_layers, cut.cache_layers) == (3, 2)
    assert lg.layout(cut) == (1, 4, 1, 0)
    assert cut.heads_layout == (48, 64, 64, 64, 48)


@pytest.mark.parametrize("key,value,named", [
    ("moe_apply_router_weight_on_input", True, "moe_apply_router_weight"),
    ("attention_bias", True, "attention_bias"),
    ("shared_expert_intermediate_size", 768, "shared_expert"),
    ("mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 38, "mlp_layer"),
    ("layer_types", ["linear_attention"] * 40, "layer_types"),
])
def test_what_is_not_built_is_refused_by_name(key, value, named):
    with pytest.raises(ValueError, match=named):
        _config_from_hf_dict("x", {**PUBLISHED, key: value}, "config.json")


def test_another_attention_factor_or_window_rope_is_refused():
    rp = json.loads(json.dumps(PUBLISHED["rope_parameters"]))
    rp["full_attention"]["attention_factor"] = 1.2
    with pytest.raises(ValueError, match="full_attention"):
        _config_from_hf_dict("x", {**PUBLISHED, "rope_parameters": rp}, "c")
    rp = json.loads(json.dumps(PUBLISHED["rope_parameters"]))
    rp["sliding_attention"]["partial_rotary_factor"] = 0.5
    with pytest.raises(ValueError, match="sliding_attention"):
        _config_from_hf_dict("x", {**PUBLISHED, "rope_parameters": rp}, "c")


def test_the_configuration_file_reads_back_as_its_base(monkeypatch):
    """benchmark/configs/laguna-xs2-L5.json with `reduced` put back is the
    registry's laguna-xs2:33b; as run it is layer 0 and one period."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    lw = _load("benchmark/launch_worker.py", "launch_worker_for_laguna")
    with open(os.path.join(ROOT, "benchmark/configs/laguna-xs2-L5.json")) as f:
        spec = json.load(f)
    cfg = lw.model_config(spec, "laguna-xs2-L5", rehearse=False)
    assert (cfg.num_layers, cfg.cache_layers, cfg.ring_layers) == (5, 2, 3)
    base = get_config("laguna-xs2:33b")
    assert dataclasses.replace(
        cfg, name=base.name, num_layers=40, window_layout=base.window_layout,
        heads_layout=base.heads_layout) == base
    assert list(spec["reduced"]) == ["num_hidden_layers"]
    assert lw.model_config(spec, "x", rehearse=True).family == "laguna"


def test_only_layers_stacked_by_kind_hold_rings():
    """The rule of ModelConfig.ring_layers on every registered family: a
    window layer holds a ring where the layers are stacked by kind."""
    from gridllm_tpu.models.configs import REGISTRY

    ringed = {n for n, c in REGISTRY.items() if c.ring_layers}
    assert ringed == {"laguna-xs2:33b", "tiny-laguna"}
    for name in ("smallthinker:21b", "gemma2:9b", "tiny-mistral"):
        c = get_config(name)
        assert c.cache_kinds == ("kv",) and c.cache_layers == c.num_layers


# -- the phases against the reference -----------------------------------------


def test_forward_matches_the_reference(params, ref_logits):
    got = np.asarray(lg.forward(params, CFG, jnp.asarray(TOKENS)[None]))[0]
    assert np.abs(got - ref_logits).max() < TOL


@pytest.mark.parametrize("broken", [
    {"skip_layer": 0}, {"skip_layer": 2}, {"window": False}, {"gate": False},
    {"router": "softmax"}, {"rope": "plain"},
    {"round_to": "float8_e4m3fn"}])
def test_a_reference_broken_in_one_mechanism_fails(params, ref_logits, broken):
    wrong = np.asarray(REF.logits(params, SIZES, list(TOKENS), **broken))
    assert np.abs(wrong - ref_logits).max() > 100 * TOL


def test_prefill_then_decode_through_both_caches(params, ref_logits):
    """A prompt of one launch, then token by token past the window and
    twice round the ring (64 rows): every step's logits."""
    rows = _rows()
    (logits,), cache = _chunks(
        params, list(TOKENS[:20]), _cache(), 0, rows[0])
    assert np.abs(logits - ref_logits[19]).max() < TOL
    active = jnp.asarray([True, False])
    step = jax.jit(lambda tok, cache: lg.decode_step(
        params, CFG, tok, cache, active))
    for p in range(20, 180):
        logits, cache = step(jnp.asarray([TOKENS[p], 0]), cache)
        assert np.abs(np.asarray(logits)[0] - ref_logits[p]).max() < TOL, p
    assert int(cache.lengths[0]) == 180


def test_chunk_launches_wrap_the_ring_and_equal_the_reference(params, ref_logits):
    """200 tokens in launches of 32 through a ring of 64 rows: the ring
    wraps three times and every launch's last logits are the
    reference's."""
    cache = _cache()
    assert cache.win.ring_pages * PS == 64
    got, cache = _chunks(params, list(TOKENS), cache, 0, _rows()[0])
    for i, logits in enumerate(got):
        p = min((i + 1) * CHUNK, len(TOKENS)) - 1
        assert np.abs(logits - ref_logits[p]).max() < TOL, p


def test_a_ring_holds_a_window_a_launch_and_a_page():
    """Whatever the context: the rings' rows a slot are the configuration's
    numbers, and the page pool holds the dense and global layers only."""
    cache = _cache(slots=3, snapshots=5, launch=CHUNK)
    win = cache.win
    assert win.ring_pages * PS <= CFG.sliding_window + CHUNK + 2 * PS
    assert win.k.shape == (3, 3 * win.ring_pages + 5 * win.snap_pages, PS,
                           CFG.num_kv_heads, CFG.head_dim_)
    assert cache.k.shape[0] == CFG.cache_layers == 2
    table = np.asarray(win.table(16))
    assert table.shape == (3, 16) and table.max() == 3 * win.ring_pages - 1
    assert (table[1] == win.ring_pages + np.arange(16) % win.ring_pages).all()
    assert (np.asarray(win.row(jnp.int32(2), 16)) == table[2]).all()


@pytest.mark.parametrize("accepted", [0, 2, 4])
def test_verify_then_rollback_equals_sequential_decode(params, ref_logits, accepted):
    """A verify launch of five rows at position 70 (past a wrap), of which
    `accepted` drafts are kept: its logits are the reference's, and after
    the rollback the next step reads what sequential decode would (the
    rejected rows of the ring are overwritten in place)."""
    rows = _rows()
    _, cache = _chunks(params, list(TOKENS[:70]), _cache(), 0, rows[0])
    active = jnp.asarray([True, False])
    cand = np.array(TOKENS[70:75])
    cand[accepted + 1:] = 3                  # drafts that will be rejected
    logits, cache = lg.verify_step(
        params, CFG, jnp.asarray([cand, np.zeros(5, int)]), cache, active)
    for j in range(accepted + 1):
        assert np.abs(np.asarray(logits)[0, j] - ref_logits[70 + j]).max() < TOL
    cache = rollback_to_length(cache, cache.lengths + jnp.asarray(
        [accepted + 1, 0]))
    for p in range(71 + accepted, 90):
        logits, cache = lg.decode_step(
            params, CFG, jnp.asarray([TOKENS[p], 0]), cache, active)
        assert np.abs(np.asarray(logits)[0] - ref_logits[p]).max() < TOL, p


def test_the_mixed_step_serves_a_chunk_beside_running_slots(params, ref_logits):
    """Slot 1 decodes at position 100 while slot 0's third chunk runs in
    the same launch: both read the reference's logits."""
    rows = _rows()
    _, cache = _chunks(params, list(TOKENS[:64]), _cache(), 0, rows[0])
    _, cache = _chunks(params, list(TOKENS[:100]), cache, 1, rows[1])
    chunk = jnp.asarray(TOKENS[64:96])
    cl, dl, cache = lg.mixed_step(
        params, CFG, chunk, jnp.int32(64), jnp.int32(32), jnp.int32(0),
        rows[0], jnp.asarray([0, TOKENS[100]]), cache,
        jnp.asarray([False, True]))
    assert np.abs(np.asarray(cl) - ref_logits[95]).max() < TOL
    assert np.abs(np.asarray(dl)[1] - ref_logits[100]).max() < TOL
    assert [int(n) for n in cache.lengths] == [96, 101]
    logits, cache = lg.decode_step(
        params, CFG, jnp.asarray([TOKENS[96], TOKENS[101]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(logits)[0] - ref_logits[96]).max() < TOL
    assert np.abs(np.asarray(logits)[1] - ref_logits[101]).max() < TOL


def test_a_chunk_launch_saves_and_a_restore_resumes(params, ref_logits):
    """Slot 0's launches save the rings' last page at 96 and 128; slot 1,
    handed the pool pages up to 96 and the snapshot, runs the rest and
    reads the reference's logits, as it would NOT from an empty ring."""
    rows = _rows()
    io = (jnp.asarray([96, 128]), jnp.asarray([1, 3]))
    _, cache = _chunks(params, list(TOKENS[:140]), _cache(), 0, rows[0],
                       state_io=io)
    shared = jnp.concatenate([rows[0][:6], rows[1][6:]])
    cold = _chunks(params, list(TOKENS[:140]), cache, 1, shared, start=96)[0]
    assert np.abs(cold[-1] - ref_logits[139]).max() > 10 * TOL
    warm = lg.restore_snapshot(cache, jnp.int32(1), jnp.int32(1), jnp.int32(96))
    got = _chunks(params, list(TOKENS[:140]), warm, 1, shared, start=96)[0]
    assert np.abs(got[-1] - ref_logits[139]).max() < TOL
    warm = lg.restore_snapshot(cache, jnp.int32(1), jnp.int32(3), jnp.int32(128))
    got = _chunks(params, list(TOKENS[:140]), warm, 1,
                  jnp.concatenate([rows[0][:8], rows[1][8:]]), start=128)[0]
    assert np.abs(got[-1] - ref_logits[139]).max() < TOL


def test_junk_left_by_an_idle_slot_reaches_nothing(params, ref_logits):
    """NaNs in every ring and pool page the live slot does not own."""
    rows = _rows()
    _, cache = _chunks(params, list(TOKENS[:40]), _cache(), 0, rows[0])
    r = cache.win.ring_pages
    win = dataclasses.replace(
        cache.win, k=cache.win.k.at[:, r:].set(jnp.nan),
        v=cache.win.v.at[:, r:].set(jnp.nan))
    mine = np.asarray(rows[0])
    others = np.setdiff1d(np.arange(40), mine[mine >= 0])
    cache = dataclasses.replace(
        cache, win=win, k=cache.k.at[:, others].set(jnp.nan),
        v=cache.v.at[:, others].set(jnp.nan))
    logits, cache = lg.decode_step(
        params, CFG, jnp.asarray([TOKENS[40], 0]), cache,
        jnp.asarray([True, False]))
    assert np.abs(np.asarray(logits)[0] - ref_logits[40]).max() < TOL


# -- the router and the expert layer's forms ------------------------------------


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_route_scores_normalises_and_scales(score, norm, scale):
    cfg = dataclasses.replace(
        get_config("tiny-mixtral"), num_experts=8, experts_per_token=3,
        router_score=score, norm_topk_prob=norm, routed_scaling_factor=scale)
    rng = np.random.default_rng(3)
    r = rng.standard_normal((5, 64)).astype(np.float32)
    w = rng.standard_normal((64, 8)).astype(np.float32)
    top_w, top_i = mixtral._route(cfg, {"router": jnp.asarray(w)}, jnp.asarray(r))
    s = r @ w
    scores = (1 / (1 + np.exp(-s)) if score == "sigmoid"
              else np.exp(s) / np.exp(s).sum(-1, keepdims=True))
    idx = np.argsort(-scores, axis=-1)[:, :3]
    want = np.take_along_axis(scores, idx, axis=-1)
    if norm:
        want = want / want.sum(-1, keepdims=True)
    assert (np.asarray(top_i) == idx).all()
    np.testing.assert_allclose(np.asarray(top_w), want * scale, rtol=1e-5)


@pytest.mark.parametrize("name,rows,form", [
    # one chip with kernels: the grouped kernel at every shape, its sorted
    # regime from the chip's ridge of 240 rows (PR 58: read faster than
    # either other form at every accepted shape)
    ("smallthinker:21b", 80, "grouped"), ("smallthinker:21b", 1040, "grouped_sorted"),
    ("deepseek-v2-lite:16b", 80, "grouped"), ("deepseek-v2-lite:16b", 528, "grouped_sorted"),
    ("mixtral:8x7b", 1040, "grouped_sorted"),
    ("laguna-xs2:33b", 16, "grouped"), ("laguna-xs2:33b", 80, "grouped"),
    ("laguna-xs2:33b", 528, "grouped_sorted"),
    ("laguna-xs2:33b", 239, "grouped"), ("laguna-xs2:33b", 240, "grouped_sorted"),
    ("mixtral:8x7b", 80, "grouped"), ("kimi-linear:48b-ep4", 80, "grouped"),
])
def test_the_expert_form_is_a_rule_of_the_shape(name, rows, form, monkeypatch):
    cfg = get_config(name)
    # XLA's ragged_dot dispatch is nobody's choice on one chip by itself
    assert not mixtral._use_ragged(rows, False, backend="tpu")
    assert mixtral.expert_form(cfg, rows, backend="tpu") == form
    assert mixtral.expert_form(dataclasses.replace(cfg, use_pallas=False),
                               rows, backend="tpu") == "all_experts"
    # off the chip the all-experts form, whatever the shape
    assert mixtral.expert_form(cfg, rows) == "all_experts"
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "on")
    assert mixtral.expert_form(cfg, rows) == "sorted"
    assert mixtral.expert_form(cfg, rows, backend="tpu") == "sorted"


def test_both_forms_of_the_expert_layer_agree(params, interpreted_kernels):
    lp = jax.tree.map(lambda a: a[0], params["glob"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    jnp.float32)
    top_w, top_i = mixtral._route(CFG, lp, x)
    dense = mixtral._moe_mlp_dense(CFG, lp, x, top_w, top_i)
    ragged = mixtral._moe_mlp_ragged(CFG, lp, x, top_w, top_i)
    assert np.abs(np.asarray(dense) - np.asarray(ragged)).max() < 1e-5
    # and the third (PR 53): the touched experts alone, by the kernel
    live = jnp.arange(40) < 25
    grouped = mixtral._moe_mlp_grouped(CFG, lp, x, top_w, top_i, live)
    assert np.abs(np.asarray(dense) - np.asarray(grouped))[:25].max() < 1e-5
    assert not np.asarray(grouped)[25:].any()


# -- the engine ---------------------------------------------------------------


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    kw = {"max_slots": 2, **kw}
    return InferenceEngine(EngineConfig(
        model="tiny-laguna", dtype="float32", page_size=PS,
        num_pages=48, max_pages_per_slot=16, prefill_buckets=(32, 128),
        prefill_chunk=32, prefill_chunk_narrow=32, seed=0, **kw))


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
            model="tiny-laguna", outcome=outcome)
    return reg.get("gridllm_state_snapshots_total").value(
        model="tiny-laguna", event=event)


def test_the_engine_serves_the_reference_past_several_wraps(params):
    """Greedy tokens of a 150-token prompt and 40 more, speculation on,
    through a ring of 64 rows: each is the reference's argmax under the
    default repeat penalty (the engine's weights are PRNGKey(0)'s)."""
    eng = _engine(prefix_cache=False)
    got = _ask(eng, "a", (WORDS * 2)[:149], n=40)
    ids, n_prompt = list(got.context), got.prompt_eval_count
    assert len(ids) == n_prompt + 40 and ids[n_prompt:] == got.token_ids
    weights = lg.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    ref = REF.logits(weights, SIZES, ids)
    short, _ = REF.margins(ref, ids, n_prompt, 1.1, 64, tie=0)
    assert float(np.max(short)) < TOL


@pytest.mark.parametrize("doc_len", [99, 107])
def test_a_reasked_prefix_is_admitted_from_pages_and_a_snapshot(
        cold_engine, doc_len):
    eng = _engine()
    doc = (WORDS * 2)[:doc_len]
    hits = _state_counts(outcome="hit")
    first = _ask(eng, "a", doc + " one two")
    again = _ask(eng, "b", doc + " six ten")
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert _state_counts(outcome="hit") == hits + 1
    assert again.token_ids == _ask(cold_engine, "c", doc + " six ten").token_ids
    assert first.token_ids == _ask(cold_engine, "d", doc + " one two").token_ids


def test_an_evicted_snapshot_degrades_to_a_cold_prefill(cold_engine):
    """The pages are found, the rings' snapshot is gone: the whole prompt
    runs again (`miss`) and says the same; that admission saves where its
    match had ended, so the next asker hits."""
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
    third = _ask(eng, "d", doc + " and how")
    assert third.cached_tokens == 96
    assert third.token_ids == _ask(cold_engine, "e", doc + " and how").token_ids


def test_a_slot_reused_after_a_longer_occupant_starts_clean(cold_engine):
    eng = _engine(max_slots=1, prefix_cache=False)
    _ask(eng, "long", (WORDS * 2)[:150], n=12)
    short = _ask(eng, "short", "a short one")
    assert short.token_ids == _ask(cold_engine, "c", "a short one").token_ids


def test_the_engine_accounts_for_the_rings():
    from gridllm_tpu.obs import default_registry

    eng = _engine()
    eng.prewarm()
    shape = eng.batch_state()["shape"]
    assert (shape["cacheRow"], shape["attnForm"]) == ("kv+window",
                                                      "per_head+ring")
    assert eng.cache.k.shape[0] == CFG.cache_layers == 2
    win = eng.cache.win
    # a window of 8, a launch of 32, a page of 16: four pages a slot
    assert (win.ring_pages, win.snap_pages) == (4, 1)
    assert win.k.shape[:2] == (3, 2 * 4 + 8)   # SNAPSHOTS_PER_SLOT x 2 slots
    mem = eng.memory_arrays()
    assert any(a is win.k for a in mem["kv"])
    assert mem["alloc"]["cacheRow"] == "kv+window"
    assert mem["alloc"]["stateBytes"] == {
        "slots": win.slot_nbytes, "snapshots": win.snap_nbytes,
        "snapshotsUsed": eng.alloc.snapshots_used, "snapshotsCapacity": 8}
    reg = default_registry()
    assert reg.get("gridllm_state_bytes").value(
        model="tiny-laguna", kind="slot") == win.slot_nbytes
    _ask(eng, "a", (WORDS * 2)[:150], n=4)
    rows = reg.get("gridllm_window_rows")
    assert rows.value(model="tiny-laguna", held="ring") == 0   # none live now
    assert not eng.kv_transfer_supported()
    assert eng.export_prefix_pages(list(range(40))) is None


@pytest.mark.parametrize("chars,ring,table", [
    (150, 64, 160),   # ten pages owned, the ring's four held
    (20, 32, 32),     # two pages owned: the ring has not wrapped yet
])
def test_the_rings_hold_fewer_rows_than_one_table_would(chars, ring, table):
    """While a request is live the gauges read the rows its window layers
    hold in their ring against the rows of the pages it owns (not of its
    table row's padded width)."""
    from gridllm_tpu.engine import GenerationRequest
    from gridllm_tpu.obs import default_registry

    eng = _engine(prefix_cache=False)
    eng.submit(GenerationRequest(
        id="a", prompt=(WORDS * 2)[:chars],
        options={"temperature": 0.0, "num_predict": 4}))
    eng.step()
    rows = default_registry().get("gridllm_window_rows")
    assert eng.alloc.max_pages_per_slot * 16 > 160   # the row's padding
    assert rows.value(model="tiny-laguna", held="ring") == ring
    assert rows.value(model="tiny-laguna", held="table") == table
    while eng.step():
        pass
    assert rows.value(model="tiny-laguna", held="ring") == 0


@pytest.mark.parametrize("refused,message", [
    ({"kv_int8": True}, "int8 KV pool is not served beside a recurrent"),
    ({"kv_host_bytes": 1 << 20}, "park_to_host"),
])
def test_int8_pages_and_the_host_tier_are_refused(refused, message):
    with pytest.raises(ValueError, match=message):
        _engine(**refused)


def test_a_mesh_and_a_tree_of_drafts_are_refused(params):
    with pytest.raises(ValueError, match="one device only"):
        lg.validate_mesh(CFG, object())
    with pytest.raises(NotImplementedError, match="tree verification"):
        lg.verify_step(params, CFG, jnp.zeros((2, 5), jnp.int32), _cache(),
                       jnp.asarray([True, False]), tree_pos=jnp.arange(5))


def test_hf_names_assemble_the_trees_by_kind(params):
    """`from_getter` on tensors under the published names gives this
    program's tree: layer 0 dense, layers 1-3 the window places, 4 global."""
    _, per, n, _ = lg.layout(CFG)
    tensors = {}

    def put(name_map, tree, i, layer):
        for key, (tmpl, tr) in name_map.items():
            a = np.asarray(tree[key][i])
            tensors[tmpl.format(layer)] = a.T if tr else a

    put(lg.DENSE_HF_MAP, params["dense"], 0, 0)
    for pi in range(n):
        for j in range(per):
            tree = params["glob"] if j == per - 1 else params["win"][j]
            layer = 1 + pi * per + j
            put(lg.SPARSE_HF_MAP, tree, pi, layer)
            for key, proj in lg._EXPERTS.items():
                for x in range(CFG.num_experts):
                    tensors[f"model.layers.{layer}.mlp.experts.{x}.{proj}"
                            ".weight"] = np.asarray(tree[key][pi, x]).T
    tensors["model.embed_tokens.weight"] = np.asarray(params["embed"])
    tensors["model.norm.weight"] = np.asarray(params["final_norm"])
    tensors["lm_head.weight"] = np.asarray(params["lm_head"]).T
    got = lg.from_getter(CFG, tensors.__getitem__, jnp.float32)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    assert jax.tree.structure(got) == jax.tree.structure(params)


def test_the_float32_witness_holds_every_served_token(monkeypatch, capsys):
    """deploy/tpu_laguna_f32.py, the chip's second witness of the cell's
    limits, at the tiny size: the engine in float32 serves a context cold
    and again behind the prefix cache, and every served token is the
    reference's with no tie set aside."""
    import sys

    from gridllm_tpu.models.configs import REGISTRY

    was = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(sys, "argv", [
        "tpu_laguna_f32.py", "--model", "tiny-laguna", "--context", "150",
        "--page", "16", "--contexts", "1", "--out", "12"])
    try:
        assert _load("deploy/tpu_laguna_f32.py", "laguna_witness").main() == 0
    finally:
        jax.config.update("jax_default_matmul_precision", was)
        REGISTRY.pop("tiny-laguna-f32-witness", None)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    report = json.loads(last.removeprefix("WITNESS="))
    cold, cached = report["asks"]
    assert cold["cached"] == 0 and cached["cached"] == 144
    assert report["state"]["hit"] == 1
    for ask in report["asks"]:
        assert ask["tie0"]["judged"] == ask["generated"] == 12
        assert ask["tie0"]["worst"] < TOL


# -- what Mosaic accepts of the two shapes, without the chip ---------------------


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


@pytest.mark.parametrize("heads,pages,window", [
    (48, 1024, 0),                  # a global layer on the page pool
    (64, 16 * 9 + 64 * 4, 512),     # a window layer on the rings
])
def test_both_layer_shapes_compile_for_the_chip(one_chip, heads, pages, window):
    """One program runs the ragged kernel at two group sizes (6 and 8
    query heads a KV head) on two pools: Mosaic takes a mixed launch's
    regions (a chunk of 512 beside 16 decode rows), a verify launch's,
    and the write kernels through either table."""
    from gridllm_tpu.ops.attention import ragged_paged_attention
    from gridllm_tpu.ops.kvcache import write_decode_all, write_prefill_all

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, s, c, kvh, d = jnp.int32, 16, 512, 8, 128
    layers = 3 if window else 2
    pool = real((layers, pages, 128, kvh, d))
    jax.jit(lambda k, v, li, kw: ragged_paged_attention(
        k, v, 128, layer=li, use_pallas=True, window=window, **kw)).lower(
            pool, pool, real((), i32), dict(
                q_chunk=real((1, c, heads, d)), chunk_row=real((64,), i32),
                chunk_start=real((), i32), chunk_total=real((), i32),
                k_chunk=real((c, kvh, d)), v_chunk=real((c, kvh, d)),
                q_group=real((s, 1, heads, d)), page_table=real((s, 64), i32),
                group_lengths=real((s,), i32), k_group=real((s, 1, kvh, d)),
                v_group=real((s, 1, kvh, d)))).compile()
    jax.jit(lambda k, v, li, kw: ragged_paged_attention(
        k, v, 128, layer=li, use_pallas=True, window=window, **kw)).lower(
            pool, pool, real((), i32), dict(
                q_group=real((s, 5, heads, d)), page_table=real((s, 64), i32),
                group_lengths=real((s,), i32), k_group=real((s, 5, kvh, d)),
                v_group=real((s, 5, kvh, d)))).compile()
    new = real((layers, c, kvh, d))
    jax.jit(lambda k, v, kn, vn, row, a, b: write_prefill_all(
        k, v, kn, vn, row, a, b, 128, use_pallas=True)).lower(
            pool, pool, new, new, real((64,), i32), real((), i32),
            real((), i32)).compile()
    rows = real((layers, s, kvh, d))
    jax.jit(lambda k, v, kn, vn, t, p, a: write_decode_all(
        k, v, kn, vn, t, p, a, 128, use_pallas=True)).lower(
            pool, pool, rows, rows, real((s, 64), i32), real((s,), i32),
            real((s,), jnp.bool_)).compile()


def test_draftless_launches_back_to_back_leave_the_serial_rings(
        cold_engine, monkeypatch):
    """The runner running ahead (nothing proposed; the window cut to two
    launches): verify launches are dispatched behind verify launches with
    no fetch between, each committing one row of the window layers' rings
    past a wrap, and the tokens are the serial steps'. A later asker of the
    prefix restores the rings' snapshot beside the pages and says what a
    cold admission says."""
    from tests.helpers import turns_running_ahead

    doc = (WORDS * 2)[:107]
    (first, again), behind = turns_running_ahead(
        _engine(), monkeypatch,
        [("a", doc + " one two", 40), ("b", doc + " six ten", 24)])
    assert sum(behind) >= 30          # a verify launch behind a verify launch
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert first.token_ids == _ask(cold_engine, "c", doc + " one two", 40).token_ids
    assert again.token_ids == _ask(cold_engine, "d", doc + " six ten", 24).token_ids
