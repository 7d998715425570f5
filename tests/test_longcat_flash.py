"""LongCat-Flash (two latent-attention sublayers a block with a low-rank
query and two constant scales, two dense feed-forwards, a shortcut-connected
expert layer with zero-compute experts, a share of the routed experts held)
against its plain float32 reference, benchmark/reference/longcat_flash_f32.py,
on seeded tiny-longcat-flash weights: two blocks (four pool layers), experts
4-7 of 16 held beside 8 zero-compute ones, top-4. Logits, not tokens. What is
new is held here: a block that owns two layers of the one latent pool
(chunked prefill, decode, verify and a rollback, a prefix-cache admission),
where the shortcut leaves and rejoins, the zero-compute picks (no weight
read, counted apart) and the share (four shares and the zero-compute part
counted once add up to the whole layer)."""

import dataclasses
from functools import partial
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import deepseek, longcat_flash as lf, mixtral
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops.kvcache import (
    PageAllocator,
    PagedKVCache,
    rollback_to_length,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-longcat-flash")
WHOLE = dataclasses.replace(CFG, experts_held=None, experts_first=None)
# float32 on both sides in another operation order (the program reads the
# latent rows absorbed, the query folded through W_kvb's key half, and sums
# the experts in another order; the reference rebuilds K and V a head):
# rounding only. The largest difference seen is 2e-6 (logits up to 0.7);
# each broken mechanism reads 0.17 to 0.74. In bfloat16 (weights and
# activations) the program lies within BF16_TOL of the float32 reference
# at positions behind no router's tie: 8 bits of mantissa through two
# blocks read up to 0.05 there, five hundred times TOL, so a float32 run
# computed in bfloat16 fails TOL
TOL = 1e-4
BF16_TOL = 0.15
PS = 16                                  # page size of the test pools


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/longcat_flash_f32.py", "longcat_flash_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return lf.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


TOKENS = np.random.default_rng(0).integers(0, CFG.vocab_size, 96)


@pytest.fixture(scope="module")
def ref_logits(params):
    return np.asarray(REF.logits(params, SIZES, list(TOKENS)))


def _cache(dtype=jnp.float32):
    return PagedKVCache.create(
        CFG.cache_layers, num_pages=24, page_size=PS, num_kv_heads=1,
        head_dim=CFG.cache_dim, max_slots=2, max_pages_per_slot=8,
        dtype=dtype, latent=True)


def _rows(n_tokens=128):
    alloc = PageAllocator(24, PS, 8)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def _chunks(params, toks, cache, slot, row, width, start=0, cfg=CFG):
    """A prompt admitted as the engine admits it, through `mixed_step`
    with no active slot, `width` rows a launch."""
    idle = jnp.zeros(cache.lengths.shape, jnp.int32)
    for s0 in range(start, len(toks), width):
        part = toks[s0:s0 + width]
        chunk = jnp.zeros((width,), jnp.int32).at[:len(part)].set(
            jnp.asarray(part))
        logits, _, cache = lf.mixed_step(
            params, cfg, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0)
    return logits, cache


# -- the configuration -------------------------------------------------------

PUBLISHED = {       # meituan-longcat/LongCat-Flash-Omni config.json (the
    # language model's keys, as the catalog's row holds them)
    "model_type": "longcat_flash", "attention_bias": False,
    "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
    "expert_ffn_hidden_size": 2048, "num_layers": 28,
    "num_attention_heads": 64, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 512,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}


def test_published_keys_read_as_the_registry_entries():
    for extra, name in (({}, "longcat-flash:560b"), (
            {"n_routed_experts": 16, "router_experts": 512,
             "experts_first": 0, "vocab_held": 16384},
            "longcat-flash:560b-ep32")):
        got = _config_from_hf_dict(name, {**PUBLISHED, **extra}, "x")
        assert got == get_config(name)
    cfg = get_config("longcat-flash:560b-ep32")
    # a slice of the vocabulary: the published count stands, the rows held
    # are what the embedding, the head and an engine's sampler span
    assert (cfg.vocab_size, cfg.vocab_held, cfg.vocab_rows) == (
        131_072, 16_384, 16_384)
    with pytest.raises(ValueError, match="not a slice"):
        dataclasses.replace(cfg, vocab_held=200_000)
    assert cfg.router_width == 768 and cfg.held_experts == (0, 16)
    assert cfg.mla_scales == (2.0, 12 ** 0.5)
    # a block owns TWO layers of the pool: a model's layer and a cache's
    # layer are not one thing here
    assert cfg.cache_layers == 56 and cfg.cache_kinds == ("latent",)
    assert cfg.cache_heads == 1 and cfg.cache_dim == 576


@pytest.mark.parametrize("change,named", [
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 10}}, "rope_scaling"),
    ({"zero_expert_type": "copy"}, "zero_expert_type"),
    ({"attention_method": "GQA"}, "attention_method"),
])
def test_what_is_not_served_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        _config_from_hf_dict("x", {**PUBLISHED, **change}, "x")


@pytest.mark.parametrize("family,keys", [
    ("deepseek_v2", {
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "n_routed_experts": 8, "num_experts_per_tok": 3,
        "moe_intermediate_size": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16}),
    ("kimi_linear", {
        "num_hidden_layers": 4, "mla_use_nope": True,
        "linear_attn_config": {"kda_layers": [1, 2, 3],
                               "full_attn_layers": [4]}}),
])
def test_the_older_readers_still_refuse_a_low_rank_query(family, keys):
    """No accepted configuration of theirs proves it: the low-rank query is
    the longcat_flash family's only."""
    with pytest.raises(ValueError, match="q_lora_rank"):
        _config_from_hf_dict(
            "x", {"model_type": family, "q_lora_rank": 24, **keys}, "x")


# -- against the reference ---------------------------------------------------


def test_forward_matches_the_reference(params, ref_logits):
    got = lf.forward(params, CFG, jnp.asarray(TOKENS)[None])[0]
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


def test_bfloat16_lies_within_its_own_tolerance_and_fails_float32s(ref_logits):
    bf = lf.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.bfloat16)
    want = REF.logits(bf, SIZES, list(TOKENS))
    got = np.asarray(lf.forward(bf, CFG, jnp.asarray(TOKENS)[None])[0])
    clear = (want.router_gap >= REF.ROUTER_TIE).all(axis=-1)
    diff = np.abs(got - np.asarray(want)).max(axis=-1)
    assert clear.sum() > 48 and diff[clear].max() < BF16_TOL
    assert diff.max() > TOL


@pytest.mark.parametrize("broken", [
    {"no_shortcut": True}, {"no_zero": True}, {"unit_scales": True},
    {"no_bias": True}, {"skip_layer": 1}, {"round_to": "float8_e4m3fn"}])
def test_a_reference_broken_in_one_mechanism_fails(params, ref_logits, broken):
    """Each control of the chip's comparison, at the CPU's size: the
    shortcut dropped, the zero-compute picks dropped, the two latent
    scales set to 1, the selection bias dropped, one block left out, every
    weight through float8."""
    wrong = np.asarray(REF.logits(params, SIZES, list(TOKENS), **broken))
    assert np.abs(wrong - ref_logits).max() > 100 * TOL


# -- the expert layer: zero-compute picks and the share -----------------------


def _moe_layer(cfg, seed=3, rows=40):
    """One block's expert leaves at `cfg`'s share and rows of normed input."""
    layers = lf.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)["layers"]
    lp = {k: layers[k][0] for k in (
        "router", "router_bias", "we_gate", "we_up", "we_down")}
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (rows, cfg.hidden_size))
    return lp, x


def _ref_moe(h, lp, first, zero=True):
    return REF.moe(h, lp, top_k=CFG.experts_per_token,
                   scaling=CFG.routed_scaling_factor, first=first,
                   routed=CFG.num_experts, bias=True, zero=zero)[0]


def test_the_router_is_as_wide_as_both_kinds_and_weighs_by_the_scores():
    lp, x = _moe_layer(WHOLE)
    assert lp["router"].shape == (64, 24) and lp["we_gate"].shape[0] == 16
    w, i = mixtral._route(WHOLE, lp, x)
    assert int(i.max()) >= 16 and int(i.max()) < 24     # zero-compute picks
    scores = jax.nn.softmax(x @ lp["router"], axis=-1)
    want = jnp.take_along_axis(scores, i, axis=-1) * 6.0    # not renormalised
    assert float(jnp.abs(w - want).max()) < 1e-6
    plain = dataclasses.replace(WHOLE, router_bias=False)
    assert bool((jnp.sort(i) != jnp.sort(mixtral._route(plain, lp, x)[1])).any())


def test_a_zero_compute_pick_reads_no_weight_and_is_counted_apart():
    """Rows that pick zero-compute experts only: nothing is touched, the
    output is the row times its weights' sum whatever the expert leaves
    hold, and the picks count under `zero`."""
    lp, x = _moe_layer(CFG)
    top_i = jnp.tile(jnp.asarray([[16, 19, 22, 23]]), (40, 1))
    top_w = jnp.full((40, 4), 0.25)
    assert int(mixtral._touched(CFG, top_i, None).sum()) == 0
    assert mixtral._route_stats(CFG, top_i, None).tolist() == [40, 0, 0, 0, 160]
    for form in (mixtral._moe_mlp_dense, mixtral._moe_mlp_ragged):
        assert float(jnp.abs(form(CFG, lp, x, top_w, top_i)).max()) == 0.0
    assert float(jnp.abs(mixtral._zero_mlp(CFG, x, top_w, top_i) - x).max()) < 1e-6
    # live rows only, one rule with what the forms compute
    live = jnp.arange(40) < 10
    mixed = top_i.at[:, 0].set(5).at[:, 1].set(1)       # held, absent
    assert mixtral._route_stats(CFG, mixed, live).tolist() == [10, 1, 10, 10, 20]


@pytest.mark.parametrize("form", ["all_experts", "sorted", "grouped"])
def test_four_shares_and_the_zero_part_add_up_to_the_whole_layer(
        form, interpreted_kernels):
    """The routed parts that the four shares give, plus the zero-compute
    part counted once (every chip computes it alike for its own tokens),
    equal the uncut reference's expert layer; and each share's own output
    is the reference's of that share."""
    lp, h = _moe_layer(WHOLE)
    routed = {"all_experts": mixtral._moe_mlp_dense,
              "sorted": mixtral._moe_mlp_ragged,
              "grouped": partial(mixtral._moe_mlp_grouped, live=None)}[form]
    top_w, top_i = mixtral._route(WHOLE, lp, h)
    zero = mixtral._zero_mlp(WHOLE, h, top_w, top_i)
    assert float(jnp.abs(zero).max()) > 0.01
    total = zero
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(WHOLE, experts_held=4, experts_first=first)
        mine = {**lp, **{k: lp[k][first:first + 4]
                         for k in ("we_gate", "we_up", "we_down")}}
        part = routed(cfg, mine, h, top_w, top_i)
        assert float(jnp.abs(part + zero - _ref_moe(h, mine, first)).max()) < TOL
        got, stats = mixtral._moe_mlp(cfg, None, None, mine, h)
        assert float(jnp.abs(got - part - zero).max()) < TOL
        assert int(stats[2:].sum()) == 40 * 4 and int(stats[1]) <= 4
        assert int(stats[4]) == int((top_i >= 16).sum()) > 0
        total = total + part
    assert float(jnp.abs(total - _ref_moe(h, lp, 0)).max()) < TOL
    whole, stats = mixtral._moe_mlp(WHOLE, None, None, lp, h)
    assert float(jnp.abs(whole - total).max()) < TOL
    assert int(stats[3]) == 0               # every routed expert is held


def test_the_rule_of_the_shape_reads_the_share_and_what_a_pick_costs():
    ep32 = get_config("longcat-flash:560b-ep32")
    # 16 held over 12 x 16 / 768 = 0.25 picks a row expected here: XLA's
    # sorted dispatch was the slower (it sorts every pick of a row, 12 a
    # token against 16 held experts); the grouped kernel's sorted regime
    # lays the held picks out alone, in tiles of 16 rows (8 a group)
    assert not mixtral._use_ragged(528, False, "tpu")
    assert mixtral.expert_form(ep32, 80, backend="tpu") == "grouped"
    assert mixtral.expert_form(ep32, 528, backend="tpu") == "grouped_sorted"
    assert mixtral.expert_form(ep32, 80) == "all_experts"
    # every accepted cell's family makes the same choice past the ridge
    assert [mixtral.expert_form(get_config(name), 528, backend="tpu")
            for name in ("kimi-linear:48b-ep4", "laguna-xs2:33b",
                         "smallthinker:21b", "deepseek-v2-lite:16b")] == [
        "grouped_sorted"] * 4


# -- through the cache -------------------------------------------------------


def test_prefill_then_decode_through_two_pool_layers_a_block(params, ref_logits):
    """Chunked prefill then decode steps = the reference's full forward,
    logits at every position; the pool holds one row a token a SUBLAYER."""
    row = _rows()[0]
    lg, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    assert np.abs(np.asarray(lg) - ref_logits[69]).max() < TOL
    assert cache.v is None and cache.k.shape == (
        2 * CFG.num_layers, 24, PS, 1, CFG.kv_lora_rank + CFG.qk_rope_head_dim)
    active = jnp.asarray([True, False])
    step = jax.jit(lambda c, t: lf.decode_step(
        params, CFG, t, c, active, with_stats=True))
    for p in range(70, 96):
        tok = jnp.zeros((2,), jnp.int32).at[0].set(int(TOKENS[p]))
        dec, cache, stats = step(cache, tok)
        assert np.abs(np.asarray(dec[0]) - ref_logits[p]).max() < TOL
        assert int(stats[0]) == CFG.num_layers      # one live row a block
        assert int(stats[2:].sum()) == CFG.num_layers * CFG.experts_per_token


def test_the_mixed_step_serves_a_chunk_beside_running_slots(params, ref_logits):
    rows = _rows()
    _, cache = _chunks(params, TOKENS[:40], _cache(), 0, rows[0], 64)
    active = jnp.asarray([True, False])
    for i, s0 in enumerate((0, 32)):
        part = TOKENS[s0:min(s0 + 32, 50)]
        chunk = jnp.zeros((32,), jnp.int32).at[:len(part)].set(jnp.asarray(part))
        cl, dl, cache = lf.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(1), rows[1], jnp.asarray([TOKENS[40 + i], 0]), cache,
            active)
        assert np.abs(np.asarray(dl[0]) - ref_logits[40 + i]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[49]).max() < TOL
    lg, _ = lf.decode_step(
        params, CFG, jnp.asarray([TOKENS[42], TOKENS[50]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[50]).max() < TOL


def test_a_prefix_cache_admission_reads_anothers_latent_pages(params, ref_logits):
    """Two prompts share 32 tokens: the second is admitted on the first's
    pages of all four pool layers and prefills only its tail; its logits
    are a cold admission's."""
    rng = np.random.default_rng(5)
    b = np.concatenate([TOKENS[:32], rng.integers(0, 256, 12)])
    alloc = PageAllocator(24, PS, 8, cache_pages=-1)
    alloc.alloc(0, 41 + 8)
    row_a = jnp.asarray(alloc.table_row(0), jnp.int32)
    _, cache = _chunks(params, TOKENS[:41], _cache(), 0, row_a, 32)
    alloc.free(0, [int(t) for t in TOKENS[:41]])
    assert alloc.match_prefix(1, [int(t) for t in b]) == 32
    alloc.alloc(1, len(b) + 8)
    row_b = jnp.asarray(alloc.table_row(1), jnp.int32)
    assert row_b[:2].tolist() == row_a[:2].tolist()
    warm, _ = _chunks(params, b, cache, 1, row_b, 32, start=32)
    cold, _ = _chunks(params, b, _cache(), 0, _rows()[0], 32)
    want = np.asarray(REF.logits(params, SIZES, list(b)))[-1]
    assert np.abs(np.asarray(warm) - want).max() < TOL
    assert np.abs(np.asarray(warm) - np.asarray(cold)).max() < TOL


def test_verify_step_and_a_rollback(params, ref_logits):
    n, t = 40, 5
    row = _rows()[0]
    _, cache = _chunks(params, TOKENS[:n], _cache(), 0, row, 32)
    # a wrong draft first: its rows are written, then rolled back
    wrong = jnp.zeros((2, t), jnp.int32).at[0].set(
        jnp.asarray([int(TOKENS[n]), 1, 2, 3, 4]))
    active = jnp.asarray([True, False])
    _, cache = lf.verify_step(params, CFG, wrong, cache, active)
    cache = rollback_to_length(cache, cache.lengths.at[0].set(n + 1))
    assert cache.lengths.tolist() == [n + 1, 0]
    cand = jnp.zeros((2, t), jnp.int32).at[0].set(
        jnp.asarray(TOKENS[n + 1:n + 1 + t]))
    logits, cache, stats = lf.verify_step(
        params, CFG, cand, cache, active, with_stats=True)
    assert np.abs(np.asarray(logits[0]) - ref_logits[n + 1:n + 1 + t]).max() < TOL
    assert int(stats[0]) == CFG.num_layers * t
    # and the rolled-back pages say what pages never written to say
    fresh, _ = _chunks(params, TOKENS[:n + 1], _cache(), 0, row, 64)
    assert np.abs(np.asarray(fresh) - ref_logits[n]).max() < TOL


# -- the engine ---------------------------------------------------------------


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    kw = {"max_slots": 2, **kw}
    return InferenceEngine(EngineConfig(
        model="tiny-longcat-flash", dtype="float32", page_size=PS,
        num_pages=48, max_pages_per_slot=12, prefill_buckets=(32, 128),
        prefill_chunk=64, prefill_chunk_narrow=32, seed=0, **kw))


def _ask(eng, rid, prompt, n=8):
    from gridllm_tpu.engine import GenerationRequest

    return eng.generate(GenerationRequest(
        id=rid, prompt=prompt, options={"temperature": 0.0, "num_predict": n}))


WORDS = ("the quick brown fox jumps over the lazy dog and keeps running "
         "through the field until night falls on the hills beyond it ")


def _count(name, **labels):
    from gridllm_tpu.obs import default_registry

    return default_registry().get(name).value(model="tiny-longcat-flash", **labels)


def test_a_reasked_prefix_is_admitted_from_latent_pages():
    """The re-ask finds the latent pages of the first 96 tokens in every
    pool layer and says what a cold admission says; the launches'
    statistics reach the counters of the share, zero-compute picks apart."""
    eng, cold = _engine(), _engine(prefix_cache=False)
    doc = (WORDS * 2)[:99]
    before = {w: _count("gridllm_moe_picks_total", where=w)
              for w in ("held", "absent", "zero")}
    first = _ask(eng, "a", doc + " one two")
    again = _ask(eng, "b", doc + " six ten")
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert again.token_ids == _ask(cold, "c", doc + " six ten").token_ids
    assert first.token_ids == _ask(cold, "d", doc + " one two").token_ids
    moved = {w: _count("gridllm_moe_picks_total", where=w) - n
             for w, n in before.items()}
    # 4 of 16 routed experts live here, 8 zero-compute ones beside them
    assert moved["held"] > 0 and moved["zero"] > moved["held"]
    assert moved["absent"] > moved["zero"]


def test_the_engine_accounts_for_two_pool_layers_a_block():
    eng = _engine()
    eng.prewarm()
    shape = eng.batch_state()["shape"]
    assert (shape["cacheRow"], shape["attnForm"]) == ("latent", "absorbed")
    assert (shape["expertsHeld"], shape["experts"]) == (4, 16)
    assert eng._expert_meta("verify", 10) == {"expert_form": "all_experts"}
    assert eng.cache.v is None and eng.cache.k.shape[0] == CFG.cache_layers == 4
    mem = eng.memory_arrays()
    assert mem["alloc"]["cacheRow"] == "latent"
    assert mem["alloc"]["rowBytes"] == CFG.cache_dim * 4
    assert not eng.kv_transfer_supported()


def test_a_slice_of_the_vocabulary_is_a_smaller_vocabulary():
    """`vocab_held`: the embedding and the head hold the slice's rows, and
    the engine's tokenizer, logits, penalty counts and sampler span them."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.models.configs import register

    sliced = register(dataclasses.replace(
        CFG, name="tiny-longcat-flash-v300", vocab_size=512, vocab_held=300))
    params = lf.init_params(sliced, jax.random.PRNGKey(0), jnp.float32)
    assert params["embed"].shape == (300, 64)
    assert params["lm_head"].shape == (64, 300)
    eng = InferenceEngine(EngineConfig(
        model=sliced.name, dtype="float32", page_size=PS, num_pages=24,
        max_pages_per_slot=8, max_slots=2, prefill_buckets=(32,),
        prefill_chunk=32, prefill_chunk_narrow=32, seed=0))
    assert (eng.cfg.vocab_size, eng.cfg.vocab_rows) == (300, 300)
    assert eng.counts.shape == (2, 300) and eng.tokenizer.vocab_size == 300
    out = _ask(eng, "v", "a slice of the vocabulary", n=6)
    assert len(out.token_ids) == 6 and max(out.token_ids) < 300


@pytest.mark.parametrize("refused,message", [
    ({"kv_int8": True}, "int8 KV pool is not served for a latent cache"),
    ({"kv_host_bytes": 1 << 20}, "host KV tier is not served for a latent"),
])
def test_int8_pages_and_the_host_tier_are_refused(refused, message):
    with pytest.raises(ValueError, match=message):
        _engine(**refused)


def test_a_mesh_and_a_checkpoint_are_refused():
    from gridllm_tpu.engine.loader import load_checkpoint

    with pytest.raises(ValueError, match="one device only"):
        lf.validate_mesh(CFG, object())
    with pytest.raises(NotImplementedError, match="checkpoints are not read"):
        load_checkpoint(CFG, "/nowhere")
    assert deepseek.softmax_scale(CFG) == 32 ** -0.5     # nope + rope, not 16


# -- compiled for the chip, without the chip --------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (tests/test_deepseek_v2.py's fixture): what
    Mosaic accepts is learned here, not on the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("td,chunk", [(5, 0), (1, 512)])
def test_the_latent_kernel_compiles_for_the_chip_at_64_heads(one_chip, td, chunk):
    """A verify launch (16 slots x 5 rows x 64 heads = 320 query rows a
    slot on the one cache head) and a mixed launch (a 512-row chunk: 32,768
    query rows) over a pool of 8 layers x 1,024 pages of 640 lanes."""
    from gridllm_tpu.ops import pallas_kernels

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, s, h, stored = jnp.int32, 16, 64, 640
    kw = dict(q_group=real((s, td, h, stored)), page_table=real((s, 64), i32),
              group_lengths=real((s,), i32), k_group=real((s, td, 1, stored)))
    if chunk:
        kw.update(q_chunk=real((1, chunk, h, stored)),
                  chunk_row=real((64,), i32), chunk_start=real((), i32),
                  chunk_total=real((), i32), k_chunk=real((chunk, 1, stored)))
    compiled = jax.jit(lambda pool, layer, kw: pallas_kernels.ragged_attention(
        pool, None, 128, layer=layer, latent_dv=512, **kw)).lower(
            real((8, 1024, 128, 1, stored)), real((), i32), kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
