"""DeepSeek-V2 (latent attention over a latent page pool, a leading dense
layer, routed experts with a shared one, un-renormalised top-k, YaRN)
against its plain float32 reference,
benchmark/reference/deepseek_v2_f32.py, on seeded tiny-deepseek-v2
weights. Logits, not tokens; contexts run past two pages and past YaRN's
original context (64), so the latent pages, the absorbed form and the
stretched frequencies each decide the result, and the reference with one
mechanism broken must fail the tolerance that the sound one passes."""

import dataclasses
import hashlib
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import deepseek, mixtral
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops.kvcache import (
    PageAllocator,
    PagedKVCache,
    rollback_to_length,
)
from gridllm_tpu.ops.layers import precompute_rope, yarn_factors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-deepseek-v2")
# float32 on both sides in another operation order (the absorbed form
# folds W_kvb into the query; the reference expands): rounding only. The
# largest difference seen is 1.1e-6 (logits up to 0.7); bf16 weights in
# float32's place read 0.07 and each broken mechanism 0.3 to 1.1
TOL = 5e-5
PS = 16                                  # page size of the test pools


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/deepseek_v2_f32.py", "deepseek_v2_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module")
def params():
    return deepseek.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def _ref(params, toks, **kw):
    return np.asarray(REF.logits(params, SIZES, list(toks), **kw))


def _cache():
    return PagedKVCache.create(
        CFG.num_layers, num_pages=24, page_size=PS,
        num_kv_heads=CFG.cache_heads, head_dim=CFG.cache_dim,
        max_slots=2, max_pages_per_slot=8, dtype=jnp.float32, latent=True)


def _rows(n_tokens=128):
    alloc = PageAllocator(24, PS, 8)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def _chunks(params, toks, cache, slot, row, width=32, start=0):
    """A prompt (its rows from `start` on) admitted as the engine admits
    it, through `mixed_step` with no active slot, `width` rows a launch."""
    idle = jnp.zeros(cache.lengths.shape, jnp.int32)
    for s0 in range(start, len(toks), width):
        part = toks[s0:s0 + width]
        chunk = jnp.zeros((width,), jnp.int32).at[:len(part)].set(
            jnp.asarray(part))
        logits, _, cache = deepseek.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0)
    return logits, cache


PUBLISHED = {       # deepseek-ai/DeepSeek-V2-Lite config.json
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def test_published_keys_read_as_the_registry_entry():
    name = "deepseek-v2-lite:16b"
    assert _config_from_hf_dict(name, PUBLISHED, "x") == get_config(name)
    spec = json.load(open(os.path.join(
        ROOT, "benchmark/configs/deepseek-v2-lite-L10.json")))
    assert {k: spec[k] for k in PUBLISHED if k != "num_hidden_layers"} == {
        k: v for k, v in PUBLISHED.items() if k != "num_hidden_layers"}
    cut = _config_from_hf_dict("x", spec, "x")
    assert dataclasses.replace(cut, name=name, num_layers=27) == get_config(name)
    # what is not served is refused by name, not run wrong
    for key, value in (("q_lora_rank", 1536), ("scoring_func", "sigmoid"),
                       ("n_group", 8), ("topk_method", "group_limited_greedy")):
        with pytest.raises(ValueError, match=key):
            _config_from_hf_dict("x", {**PUBLISHED, key: value}, "x")
    # another family's config reads as it did: the new fields at rest
    dense = get_config("mistral:7b")
    assert (dense.cache_heads, dense.cache_dim, dense.kv_row_values) == (
        8, 128, 2 * 8 * 128)
    assert dense.expert_width == dense.intermediate_size and dense.norm_topk_prob


def test_yarn_against_hand_figures():
    cfg = get_config("deepseek-v2-lite:16b")
    scale, mult = yarn_factors(cfg.rope_scaling)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert scale == pytest.approx(1.5896, abs=1e-4) and mult == 1.0
    assert deepseek.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.5896, rel=1e-4)
    f = np.asarray(precompute_rope(64, 10_000.0, cfg.rope_scaling))
    plain = 10_000.0 ** (-np.arange(32) / 32)
    # dim(32) = 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47, dim(1) = 22.5:
    # pairs 0..10 keep their frequency, 23.. are divided by 40, a ramp between
    assert np.allclose(f[:11], plain[:11], rtol=1e-6)
    assert np.allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (15 - 10) / (23 - 10)
    assert f[15] == pytest.approx(plain[15] / 40 * ramp + plain[15] * (1 - ramp), rel=1e-5)
    assert np.allclose(np.asarray(REF.yarn(PUBLISHED)[0]), f, rtol=1e-6)
    assert REF.yarn(PUBLISHED)[1] == pytest.approx(scale)
    # llama3's rule and no rule read as they did
    assert yarn_factors(None) == (1.0, 1.0)
    assert yarn_factors(get_config("llama3.1:8b").rope_scaling) == (1.0, 1.0)


def test_forward_matches_the_reference(params):
    toks = _tokens(100)                       # past YaRN's original 64
    got = deepseek.forward(params, CFG, jnp.asarray(toks)[None])[0]
    assert np.abs(np.asarray(got) - _ref(params, toks)).max() < TOL


@pytest.mark.parametrize("broken", [
    {"renormalise_topk": True}, {"no_shared": True}, {"rope": "plain"},
    {"skip_layer": 0}, {"skip_layer": 2}])
def test_a_reference_broken_in_one_mechanism_fails(params, broken):
    toks = _tokens(100)
    got = np.asarray(deepseek.forward(params, CFG, jnp.asarray(toks)[None])[0])
    assert np.abs(got - _ref(params, toks, **broken)).max() > 1000 * TOL


def test_bf16_fails_the_tolerance(params):
    toks = _tokens(100)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = np.asarray(deepseek.forward(params, CFG, jnp.asarray(toks)[None])[0])
    assert np.abs(got - _ref(rounded, toks)).max() > 100 * TOL


def test_prefill_then_decode_through_the_latent_pool(params):
    toks = _tokens(48, seed=1)
    n = 40                                   # past two pages of 16
    row = _rows()[0]
    logits, cache = _chunks(params, toks[:n], _cache(), 0, row, width=64)
    want = _ref(params, toks)
    assert np.abs(np.asarray(logits) - want[n - 1]).max() < TOL
    # the pool holds ONE row of latent + rope key a token a layer, and no V
    assert cache.v is None and cache.k.shape == (
        CFG.num_layers, 24, PS, 1, CFG.kv_lora_rank + CFG.qk_rope_head_dim)
    active = jnp.asarray([True, False])
    for p in range(n, len(toks)):
        tok = jnp.zeros((2,), jnp.int32).at[0].set(int(toks[p]))
        dec, cache, stats = deepseek.decode_step(
            params, CFG, tok, cache, active, with_stats=True)
        assert np.abs(np.asarray(dec[0]) - want[p]).max() < TOL
        routed = CFG.num_layers - CFG.first_k_dense
        assert stats.tolist() == [routed, routed * CFG.experts_per_token]


@pytest.mark.parametrize("behind", [32, 48])
def test_chunked_prefill_and_the_mixed_step(params, behind):
    """The chunk region (absorbed) behind a prefix of two and of three
    pages, alone and beside another slot's decode token."""
    a, b = _tokens(53, seed=2), _tokens(behind + 32, seed=3)
    rows = _rows()
    logits, cache = _chunks(params, a[:52], _cache(), 0, rows[0])
    want_a = _ref(params, a)
    assert np.abs(np.asarray(logits) - want_a[51]).max() < TOL
    # slot 1 admits its prefix alone, its last chunk (behind two or three
    # pages) beside slot 0's decode token: one launch a layer
    _, cache = _chunks(params, b[:behind], cache, 1, rows[1], width=16)
    tokens = jnp.zeros((2,), jnp.int32).at[0].set(int(a[52]))
    chunk_logits, dec, cache = deepseek.mixed_step(
        params, CFG, jnp.asarray(b[behind:]), jnp.int32(behind), jnp.int32(32),
        jnp.int32(1), rows[1], tokens, cache, jnp.asarray([True, False]))
    assert np.abs(np.asarray(chunk_logits) - _ref(params, b)[-1]).max() < TOL
    assert np.abs(np.asarray(dec[0]) - want_a[52]).max() < TOL


def test_a_prefix_cache_admission_reads_anothers_latent_pages(params):
    """Two prompts share 32 tokens: the second is admitted on the first's
    pages (the allocator's content-addressed match: no second table or
    key) and prefills only its tail."""
    shared, tail_a, tail_b = _tokens(32, seed=5), _tokens(9, 6), _tokens(12, 7)
    a, b = np.concatenate([shared, tail_a]), np.concatenate([shared, tail_b])
    alloc = PageAllocator(24, PS, 8, cache_pages=-1)
    alloc.alloc(0, len(a) + 8)
    row_a = jnp.asarray(alloc.table_row(0), jnp.int32)
    _, cache = _chunks(params, a, _cache(), 0, row_a)
    alloc.free(0, [int(t) for t in a])      # its full pages enter the cache
    assert alloc.match_prefix(1, [int(t) for t in b]) == 32
    alloc.alloc(1, len(b) + 8)
    row_b = jnp.asarray(alloc.table_row(1), jnp.int32)
    assert row_b[:2].tolist() == row_a[:2].tolist()
    logits, cache = _chunks(params, b, cache, 1, row_b, start=32)
    assert np.abs(np.asarray(logits) - _ref(params, b)[-1]).max() < TOL


def test_verify_step_and_a_rollback(params):
    toks = _tokens(46, seed=4)
    n, t = 40, 4
    row = _rows()[0]
    _, cache = _chunks(params, toks[:n], _cache(), 0, row)
    # a wrong draft first: its rows are written, then rolled back
    wrong = jnp.zeros((2, t), jnp.int32).at[0].set(
        jnp.asarray([int(toks[n]), 1, 2, 3]))
    active = jnp.asarray([True, False])
    _, cache = deepseek.verify_step(params, CFG, wrong, cache, active)
    cache = rollback_to_length(cache, cache.lengths.at[0].set(n + 1))
    assert cache.v is None and cache.lengths.tolist() == [n + 1, 0]
    cand = jnp.zeros((2, t), jnp.int32).at[0].set(
        jnp.asarray(toks[n + 1:n + 1 + t]))
    logits, cache, stats = deepseek.verify_step(
        params, CFG, cand, cache, active, with_stats=True)
    want = _ref(params, toks)
    assert np.abs(np.asarray(logits[0]) - want[n + 1:n + 1 + t]).max() < TOL
    assert int(stats[0]) == (CFG.num_layers - CFG.first_k_dense) * t


def test_absorbed_equals_expanded_on_the_same_latents(params):
    """One layer's attention both ways from the same rows."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    rng = np.random.default_rng(0)
    t = 24
    q_nope = jnp.asarray(rng.normal(size=(1, t, CFG.num_heads, 16)), jnp.float32)
    q_pe = jnp.asarray(rng.normal(size=(1, t, CFG.num_heads, 16)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(1, t, CFG.cache_dim)), jnp.float32)
    pos = jnp.arange(t)[None]
    want = deepseek._expanded(CFG, lp, q_nope, q_pe, pos, rows, pos,
                              jnp.ones((1, t), bool))

    def on_rows(q):      # plain attention straight on the latent rows
        s = jnp.einsum("bthw,bnw->bhtn", q, rows) / math.sqrt(CFG.cache_dim)
        s = jnp.where(pos[:, None, None, :] <= pos[:, None, :, None], s, -1e30)
        return jnp.einsum("bhtn,bnr->bthr", jax.nn.softmax(s, -1),
                          rows[..., :CFG.kv_lora_rank])

    got = deepseek._absorbed(CFG, lp, q_nope, q_pe, on_rows)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_the_ragged_kernel_reads_latent_pages(params, monkeypatch):
    """The Pallas kernel's latent form (interpreted): one page DMA a step
    serves scores and values; chunk and group regions in one launch."""
    from gridllm_tpu.ops import attention, kvcache

    rng = np.random.default_rng(1)
    w, dv, h = CFG.cache_dim, CFG.kv_lora_rank, CFG.num_heads
    pool = jnp.asarray(rng.normal(size=(2, 24, PS, 1, w)), jnp.float32)
    rows = _rows()
    table = jnp.stack(rows)
    kw = dict(
        q_chunk=jnp.asarray(rng.normal(size=(1, 32, h, w)), jnp.float32),
        chunk_row=rows[1], chunk_start=jnp.int32(32), chunk_total=jnp.int32(60),
        k_chunk=jnp.asarray(rng.normal(size=(32, 1, w)), jnp.float32),
        q_group=jnp.asarray(rng.normal(size=(2, 5, h, w)), jnp.float32),
        page_table=table, group_lengths=jnp.asarray([37, 0], jnp.int32),
        k_group=jnp.asarray(rng.normal(size=(2, 5, 1, w)), jnp.float32),
        layer=jnp.int32(1), latent_dv=dv)
    want = attention.ragged_paged_attention_ref(pool, None, PS, **kw)
    monkeypatch.setattr(kvcache, "_env_mode", lambda: (True, True))
    got = attention.ragged_paged_attention(pool, None, PS, **kw)
    assert got[0].shape == (1, 32, h, dv) and got[1].shape == (2, 5, h, dv)
    assert np.abs(np.asarray(got[0][0, :28]) - np.asarray(want[0][0, :28])).max() < 1e-4
    assert np.abs(np.asarray(got[1][0]) - np.asarray(want[1][0])).max() < 1e-4


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    return InferenceEngine(EngineConfig(
        model="tiny-deepseek-v2", max_slots=2, page_size=8, num_pages=64,
        max_pages_per_slot=16, prefill_buckets=(16, 32), prefill_chunk=32,
        seed=0, **kw))


def test_the_engine_serves_it_on_one_latent_row_a_token():
    from gridllm_tpu.engine import GenerationRequest
    from gridllm_tpu.obs import default_registry

    eng = _engine()
    eng.prewarm()
    assert eng.cache.v is None
    assert eng.cache.k.shape[3:] == (1, CFG.cache_dim)
    itemsize = eng.cache.k.dtype.itemsize
    reg = default_registry()
    assert reg.get("gridllm_kv_row_bytes").value(
        model="tiny-deepseek-v2", kind="latent") == CFG.cache_dim * itemsize
    assert reg.get("gridllm_kv_row_bytes_per_head_equiv").value(
        model="tiny-deepseek-v2") == (
            CFG.num_heads * (CFG.head_dim_ + CFG.v_head_dim) * itemsize)
    doc = "a document that is longer than two pages of eight tokens, asked "
    first = eng.generate(GenerationRequest(
        id="q1", prompt=doc + "one", options={"temperature": 0.0, "num_predict": 10}))
    again = eng.generate(GenerationRequest(
        id="q2", prompt=doc + "one", options={"temperature": 0.0, "num_predict": 10}))
    # the second is admitted from the first's latent pages and, through
    # the same programs on the same rows, says the same
    assert first.eval_count > 0 and again.token_ids == first.token_ids
    assert first.cached_tokens == 0 and again.cached_tokens >= 16
    mem = eng.memory_arrays()["alloc"]
    assert mem["cacheRow"] == "latent" and mem["rowBytes"] == CFG.cache_dim * itemsize
    assert not eng.kv_transfer_supported()
    shape = eng.batch_state()["shape"]
    assert (shape["cacheRow"], shape["attnForm"]) == ("latent", "absorbed")


@pytest.mark.parametrize("refused", [{"kv_int8": True}, {"kv_host_bytes": 1 << 20}])
def test_int8_pages_and_spill_are_refused_for_a_latent_cache(refused):
    with pytest.raises(ValueError, match="latent cache"):
        _engine(**refused)


def test_a_mesh_is_refused():
    with pytest.raises(ValueError, match="one device only"):
        deepseek.validate_mesh(CFG, object())


def test_hf_names_and_the_rope_pairing(params, tmp_path):
    """convert_hf_state_dict on a state dict in the published layout
    (interleaved RoPE pairs) gives this program's tree."""
    import numpy as np

    dn, dr, r = CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.kv_lora_rank
    perm_q = deepseek._rope_pairing(CFG, CFG.num_heads, dn + dr, dn)
    perm_kv = deepseek._rope_pairing(CFG, 1, r + dr, r)
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    for tree, names, base in ((params["dense"], deepseek.DENSE_HF_MAP, 0),
                              (params["layers"], deepseek.hf_map(CFG),
                               CFG.first_k_dense)):
        for leaf, (tmpl, tr) in names.items():
            for i in range(tree[leaf].shape[0]):
                w = np.asarray(tree[leaf][i])
                if leaf == "wq":
                    w = w[:, np.argsort(perm_q)]
                if leaf == "w_kva":
                    w = w[:, np.argsort(perm_kv)]
                if tmpl.count("{}") == 2:
                    for x in range(CFG.num_experts):
                        sd[tmpl.format(base + i, x)] = w[x].T if tr else w[x]
                else:
                    sd[tmpl.format(base + i)] = w.T if tr else w
    assert "model.layers.1.mlp.shared_experts.gate_proj.weight" in sd
    assert "model.layers.0.self_attn.kv_a_proj_with_mqa.weight" in sd
    back = deepseek.convert_hf_state_dict(CFG, sd, dtype=jnp.float32)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and through the engine's safetensors reader
    from safetensors.numpy import save_file

    from gridllm_tpu.engine.loader import load_checkpoint, save_checkpoint

    save_file({k: np.ascontiguousarray(v) for k, v in sd.items()},
              str(tmp_path / "model.safetensors"))
    loaded = load_checkpoint(CFG, str(tmp_path), jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(NotImplementedError, match="not written"):
        save_checkpoint(params, CFG, str(tmp_path / "out"))


# -- compiled for the chip, without the chip --------------------------------
# The TPU's compiler is installed here and compiles for a v5e that is
# described, not attached (nothing runs): what Mosaic accepts of the latent
# geometry is held at no chip time. The topology is described inside a
# fixture of this file alone, never at import.


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
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _real(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _latent_launch(one_chip, stored, td=5, chunk=0):
    """DeepSeek-V2-Lite's verify launch (16 slots x K + 1 rows, 16 heads on
    the one cache head) over a pool of 10 layers x 1,024 pages."""
    from gridllm_tpu.ops import pallas_kernels

    i32, s, h = jnp.int32, 16, 16
    kw = dict(q_group=_real(one_chip, (s, td, h, stored)),
              page_table=_real(one_chip, (s, 64), i32),
              group_lengths=_real(one_chip, (s,), i32),
              k_group=_real(one_chip, (s, td, 1, stored)))
    if chunk:
        kw.update(q_chunk=_real(one_chip, (1, chunk, h, stored)),
                  chunk_row=_real(one_chip, (64,), i32),
                  chunk_start=_real(one_chip, (), i32),
                  chunk_total=_real(one_chip, (), i32),
                  k_chunk=_real(one_chip, (chunk, 1, stored)))
    pool = _real(one_chip, (10, 1024, 128, 1, stored))
    return jax.jit(lambda pool, layer, kw: pallas_kernels.ragged_attention(
        pool, None, 128, layer=layer, latent_dv=512, **kw)).lower(
            pool, _real(one_chip, (), i32), kw).compile()


def test_the_latent_kernels_compile_for_the_chip_at_640_lanes(one_chip):
    from gridllm_tpu.ops import kvcache, pallas_kernels

    for compiled in (_latent_launch(one_chip, 640),
                     _latent_launch(one_chip, 640, td=1, chunk=512)):
        assert "tpu_custom_call" in compiled.as_text()
        # the pool is handed to the kernel as it lies: no copy of it
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    i32 = jnp.int32
    pool = _real(one_chip, (10, 1024, 128, 1, 640))
    chunk = jax.jit(
        lambda pool, new, row, a, n: pallas_kernels.paged_write_chunk(
            pool, None, new, None, row, a, n, page_size=128)[0],
        donate_argnums=0).lower(
            pool, _real(one_chip, (10, 512, 1, 640)), _real(one_chip, (64,), i32),
            _real(one_chip, (), i32), _real(one_chip, (), i32)).compile()
    rows = jax.jit(kvcache._write_latent_rows, donate_argnums=0).lower(
        pool, _real(one_chip, (10, 80, 1, 640)), _real(one_chip, (80,), i32),
        _real(one_chip, (80,), i32)).compile()
    # both in place: a write that copied the pool would need 1.7 GB here
    assert chunk.memory_analysis().temp_size_in_bytes < 1 << 20
    assert rows.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_refuses_a_row_stored_at_576_lanes(one_chip):
    """Why the row is stored padded: the page [128, 576] is tiled at 640
    lanes in HBM and 576 of them cannot be sliced for the page DMA."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _latent_launch(one_chip, 576, td=1)


# a routed family that is not this one traces its verify program as it did
# before the shared expert, the un-renormalised weights and the latent
# pool were threaded through mixtral._moe_mlp and the ops: the hash was
# taken on the parent commit (965a97a, bd1c63d7...) and again in PR 49,
# whose one layer body traces the same equations with the RoPE frequencies
# computed after the positions and not before.
# tests/test_smallthinker.py holds a dense family's the same way.
ROUTED_VERIFY_JAXPR = (
    "ef5657d56692359fb7d823c51591838c85192b4299fc76a217c3ab1cca2bbb0f")


def routed_verify_jaxpr() -> str:
    cfg = get_config("tiny-smallthinker")
    params = jax.eval_shape(
        lambda: mixtral.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        cfg.num_layers, num_pages=16, page_size=8,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
        max_slots=2, max_pages_per_slot=8, dtype=jnp.float32))
    cfg = dataclasses.replace(cfg, use_pallas=False)
    text = str(jax.make_jaxpr(
        lambda p, c, t, a: mixtral.verify_step(p, cfg, t, c, a))(
            params, cache, jax.ShapeDtypeStruct((2, 5), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.bool_)))
    return hashlib.sha256(text.encode()).hexdigest()


def test_a_routed_familys_verify_program_is_unchanged():
    assert routed_verify_jaxpr() == ROUTED_VERIFY_JAXPR
