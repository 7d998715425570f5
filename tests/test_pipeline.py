"""Pipeline parallelism (parallel/pipeline.py): numerical parity with the
single-device engine ops on the virtual 8-device CPU mesh, plus the
engine serving end-to-end over a pp×dp×tp mesh (SURVEY.md §2.5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.ops.kvcache import PagedKVCache, PageAllocator
from gridllm_tpu.parallel import pipeline
from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh
from gridllm_tpu.parallel.sharding import shard_cache, shard_params

CFG = get_config("tiny-llama")  # num_layers=2 → 1 layer per stage at pp=2


def _fresh_cache(dtype=jnp.float32):
    return PagedKVCache.create(
        CFG.num_layers, num_pages=16, page_size=8,
        num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim_,
        max_slots=4, max_pages_per_slot=4, dtype=dtype,
    )


def _alloc_row():
    alloc = PageAllocator(16, 8, 4)
    alloc.alloc(0, 16)
    return jnp.asarray(alloc.table_row(0), jnp.int32)


@pytest.fixture(scope="module")
def pp_mesh():
    return build_mesh(MeshConfig(pp=2, dp=2, tp=2))


def test_pp_prefill_decode_match_single_device(pp_mesh):
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = jnp.asarray([5, 7, 11, 13, 17, 19, 23, 29], jnp.int32)
    row = _alloc_row()

    ref_logits, ref_cache = llama.prefill(
        params, CFG, prompt, jnp.int32(8), _fresh_cache(), jnp.int32(0), row)
    tok = jnp.zeros((4,), jnp.int32).at[0].set(3)
    active = jnp.zeros((4,), bool).at[0].set(True)
    ref_dec, ref_cache2 = llama.decode_step(params, CFG, tok, ref_cache, active)

    sp_params = shard_params(params, pp_mesh)
    sp_cache = shard_cache(_fresh_cache(), pp_mesh)
    pp_logits, pp_cache = pipeline.prefill(
        sp_params, CFG, prompt, jnp.int32(8), sp_cache, jnp.int32(0), row,
        mesh=pp_mesh)
    np.testing.assert_allclose(
        np.asarray(pp_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(pp_cache.k), np.asarray(ref_cache.k), rtol=2e-4, atol=2e-4)
    assert int(pp_cache.lengths[0]) == 8

    pp_dec, pp_cache2 = pipeline.decode_step(
        sp_params, CFG, tok, pp_cache, active, mesh=pp_mesh)
    np.testing.assert_allclose(
        np.asarray(pp_dec), np.asarray(ref_dec), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(pp_cache2.k), np.asarray(ref_cache2.k), rtol=2e-4, atol=2e-4)
    assert int(pp_cache2.lengths[0]) == 9


def test_pp_prefill_chunk_matches_single_device(pp_mesh):
    params = llama.init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    row = _alloc_row()
    ids = jnp.asarray(list(range(2, 18)), jnp.int32)  # 16 tokens, 2 chunks of 8

    ref_cache = _fresh_cache()
    for s0 in (0, 8):
        ref_logits, ref_cache = llama.prefill_chunk(
            params, CFG, ids[s0:s0 + 8], jnp.int32(s0), jnp.int32(8),
            ref_cache, jnp.int32(0), row)

    sp_params = shard_params(params, pp_mesh)
    pp_cache = shard_cache(_fresh_cache(), pp_mesh)
    for s0 in (0, 8):
        pp_logits, pp_cache = pipeline.prefill_chunk(
            sp_params, CFG, ids[s0:s0 + 8], jnp.int32(s0), jnp.int32(8),
            pp_cache, jnp.int32(0), row, mesh=pp_mesh)
    np.testing.assert_allclose(
        np.asarray(pp_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(pp_cache.k), np.asarray(ref_cache.k), rtol=2e-4, atol=2e-4)
    assert int(pp_cache.lengths[0]) == 16


def test_pp_validate_rejects_bad_shapes():
    mesh3 = build_mesh(MeshConfig(pp=4, tp=2))  # L=2 % pp=4 != 0
    with pytest.raises(ValueError, match="not divisible"):
        pipeline.validate(CFG, mesh3)
    sp_mesh = build_mesh(MeshConfig(pp=2, sp=2, tp=2))
    with pytest.raises(ValueError, match="sp"):
        pipeline.validate(CFG, sp_mesh)
    mix = get_config("tiny-mixtral")
    with pytest.raises(ValueError, match="llama-skeleton"):
        pipeline.validate(mix, build_mesh(MeshConfig(pp=2, tp=2, dp=2)))


def test_engine_serves_over_pp_mesh():
    """End-to-end: engine with a pp×dp×tp mesh produces the same tokens as
    the unmeshed engine (temperature 0, fixed seed)."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    def run(mesh_cfg):
        eng = InferenceEngine(EngineConfig(
            model="tiny-llama", max_slots=2, page_size=8, num_pages=32,
            max_pages_per_slot=4, prefill_buckets=(16, 32), mesh=mesh_cfg,
        ))
        res = eng.generate(GenerationRequest(
            id="pp1", prompt="hello pipeline world",
            options={"temperature": 0, "num_predict": 6, "seed": 42},
        ))
        assert res.done_reason in ("stop", "length")
        return res.token_ids

    base = run(None)
    pp = run(MeshConfig(pp=2, dp=2, tp=2))
    assert base == pp


def test_pp_engine_attention_is_the_ragged_jnp_leg(monkeypatch):
    """A pp engine's chunked prefill and decode go through the one paged
    dispatcher, on its jnp leg even with kernels on (the partial-manual pp
    region pins use_pallas=False), chunk by chunk through prefill_chunk_fn
    — pipeline is the one module without a mixed_step."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest
    from gridllm_tpu.obs import default_registry
    from gridllm_tpu.ops import kvcache

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()
    c = default_registry().get("gridllm_kernel_dispatch_total")

    def snap():
        return {(lb["op"], lb["path"]): v for lb, v in c.items()
                if lb["op"].startswith("attention_")}

    try:
        before = snap()
        eng = InferenceEngine(EngineConfig(
            model="tiny-llama", max_slots=2, page_size=8, num_pages=32,
            max_pages_per_slot=8, prefill_buckets=(16, 32), prefill_chunk=16,
            mesh=MeshConfig(pp=2, dp=2, tp=2),
        ))
        assert not eng._use_mixed
        res = eng.generate(GenerationRequest(
            id="ppr", prompt="abcdefgh" * 4,  # 33 ids > chunk 16 → chunked
            options={"temperature": 0, "num_predict": 4, "seed": 1},
        ))
        assert len(res.token_ids) == 4
        after = snap()
    finally:
        kvcache._env_mode.cache_clear()
    grew = {k for k, v in after.items() if v > before.get(k, 0)}
    assert grew == {("attention_ragged", "jnp")}, grew


def test_pp_engine_rejects_decoder_embeddings():
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=2, page_size=8, num_pages=32,
        max_pages_per_slot=4, prefill_buckets=(16, 32),
        mesh=MeshConfig(pp=2, dp=2, tp=2),
    ))
    with pytest.raises(RuntimeError, match="pipeline"):
        eng.embed(["hello"])


@pytest.mark.parametrize("n_slots", [4, 3])  # 4 → microbatched, 3 → fallback
def test_pp_decode_schedules_match_single_device(pp_mesh, n_slots):
    """Both decode schedules (GPipe microbatched when S % pp == 0, the
    sequential fallback otherwise) must match the unsharded decode for
    MULTIPLE active slots with ragged lengths."""
    params = llama.init_params(CFG, jax.random.PRNGKey(5), dtype=jnp.float32)
    cache = PagedKVCache.create(
        CFG.num_layers, num_pages=16, page_size=8,
        num_kv_heads=CFG.num_kv_heads, head_dim=CFG.head_dim_,
        max_slots=n_slots, max_pages_per_slot=4, dtype=jnp.float32)
    alloc = PageAllocator(16, 8, 4)
    # ragged prefixes in every slot
    ref_cache = cache
    for slot, ln in enumerate([5, 9, 2, 7][:n_slots]):
        alloc.alloc(slot, 16)
        row = jnp.asarray(alloc.table_row(slot), jnp.int32)
        ids = jnp.asarray(list(range(2, 2 + 16)), jnp.int32)
        _, ref_cache = llama.prefill(
            params, CFG, ids, jnp.int32(ln), ref_cache, jnp.int32(slot), row)

    tok = jnp.asarray(list(range(40, 40 + n_slots)), jnp.int32)
    act = jnp.ones((n_slots,), bool)
    ref_dec, ref_after = llama.decode_step(params, CFG, tok, ref_cache, act)

    sp_params = shard_params(params, pp_mesh)
    pp_cache = shard_cache(ref_cache, pp_mesh)
    pp_dec, pp_after = pipeline.decode_step(
        sp_params, CFG, tok, pp_cache, act, mesh=pp_mesh)
    np.testing.assert_allclose(
        np.asarray(pp_dec), np.asarray(ref_dec), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(pp_after.k), np.asarray(ref_after.k),
        rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(
        np.asarray(pp_after.lengths), np.asarray(ref_after.lengths))
