"""Pallas kernels under meshes (VERDICT r04 #2).

pallas_call has no GSPMD partitioning rule, so the dispatch layers wrap
the kernels in a FULL-manual shard_map at the kernel boundary with
kv-heads split over "tp" (ops.kvcache.kernel_mesh_axis). These tests run
that meshed path on the virtual 8-device CPU mesh with interpret-mode
kernels and assert exact parity with the jnp references — the same
wrapper code runs compiled kernels on real TPU.

Reference behavior being reproduced: the serving engine of the reference
runs whatever Ollama does on one GPU (client/src/services/OllamaService.ts);
sharded serving with kernel-grade attention is where this framework has no
reference analogue and must self-verify (SURVEY.md §4, §7 step 5-6).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.ops.attention import (
    attention_prefill,
    attention_prefill_ref,
    paged_attention_decode_ref,
    ragged_paged_attention,
)
from gridllm_tpu.ops.kvcache import (
    kernel_mesh_axis,
    write_decode_all,
    write_prefill_all,
)
from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh
from tests.helpers import ragged_decode

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    from gridllm_tpu.ops import kvcache

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()
    yield
    kvcache._env_mode.cache_clear()


def _mesh(tp=4, dp=2, sp=1, ep=1):
    return build_mesh(MeshConfig(tp=tp, dp=dp, sp=sp, ep=ep))


def _decode(q, kp, vp, pt, lens, kc, vc, **kw):
    return ragged_decode(q, kp, vp, pt, lens, PS, kc, vc, use_pallas=True,
                         **kw)


L, NP, PS, MPS = 3, 24, 16, 6
S, H, KVH, D = 4, 16, 8, 64


def _decode_operands(kvh=KVH, h=H, d=D):
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (L, NP, PS, kvh, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(1), (L, NP, PS, kvh, d),
                           jnp.float32)
    pt = jnp.tile(jnp.arange(MPS, dtype=jnp.int32)[None], (S, 1))
    lens = jnp.array([37, 0, 90, 5], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, h, d), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(3), (S, kvh, d), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(4), (S, kvh, d), jnp.float32)
    return kp, vp, pt, lens, q, kc, vc


def test_kernel_mesh_axis_modes():
    mesh = _mesh(tp=4, dp=2)
    assert kernel_mesh_axis(None, 8, 16) == ("direct", None)
    assert kernel_mesh_axis(mesh, 8, 16) == ("wrap", "tp")
    assert kernel_mesh_axis(mesh, 2, 16) == ("wrap", None)  # kvh % tp != 0
    pp = build_mesh(MeshConfig(pp=2, tp=4, dp=1))
    assert kernel_mesh_axis(pp, 8, 16) == ("ref", None)


def test_meshed_decode_matches_ref():
    mesh = _mesh()
    kp, vp, pt, lens, q, kc, vc = _decode_operands()

    def f(q, kp, vp, pt, lens, kc, vc):
        return _decode(q, kp, vp, pt, lens, kc, vc,
                       layer=jnp.int32(1), mesh=mesh)

    out = jax.jit(f)(q, kp, vp, pt, lens, kc, vc)
    ref = paged_attention_decode_ref(
        q, kp[1], vp[1], pt, lens, PS, k_cur=kc, v_cur=vc
    )
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_meshed_decode_indivisible_heads_replicates():
    """KVH=2 on tp=4: wrapper engages with heads replicated (matches
    sharding._fit's fallback) and stays correct."""
    mesh = _mesh()
    kp, vp, pt, lens, q, kc, vc = _decode_operands(kvh=2, h=4)

    def f(q, kp, vp, pt, lens, kc, vc):
        return _decode(q, kp, vp, pt, lens, kc, vc,
                       layer=jnp.int32(2), mesh=mesh)

    out = jax.jit(f)(q, kp, vp, pt, lens, kc, vc)
    ref = paged_attention_decode_ref(
        q, kp[2], vp[2], pt, lens, PS, k_cur=kc, v_cur=vc
    )
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_meshed_decode_traced_window_softcap():
    """gemma2-style: traced per-layer window + static softcap through the
    meshed wrapper."""
    mesh = _mesh()
    kp, vp, pt, lens, q, kc, vc = _decode_operands()

    def f(q, kp, vp, pt, lens, kc, vc, win):
        return _decode(q, kp, vp, pt, lens, kc, vc,
                       layer=jnp.int32(0), mesh=mesh,
                       logit_softcap=50.0, window=win)

    win = jnp.int32(32)
    out = jax.jit(f)(q, kp, vp, pt, lens, kc, vc, win)
    ref = paged_attention_decode_ref(
        q, kp[0], vp[0], pt, lens, PS, k_cur=kc, v_cur=vc,
        logit_softcap=50.0, window=win,
    )
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_meshed_prefill_matches_ref():
    mesh = _mesh()
    B, T = 1, 256
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, KVH, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, KVH, D), jnp.float32)
    sl = jnp.array([200], jnp.int32)

    out = jax.jit(
        lambda q, k, v, sl: attention_prefill(
            q, k, v, sl, use_pallas=True, mesh=mesh
        )
    )(q, k, v, sl)
    ref = attention_prefill_ref(q, k, v, sl)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_meshed_write_decode_matches_scatter():
    mesh = _mesh()
    kvh, d = KVH, D
    kp = jnp.zeros((L, NP, PS, kvh, d), jnp.float32)
    vp = jnp.zeros((L, NP, PS, kvh, d), jnp.float32)
    pt = jnp.tile(jnp.arange(MPS, dtype=jnp.int32)[None], (S, 1))
    positions = jnp.array([3, 17, 0, 95], jnp.int32)
    active = jnp.array([True, True, False, True])
    kn = jax.random.normal(jax.random.PRNGKey(5), (L, S, kvh, d), jnp.float32)
    vn = jax.random.normal(jax.random.PRNGKey(6), (L, S, kvh, d), jnp.float32)

    out_k, out_v = jax.jit(
        lambda kp, vp, kn, vn, pt, pos, act: write_decode_all(
            kp, vp, kn, vn, pt, pos, act, PS, use_pallas=True, mesh=mesh
        )
    )(kp, vp, kn, vn, pt, positions, active)
    ref_k, ref_v = write_decode_all(
        kp, vp, kn, vn, pt, positions, active, PS, use_pallas=False
    )
    np.testing.assert_array_equal(out_k, ref_k)
    np.testing.assert_array_equal(out_v, ref_v)


def test_meshed_write_prefill_matches_scatter():
    mesh = _mesh()
    kvh, d = KVH, D
    T = 2 * PS  # kernel path needs T % page_size == 0
    kp = jnp.zeros((L, NP, PS, kvh, d), jnp.float32)
    vp = jnp.zeros((L, NP, PS, kvh, d), jnp.float32)
    row = jnp.arange(MPS, dtype=jnp.int32)
    kn = jax.random.normal(jax.random.PRNGKey(7), (L, T, kvh, d), jnp.float32)
    vn = jax.random.normal(jax.random.PRNGKey(8), (L, T, kvh, d), jnp.float32)
    start, length = jnp.int32(PS), jnp.int32(PS + 5)

    out_k, out_v = jax.jit(
        lambda kp, vp, kn, vn, row, start, length: write_prefill_all(
            kp, vp, kn, vn, row, start, length, PS, use_pallas=True,
            mesh=mesh,
        )
    )(kp, vp, kn, vn, row, start, length)
    ref_k, ref_v = write_prefill_all(
        kp, vp, kn, vn, row, start, length, PS, use_pallas=False
    )
    # the chunk kernel writes whole pages while the scatter drops padded
    # rows (tests/test_pallas.py) — only positions < start+length are part
    # of the contract (attention masks by length, padding is never read)
    for t in range(int(length)):
        pos = int(start) + t
        p, o = int(row[pos // PS]), pos % PS
        np.testing.assert_array_equal(out_k[:, p, o], ref_k[:, p, o])
        np.testing.assert_array_equal(out_v[:, p, o], ref_v[:, p, o])


def test_meshed_engine_keeps_kernels_on():
    """A tp mesh no longer flips cfg.use_pallas off (engine/engine.py);
    only pp > 1 does (the pipeline region pins jnp paths itself)."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", mesh=MeshConfig(tp=8), max_slots=2,
        num_pages=16, page_size=8, max_pages_per_slot=8,
        prefill_buckets=(16,),
    ))
    assert eng.cfg.use_pallas is not False  # auto/env policy preserved

    eng_pp = InferenceEngine(EngineConfig(
        model="tiny-llama", mesh=MeshConfig(pp=2, tp=4), max_slots=2,
        num_pages=16, page_size=8, max_pages_per_slot=8,
        prefill_buckets=(16,),
    ))
    assert eng_pp.cfg.use_pallas is False


def test_meshed_engine_generates_with_kernels():
    """End-to-end: a tp:8-meshed engine serving with interpret-mode
    kernels produces the same tokens as an unmeshed jnp engine (greedy,
    same random weights)."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine

    from gridllm_tpu.ops import kvcache

    opts = {"temperature": 0.0, "num_predict": 8}
    results = {}
    try:
        for tag, mesh, env in (
            ("meshed-kernels", MeshConfig(tp=8), "interpret"),
            ("unmeshed-jnp", None, "0"),
        ):
            os.environ["GRIDLLM_PALLAS"] = env
            kvcache._env_mode.cache_clear()
            eng = InferenceEngine(EngineConfig(
                model="tiny-llama", mesh=mesh, max_slots=2, num_pages=64,
                page_size=8, max_pages_per_slot=8, prefill_buckets=(16, 32),
            ))
            res = eng.generate(GenerationRequest(
                id=tag, prompt="hello", options=opts,
            ))
            results[tag] = res.token_ids
    finally:
        os.environ["GRIDLLM_PALLAS"] = "interpret"
        kvcache._env_mode.cache_clear()
    assert results["meshed-kernels"] == results["unmeshed-jnp"]
    assert len(results["meshed-kernels"]) == 8


def test_meshed_prefix_chunk_matches_ref():
    """The ragged kernel's chunk region through the full-manual tp
    shard_map."""
    mesh = _mesh()
    t, ps, maxp = 16, 8, 6
    kp = jax.random.normal(jax.random.PRNGKey(0), (L, NP, PS, KVH, D),
                           jnp.float32)
    vp = kp * 0.9
    row = jnp.arange(maxp, dtype=jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, t, H, D), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(2), (t, KVH, D), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(3), (t, KVH, D), jnp.float32)
    start, total = jnp.int32(PS), jnp.int32(PS + 12)

    from gridllm_tpu.ops.attention import _prefix_chunk_ref

    got = jax.jit(
        lambda q, kp, vp, row, start, total, kc, vc: ragged_paged_attention(
            kp, vp, PS, q_chunk=q, chunk_row=row, chunk_start=start,
            chunk_total=total, k_chunk=kc, v_chunk=vc,
            layer=jnp.int32(1), use_pallas=True, mesh=mesh,
        )[0]
    )(q, kp, vp, row, start, total, kc, vc)
    want = _prefix_chunk_ref(
        q, kp, vp, row, start, total, PS, k_cur=kc, v_cur=vc,
        layer=jnp.int32(1),
    )
    np.testing.assert_allclose(got[:, :12], want[:, :12], atol=2e-5)
