"""Mixtral MoE numerics goldens (same two-oracle scheme as test_models.py):
HF MixtralForCausalLM on identical tiny weights, then paged prefill/decode
vs the cache-free forward."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from gridllm_tpu.models import mixtral
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.ops.kvcache import PagedKVCache, PageAllocator

CFG = get_config("tiny-mixtral")


@pytest.fixture(scope="module")
def params_fp32():
    return mixtral.init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)


def _hf_model(params):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import MixtralForCausalLM

    model = MixtralForCausalLM(CFG.hf_config()).eval()
    sd = {}

    def put(name, arr, transpose):
        a = np.asarray(arr, np.float32)
        sd[name] = torch.from_numpy(a.T.copy() if transpose else a.copy())

    put("model.embed_tokens.weight", params["embed"], False)
    lp = params["layers"]
    for i in range(CFG.num_layers):
        pre = f"model.layers.{i}."
        put(pre + "input_layernorm.weight", lp["attn_norm"][i], False)
        put(pre + "self_attn.q_proj.weight", lp["wq"][i], True)
        put(pre + "self_attn.k_proj.weight", lp["wk"][i], True)
        put(pre + "self_attn.v_proj.weight", lp["wv"][i], True)
        put(pre + "self_attn.o_proj.weight", lp["wo"][i], True)
        put(pre + "post_attention_layernorm.weight", lp["mlp_norm"][i], False)
        put(pre + "block_sparse_moe.gate.weight", lp["router"][i], True)
        for x in range(CFG.num_experts):
            epre = pre + f"block_sparse_moe.experts.{x}."
            put(epre + "w1.weight", lp["we_gate"][i, x], True)
            put(epre + "w2.weight", lp["we_down"][i, x], True)
            put(epre + "w3.weight", lp["we_up"][i, x], True)
    put("model.norm.weight", params["final_norm"], False)
    put("lm_head.weight", params["lm_head"], True)
    model.load_state_dict(sd)
    return model, torch


def test_forward_matches_hf(params_fp32):
    model, torch = _hf_model(params_fp32)
    tokens = np.array([[5, 17, 99, 3, 42, 7, 250, 1]], np.int32)
    ours = np.asarray(mixtral.forward(params_fp32, CFG, jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = model(torch.from_numpy(tokens).long()).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_convert_hf_state_dict_roundtrip(params_fp32):
    model, _torch = _hf_model(params_fp32)
    back = mixtral.convert_hf_state_dict(CFG, model.state_dict(), dtype=jnp.float32)
    tokens = jnp.asarray([[9, 8, 7, 6, 5]], jnp.int32)
    a = mixtral.forward(params_fp32, CFG, tokens)
    b = mixtral.forward(back, CFG, tokens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_moe_mlp_matches_per_token_brute_force(params_fp32):
    """_moe_mlp (the production dense-weighted einsum) == an independent
    per-token loop that runs only the top-k selected experts — catches
    gating bugs (dropped renormalization, wrong combine) without torch."""
    x = jax.random.normal(jax.random.PRNGKey(2), (5, CFG.hidden_size), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params_fp32["layers"])
    got = np.asarray(mixtral._moe_mlp(CFG, None, None, lp, x)[0])

    def silu(a):
        return a / (1.0 + np.exp(-a))

    xs = np.asarray(x)
    router = np.asarray(lp["router"])
    want = np.zeros_like(xs)
    for t in range(xs.shape[0]):
        logits = xs[t] @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p)[: CFG.experts_per_token]
        w = p[top] / p[top].sum()
        for wi, xp in zip(w, top):
            g = xs[t] @ np.asarray(lp["we_gate"][xp])
            u = xs[t] @ np.asarray(lp["we_up"][xp])
            want[t] += wi * (silu(g) * u) @ np.asarray(lp["we_down"][xp])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_prefill_decode_match_forward(params_fp32):
    prompt = [5, 17, 99, 3, 42]
    n_gen = 5
    seq = list(prompt)
    oracle = []
    for _ in range(n_gen):
        logits = mixtral.forward(params_fp32, CFG, jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        oracle.append(nxt)
        seq.append(nxt)

    cache = PagedKVCache.create(
        CFG.num_layers, 16, 8, CFG.num_kv_heads, CFG.head_dim_, 4, 8,
        dtype=jnp.float32,
    )
    alloc = PageAllocator(16, 8, 8)
    slot = 1
    alloc.alloc(slot, len(prompt) + n_gen)
    row = jnp.asarray(alloc.table_row(slot), jnp.int32)
    padded = jnp.asarray(prompt + [0] * (8 - len(prompt)), jnp.int32)
    logits, cache = mixtral.prefill(
        params_fp32, CFG, padded, jnp.int32(len(prompt)), cache,
        jnp.int32(slot), row,
    )
    got = [int(jnp.argmax(logits))]
    tokens = jnp.zeros((cache.max_slots,), jnp.int32).at[slot].set(got[0])
    active = jnp.zeros((cache.max_slots,), bool).at[slot].set(True)
    for _ in range(n_gen - 1):
        logits, cache = mixtral.decode_step(params_fp32, CFG, tokens, cache, active)
        nxt = int(jnp.argmax(logits[slot]))
        got.append(nxt)
        tokens = tokens.at[slot].set(nxt)
    assert got == oracle


def test_engine_generates_with_mixtral():
    """The engine's family dispatch + fused decode works end-to-end on the
    MoE model (byte tokenizer, greedy)."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine

    eng = InferenceEngine(EngineConfig(
        model="tiny-mixtral", max_slots=2, page_size=8, num_pages=32,
        max_pages_per_slot=8, prefill_buckets=(16,), seed=0,
    ))
    res = eng.generate(GenerationRequest(
        id="m1", prompt="hello", options={"temperature": 0.0, "num_predict": 8},
    ))
    assert res.done_reason in ("length", "stop")
    assert res.eval_count > 0


def test_ragged_dispatch_matches_dense(interpreted_kernels):
    """VERDICT #7: the sorted ragged-dispatch MoE form (prefill) must be
    numerically equivalent to the dense all-experts form — exact routing,
    no capacity drops — across token counts around the dispatch threshold;
    and so must the grouped form (PR 53: the kernel, interpreted)."""
    import numpy as np

    from gridllm_tpu.models.mixtral import (
        _moe_mlp_dense,
        _moe_mlp_grouped,
        _moe_mlp_ragged,
        init_params,
    )


    cfg = get_config("tiny-mixtral")
    params = init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 slice
    for t in (16, 33, 128):
        x = jax.random.normal(jax.random.PRNGKey(t), (1, t, cfg.hidden_size))
        route = mixtral._route(cfg, lp, x)
        dense = _moe_mlp_dense(cfg, lp, x, *route)
        ragged = _moe_mlp_ragged(cfg, lp, x, *route)
        np.testing.assert_allclose(
            np.asarray(ragged), np.asarray(dense), rtol=2e-5, atol=2e-5,
        )
        grouped = _moe_mlp_grouped(cfg, lp, x, *route, None)
        np.testing.assert_allclose(
            np.asarray(grouped), np.asarray(dense), rtol=2e-5, atol=2e-5,
        )
    # on one chip a verify launch's rows take it, a chunk's its sorted regime
    assert mixtral.expert_form(cfg, 80, backend="tpu") == "grouped"
    assert mixtral.expert_form(cfg, 528, backend="tpu") == "grouped_sorted"
    assert mixtral.expert_form(cfg, 80) == "all_experts"


@pytest.mark.parametrize("lead,dtype", [
    ((1, 33), jnp.float32), ((4, 5), jnp.float32), ((40,), jnp.float32),
    ((1, 33), jnp.bfloat16)])
def test_the_sorted_regime_through_moe_mlp_matches_dense(
        lead, dtype, monkeypatch, interpreted_kernels):
    """PR 58: `_moe_mlp` whole, in the layouts the step programs give it
    (a chunk's [1, T, E], a verify launch's [S, T, E], flat rows), with
    `expert_form` picking the grouped kernel's sorted regime: the
    all-experts form's output on the live rows, zeros on the others, the
    same statistics; in float32 to float32's bound (the weighting and the
    sums over a row's picks are float32)."""
    from functools import partial

    cfg = get_config("tiny-mixtral")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(3), dtype=dtype)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(7), (*lead, cfg.hidden_size)
                          ).astype(dtype)
    live = (jnp.arange(x.size // cfg.hidden_size) % 4 != 2).reshape(lead)
    dense, want_stats = mixtral._moe_mlp(cfg, None, live, lp, x)
    monkeypatch.setattr(mixtral, "_SORTED_MIN_ROWS", 0)
    monkeypatch.setattr(mixtral, "expert_form",
                        partial(mixtral.expert_form, backend="tpu"))
    assert mixtral.expert_form(cfg, live.size) == "grouped_sorted"
    got, stats = mixtral._moe_mlp(cfg, None, live, lp, x)
    assert got.shape == x.shape and got.dtype == x.dtype
    assert np.array_equal(np.asarray(stats), np.asarray(want_stats))
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got, np.float32)[on],
                               np.asarray(dense, np.float32)[on],
                               rtol=tol, atol=tol)
    assert not np.asarray(got, np.float32)[~on].any()


def test_ragged_dispatch_through_full_model(monkeypatch):
    """Force the ragged MoE form on CPU and check the full prefill+decode
    engine path matches the dense form token-for-token (greedy)."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine

    opts = {"temperature": 0.0, "num_predict": 6}
    kw = dict(model="tiny-mixtral", max_slots=2, page_size=8, num_pages=32,
              max_pages_per_slot=8, prefill_buckets=(32,), seed=0)
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "1")
    ragged = InferenceEngine(EngineConfig(**kw)).generate(
        GenerationRequest(id="r", prompt="hello world test", options=opts))
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "0")
    dense = InferenceEngine(EngineConfig(**kw)).generate(
        GenerationRequest(id="d", prompt="hello world test", options=opts))
    assert ragged.token_ids == dense.token_ids


def test_meshed_ep_ragged_matches_dense(monkeypatch):
    """VERDICT r03 #7: under a mesh the MoE must not pay the 4× dense tax.
    The shard_map EP ragged dispatch must match the dense all-experts form
    numerically (fp32, 8-device CPU mesh with ep=2 × tp=2)."""
    import numpy as np
    from gridllm_tpu.models import mixtral
    from gridllm_tpu.models.configs import get_config
    from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh
    from gridllm_tpu.parallel.sharding import shard_params

    cfg = get_config("tiny-mixtral")
    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2))
    params = mixtral.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "we_gate", "we_up", "we_down")}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, cfg.hidden_size),
                          jnp.float32)

    dense = mixtral._moe_mlp_dense(cfg, lp, x, *mixtral._route(cfg, lp, x))
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "1")
    with mesh:
        ragged = mixtral._moe_mlp(cfg, mesh, None, lp, x)[0]
    np.testing.assert_allclose(
        np.asarray(ragged), np.asarray(dense), rtol=2e-4, atol=2e-4,
    )


def test_meshed_moe_selects_ragged_for_prefill(monkeypatch):
    """Gate logic: meshed + prefill-sized tokens + divisible layout +
    ragged enabled → the EP shard_map path (not dense)."""
    from unittest import mock
    from gridllm_tpu.models import mixtral
    from gridllm_tpu.models.configs import get_config
    from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = get_config("tiny-mixtral")
    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2))
    params = mixtral.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in ("router", "we_gate", "we_up", "we_down")}
    x = jnp.zeros((1, 32, cfg.hidden_size), jnp.float32)
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "1")
    with mock.patch.object(
        mixtral, "_moe_mlp_ragged_ep", wraps=mixtral._moe_mlp_ragged_ep
    ) as spy:
        with mesh:
            mixtral._moe_mlp(cfg, mesh, None, lp, x)
        assert spy.called
    # decode-sized batch stays dense under the mesh
    xs = jnp.zeros((4, cfg.hidden_size), jnp.float32)
    with mock.patch.object(mixtral, "_moe_mlp_ragged_ep") as spy2:
        with mesh:
            mixtral._moe_mlp(cfg, mesh, None, lp, xs)
        assert not spy2.called


def test_delegation_threads_mesh_to_llama():
    """The engine passes mesh=self.mesh to the family module; mixtral's
    delegation wrappers must forward it to llama or the meshed-kernel
    dispatch (ops.kvcache.kernel_mesh_axis) silently degrades to bare
    pallas_call under GSPMD (review finding, round 5)."""
    from unittest import mock

    from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = get_config("tiny-mixtral")
    mesh = build_mesh(MeshConfig(tp=2, dp=4))
    seen = {}

    def spy_decode(params, c, tokens, cache, active, mlp=None, mesh=None,
                   **kw):
        seen["decode"] = mesh
        raise RuntimeError("stop")

    def spy_chunk(params, c, tokens, start, length, cache, slot, row,
                  mlp=None, mesh=None, embeds=None):
        seen["chunk"] = mesh
        raise RuntimeError("stop")

    with mock.patch.object(mixtral.llama, "decode_step", spy_decode):
        try:
            mixtral.decode_step(None, cfg, None, None, None, mesh=mesh)
        except RuntimeError:
            pass
    with mock.patch.object(mixtral.llama, "prefill_chunk", spy_chunk):
        try:
            # a real chunk: the wrapper marks its live rows for the router
            mixtral.prefill_chunk(None, cfg, jnp.zeros((8,), jnp.int32), None,
                                  jnp.int32(8), None, None, None, mesh=mesh)
        except RuntimeError:
            pass
    assert seen["decode"] is mesh
    assert seen["chunk"] is mesh
