"""The routed experts' third form (models/mixtral.py `grouped`, PR 53):
`grouped_experts`, the Pallas product that reads the experts a live row
touched and no others, against its jnp reference and the all-experts
form; the rule of the shape; the layer scans that hand it the stacked
leaves; and what Mosaic accepts of the five published shapes, without the
chip."""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import mixtral
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.ops import experts, kernels, pallas_kernels

BASE = dataclasses.replace(
    get_config("tiny-mixtral"), hidden_size=128, num_experts=8,
    experts_per_token=2, moe_intermediate_size=256)
SPEC = next(k for k in kernels.KERNELS if k.name == "grouped_experts")
# float32 on both sides in another order of summation
F32_TOL = 2e-5


def _layer(cfg, seed=0, dtype=jnp.float32, layers=None):
    e, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    lead = () if layers is None else (layers,)
    lp = {"router": jax.random.normal(ks[0], (e, cfg.num_experts)) * 0.3,
          "we_gate": (jax.random.normal(ks[1], (*lead, held, e, f))
                      * e ** -0.5).astype(dtype),
          "we_up": (jax.random.normal(ks[2], (*lead, held, e, f))
                    * e ** -0.5).astype(dtype),
          "we_down": (jax.random.normal(ks[3], (*lead, held, f, e))
                      * f ** -0.5).astype(dtype)}
    if cfg.router_bias:
        lp["router_bias"] = jax.random.normal(ks[4], (cfg.num_experts,))
    return lp


def _live(kind, rows):
    return {"all": None, "some": jnp.arange(rows) % 3 != 1,
            "none": jnp.zeros((rows,), bool),
            "one": jnp.arange(rows) == 2}[kind]


CASES = {
    "swiglu_renormalised": dict(cfg=dict(expert_act="silu", norm_topk_prob=True)),
    "reglu_not_renormalised": dict(
        cfg=dict(expert_act="relu", norm_topk_prob=False)),
    "sigmoid_with_a_selection_bias": dict(cfg=dict(
        router_score="sigmoid", router_bias=True, routed_scaling_factor=2.5)),
    "a_share_with_absent_picks": dict(
        cfg=dict(experts_held=4, experts_first=2), live="some"),
    "some_rows_not_live": dict(live="some"),
    "one_row_live": dict(live="one"),
    "no_row_live": dict(live="none"),
    "every_expert_touched": dict(cfg=dict(experts_per_token=8)),
    "rows_16": dict(rows=16), "rows_80": dict(rows=80),
    "rows_239": dict(rows=239, live="some"),
    "f_tiles": dict(tile_f=128, live="some"),
    "a_layer_of_the_stack": dict(layers=3, layer=2, live="some"),
    "bfloat16": dict(dtype=jnp.bfloat16, live="some"),
}


@pytest.mark.parametrize("case", CASES)
def test_grouped_experts_matches_its_reference_and_the_all_experts_form(
        case, monkeypatch, interpreted_kernels):
    """The kernel (interpret mode) against `grouped_experts_ref` and, on
    the rows that are live, against `_moe_mlp_dense`; rows that are not
    live come back zeros; with no row live nothing is touched."""
    c = CASES[case]
    cfg = dataclasses.replace(BASE, **c.get("cfg", {}))
    rows, dtype = c.get("rows", 40), c.get("dtype", jnp.float32)
    lp = _layer(cfg, dtype=dtype, layers=c.get("layers"))
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.hidden_size)
                          ).astype(dtype)
    top_w, top_i = mixtral._route(cfg, lp, x)
    live = _live(c.get("live", "all"), rows)
    touched = mixtral._touched(cfg, top_i, live)
    seen = []
    real = pallas_kernels.grouped_experts

    def kernel(*a, **kw):
        seen.append(a)
        return real(*a, **kw, **({"tile_f": c["tile_f"]} if "tile_f" in c else {}))

    monkeypatch.setattr(pallas_kernels, "grouped_experts", kernel, raising=True)
    if "layers" in c:
        at = {k: v[c["layer"]] if k.startswith("we_") else v
              for k, v in lp.items()}
        lp = {**at, "layer_stack": (lp, jnp.int32(c["layer"]))}
    got = mixtral._moe_mlp_grouped(cfg, lp, x, top_w, top_i, live)
    xk, gates, flags, wg, wu, wd, li = seen[0]
    assert np.array_equal(np.asarray(flags), np.asarray(touched))
    assert wg.ndim == (4 if "layers" in c else 3)
    want = experts.grouped_experts_ref(xk, gates, flags, wg, wu, wd, li,
                                       act=cfg.expert_act)
    f32 = dtype == jnp.float32
    rtol, atol = (F32_TOL, F32_TOL) if f32 else (SPEC.rtol, SPEC.atol)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol, atol=atol)
    dense = np.asarray(mixtral._moe_mlp_dense(cfg, lp, x, top_w, top_i),
                       np.float32)
    on = np.ones(rows, bool) if live is None else np.asarray(live)
    np.testing.assert_allclose(np.asarray(got, np.float32)[on], dense[on],
                               rtol=rtol, atol=4 * atol)
    assert not np.asarray(got, np.float32)[~on].any()
    # what the counter counts is what the kernel was told to read
    assert int(mixtral._route_stats(cfg, top_i, live)[1]) == int(touched.sum())
    if case == "every_expert_touched":
        assert int(touched.sum()) == cfg.num_experts
    if case == "no_row_live":
        assert int(touched.sum()) == 0
    if case == "a_share_with_absent_picks":
        stats = mixtral._route_stats(cfg, top_i, live)
        assert int(stats[3]) > 0 and int(touched.sum()) <= 4


def test_an_untouched_expert_is_never_read(interpreted_kernels):
    """NaN in every expert no live row picked reaches nothing: the kernel
    leaves those slabs where they are (the all-experts form multiplies
    them by a zero gate, and 0 x NaN is NaN)."""
    cfg = BASE
    lp = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.hidden_size))
    top_w, top_i = mixtral._route(cfg, lp, x)
    live = jnp.arange(16) < 2
    touched = np.asarray(mixtral._touched(cfg, top_i, live)) > 0
    assert 0 < touched.sum() < cfg.num_experts
    poisoned = {k: jnp.where(touched.reshape(-1, 1, 1), v, jnp.nan)
                if k.startswith("we_") else v for k, v in lp.items()}
    got = mixtral._moe_mlp_grouped(cfg, poisoned, x, top_w, top_i, live)
    want = mixtral._moe_mlp_grouped(cfg, lp, x, top_w, top_i, live)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.isnan(np.asarray(
        mixtral._moe_mlp_dense(cfg, poisoned, x, top_w, top_i))).any()


@pytest.mark.parametrize("name,rows,tpu", [
    ("smallthinker:21b", 16, "grouped"), ("smallthinker:21b", 80, "grouped"),
    ("smallthinker:21b", 239, "grouped"), ("smallthinker:21b", 240, "all_experts"),
    ("deepseek-v2-lite:16b", 80, "grouped"),
    ("deepseek-v2-lite:16b", 528, "all_experts"),
    ("laguna-xs2:33b", 80, "grouped"), ("laguna-xs2:33b", 528, "sorted"),
    ("kimi-linear:48b-ep4", 80, "grouped"), ("kimi-linear:48b-ep4", 528, "sorted"),
    ("mixtral:8x7b", 80, "grouped"), ("mixtral:8x7b", 1040, "all_experts"),
])
def test_grouped_is_the_form_under_the_ridge_on_one_chip(name, rows, tpu,
                                                         monkeypatch):
    cfg = get_config(name)
    assert mixtral.expert_form(cfg, rows, backend="tpu") == tpu
    # the CPU, kernels refused, a mesh, and the forcing variable: as before
    assert mixtral.expert_form(cfg, rows) == "all_experts"
    off = dataclasses.replace(cfg, use_pallas=False)
    assert mixtral.expert_form(off, rows, backend="tpu") == "all_experts"
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "off")
    assert mixtral.expert_form(cfg, rows, backend="tpu") == "all_experts"
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "on")
    assert mixtral.expert_form(cfg, rows, backend="tpu") == "sorted"


def test_a_mesh_keeps_the_inherited_rule():
    class Mesh:
        shape = {"ep": 2, "tp": 1}

    cfg = get_config("mixtral:8x7b")
    assert mixtral.expert_form(cfg, 80, Mesh(), backend="tpu") == "sorted"
    assert mixtral.expert_form(cfg, 8, Mesh(), backend="tpu") == "all_experts"


@pytest.mark.parametrize("model", [
    "tiny-smallthinker", "tiny-mixtral", "tiny-deepseek-v2", "tiny-laguna",
    "tiny-kimi-linear"])
def test_every_familys_layers_hand_the_kernel_their_experts(
        model, monkeypatch, interpreted_kernels):
    """Through each family's own layer loop (llama's scan, DeepSeek-V2's,
    Laguna's periods, Kimi's list of layers): the hidden states with the
    grouped form are the all-experts form's, and a scanned family's kernel
    was given the stack and an index, not a slice."""
    from gridllm_tpu.engine.engine import _model_module

    cfg = get_config(model)
    mod = _model_module(cfg)
    params = mod.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)))
    want = mod.hidden_states(params, cfg, tokens)
    ranks = []

    def kernel(x, gates, touched, wg, *a, **kw):
        ranks.append(wg.ndim)
        return real(x, gates, touched, wg, *a, **kw)

    real = pallas_kernels.grouped_experts
    monkeypatch.setattr(pallas_kernels, "grouped_experts", kernel)
    monkeypatch.setattr(mixtral, "expert_form",
                        partial(mixtral.expert_form, backend="tpu"))
    got = mod.hidden_states(params, cfg, tokens)
    assert float(jnp.abs(got - want).max()) < 5e-5
    assert ranks and set(ranks) <= ({3} if model == "tiny-kimi-linear"
                                    else {3, 4})
    if model in ("tiny-smallthinker", "tiny-mixtral", "tiny-deepseek-v2"):
        assert set(ranks) == {4}


def test_the_engine_counts_the_grouped_forms_rows(monkeypatch,
                                                  interpreted_kernels):
    """gridllm_moe_form_rows_total{form="grouped", launch="verify"} counts
    every verify launch's rows, the span's meta names the form, and the
    kernel's dispatch is recorded on the kernel's path."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
    from gridllm_tpu.obs.perf import MOE_FORM_ROWS_TOTAL, PHASE_SECONDS
    from gridllm_tpu.ops.kvcache import _KERNEL_DISPATCH

    monkeypatch.setattr(mixtral, "expert_form",
                        partial(mixtral.expert_form, backend="tpu"))
    m = "tiny-smallthinker"
    eng = InferenceEngine(EngineConfig(
        model=m, max_slots=2, page_size=8, num_pages=64, max_pages_per_slot=16,
        prefill_buckets=(16, 32), prefill_chunk=32, seed=0, spec_decode=True))
    labels = dict(model=m, form="grouped", launch="verify")
    r0 = MOE_FORM_ROWS_TOTAL.value(**labels)
    n0 = PHASE_SECONDS.count(model=m, phase="dispatch_verify")
    k0 = _KERNEL_DISPATCH.value(op="grouped_experts", path="pallas")
    res = eng.generate(GenerationRequest(
        id="g1", prompt="which experts does this touch",
        options={"temperature": 0.0, "num_predict": 8}))
    assert res.eval_count > 0
    launches = PHASE_SECONDS.count(model=m, phase="dispatch_verify") - n0
    assert launches > 0
    assert MOE_FORM_ROWS_TOTAL.value(**labels) - r0 == launches * 2 * 5
    assert eng._expert_meta("verify", 10)["expert_form"] == "grouped"
    assert _KERNEL_DISPATCH.value(op="grouped_experts", path="pallas") > k0


# -- what Mosaic accepts of the five published shapes, without the chip ---------


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


@pytest.mark.parametrize("model,layers", [
    ("smallthinker:21b", 12), ("deepseek-v2-lite:16b", 9), ("laguna-xs2:33b", 1),
    ("kimi-linear:48b-ep4", None), ("mixtral:8x7b", 2)])
def test_the_kernel_compiles_for_the_chip_at_the_published_shapes(
        one_chip, model, layers):
    """80 rows (a verify launch's 16 slots x K+1) against the experts of
    64 x 2560 x 768, 64 x 2048 x 1408, 256 x 2048 x 512, 64 held of 256 x
    2304 x 1024 and 8 x 4096 x 14336 (F-tiles: one expert is 352 MB): the
    VMEM limit and the tile are ones Mosaic takes, and the custom call
    carries the name the benchmark's readers know."""
    import re

    cfg = get_config(model)
    e, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    lead = () if layers is None else (layers,)

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(partial(
        pallas_kernels.grouped_experts, act=cfg.expert_act)).lower(
            real((80, e)), real((80, held), jnp.float32),
            real((held,), jnp.int32), real((*lead, held, e, f)),
            real((*lead, held, e, f)), real((*lead, held, f, e)),
            real((), jnp.int32)).compile()
    assert re.search(r"^\s*(ROOT )?%grouped_experts[.\d]* = .*custom-call\(",
                     compiled.as_text(), re.M)
    tile = pallas_kernels._expert_tile(e, f, 2)
    assert tile == (512 if model == "mixtral:8x7b" else f)
