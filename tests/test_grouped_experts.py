"""The routed experts' third form (models/mixtral.py `grouped`, PR 53):
`grouped_experts`, the Pallas product that reads the experts a live row
touched and no others, against its jnp reference and the all-experts
form; its sorted regime for rows past the chip's ridge (`grouped_sorted`,
PR 58: the rows laid out by expert, each expert against its own group);
the rule of the shape; the layer scans that hand both the stacked leaves;
and what Mosaic accepts of the published shapes, without the chip."""

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
    lp = {"router": jax.random.normal(ks[0], (e, cfg.router_width)) * 0.3,
          "we_gate": (jax.random.normal(ks[1], (*lead, held, e, f))
                      * e ** -0.5).astype(dtype),
          "we_up": (jax.random.normal(ks[2], (*lead, held, e, f))
                    * e ** -0.5).astype(dtype),
          "we_down": (jax.random.normal(ks[3], (*lead, held, f, e))
                      * f ** -0.5).astype(dtype)}
    if cfg.router_bias:
        lp["router_bias"] = jax.random.normal(ks[4], (cfg.router_width,))
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
    ("smallthinker:21b", 239, "grouped"),
    ("smallthinker:21b", 240, "grouped_sorted"),
    ("deepseek-v2-lite:16b", 80, "grouped"),
    ("deepseek-v2-lite:16b", 528, "grouped_sorted"),
    ("laguna-xs2:33b", 80, "grouped"), ("laguna-xs2:33b", 528, "grouped_sorted"),
    ("kimi-linear:48b-ep4", 80, "grouped"),
    ("kimi-linear:48b-ep4", 528, "grouped_sorted"),
    ("mixtral:8x7b", 80, "grouped"), ("mixtral:8x7b", 1040, "grouped_sorted"),
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


@pytest.mark.parametrize("regime", ["grouped", "grouped_sorted"])
@pytest.mark.parametrize("model", [
    "tiny-smallthinker", "tiny-mixtral", "tiny-deepseek-v2", "tiny-laguna",
    "tiny-kimi-linear", "tiny-longcat-flash"])
def test_every_familys_layers_hand_the_kernel_their_experts(
        model, regime, monkeypatch, interpreted_kernels):
    """Through each family's own layer loop (llama's scan, DeepSeek-V2's,
    Laguna's periods, Kimi's list of layers, LongCat's blocks), with the
    grouped kernel or its sorted regime at every row count: the hidden
    states are the all-experts form's, and a scanned family's kernel was
    given the stack and an index, not a slice (a slice handed to a custom
    call inside a scan is a copy of it)."""
    from gridllm_tpu.engine.engine import _model_module

    cfg = get_config(model)
    mod = _model_module(cfg)
    params = mod.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 12)))
    want = mod.hidden_states(params, cfg, tokens)
    ranks = []
    name = {"grouped": "grouped_experts",
            "grouped_sorted": "grouped_experts_sorted"}[regime]
    real = getattr(pallas_kernels, name)

    def kernel(x, a, b, wg, *rest, **kw):
        ranks.append(wg.ndim)
        return real(x, a, b, wg, *rest, **kw)

    monkeypatch.setattr(pallas_kernels, name, kernel)
    monkeypatch.setattr(mixtral, "_SORTED_MIN_ROWS",
                        10 ** 6 if regime == "grouped" else 0)
    monkeypatch.setattr(mixtral, "expert_form",
                        partial(mixtral.expert_form, backend="tpu"))
    got = mod.hidden_states(params, cfg, tokens)
    assert float(jnp.abs(got - want).max()) < 5e-5
    assert ranks and set(ranks) <= ({3} if model == "tiny-kimi-linear"
                                    else {3, 4})
    if model in ("tiny-smallthinker", "tiny-mixtral", "tiny-deepseek-v2",
                 "tiny-longcat-flash"):
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


# -- the sorted regime: rows past the chip's ridge (PR 58) ----------------------


def _chunk_live(rows):
    """A mixed launch's rows: a chunk whose tail is padding, then slots of
    which some are not active."""
    r = jnp.arange(rows)
    return (r < rows - 14) | ((r >= rows - 6) & (r % 2 == 0))


SORTED_CASES = {
    # the five cells' shapes at small size (the registry's tiny presets)
    "smallthinker_8_top_3_reglu": dict(model="tiny-smallthinker"),
    "deepseek_v2_8_top_3_not_renormalised": dict(model="tiny-deepseek-v2"),
    "laguna_16_top_4_sigmoid": dict(model="tiny-laguna"),
    "kimi_4_held_of_16_top_4": dict(model="tiny-kimi-linear"),
    "longcat_4_held_of_16_and_8_zero_top_4": dict(model="tiny-longcat-flash"),
    "a_share_with_absent_picks": dict(
        cfg=dict(experts_held=4, experts_first=2)),
    "zero_compute_picks": dict(cfg=dict(zero_experts=4, experts_per_token=3)),
    "rows_not_live_and_chunk_padding": dict(live="chunk"),
    "every_row_live": dict(live="all"),
    "no_row_live": dict(live="none"),
    "one_row_live": dict(live="one"),
    "an_expert_with_an_empty_group": dict(picks="empty"),
    "a_group_of_two_whole_tiles_and_one_of_a_single_row": dict(
        picks="exact", tm=8, live="all"),
    "tiles_of_8": dict(tm=8), "tiles_of_64": dict(tm=64),
    "rows_528": dict(rows=528, live="chunk"),
    "f_tiles": dict(tile_f=128),
    "f_tiles_and_groups_of_several_tiles": dict(tile_f=128, tm=8),
    "a_layer_of_the_stack": dict(layers=3, layer=2),
    "bfloat16": dict(dtype=jnp.bfloat16),
    "bfloat16_f_tiles": dict(dtype=jnp.bfloat16, tile_f=128),
}


def _made_picks(kind, rows, nx):
    """Picks made by hand, two a row: `empty` leaves expert 3 without a
    pick; `exact` gives expert 0 sixteen picks (two tiles of 8 exactly),
    expert 1 one pick and the rest to experts 2.."""
    r = np.arange(rows)
    if kind == "empty":
        a = np.where(r % (nx - 1) >= 3, r % (nx - 1) + 1, r % (nx - 1))
        b = np.where(a == nx - 1, 0, a + 1)
        b = np.where(b == 3, 4, b)
    else:
        a = np.where(r < 16, 0, 2 + r % (nx - 2))
        b = np.where(r == 20, 1, 2 + (r + 1) % (nx - 2))
    return jnp.asarray(np.stack([a, b], axis=1), jnp.int32)


@pytest.mark.parametrize("case", SORTED_CASES)
def test_the_sorted_regime_matches_its_reference_and_the_all_experts_form(
        case, monkeypatch, interpreted_kernels):
    """The sorted regime (interpret mode) against `sorted_experts_ref` on
    the operands the kernel's dispatcher was given and, on the rows that
    are live, against `_moe_mlp_dense`; float32 within float32's bound (the
    sums over F and over a row's picks are float32 sums); rows that are not
    live come back zeros; every group starts at a multiple of the row tile
    and every tile names one expert."""
    c = SORTED_CASES[case]
    cfg = (get_config(c["model"]) if "model" in c
           else dataclasses.replace(BASE, **c.get("cfg", {})))
    rows, dtype = c.get("rows", 40), c.get("dtype", jnp.float32)
    lp = _layer(cfg, dtype=dtype, layers=c.get("layers"))
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.hidden_size)
                          ).astype(dtype)
    top_w, top_i = mixtral._route(cfg, lp, x)
    if "picks" in c:
        top_i = _made_picks(c["picks"], rows, cfg.num_experts)
    live = (_chunk_live(rows) if c.get("live", "some") == "chunk"
            else _live(c.get("live", "some"), rows))
    seen = []
    real = pallas_kernels.grouped_experts_sorted

    def kernel(*a, **kw):
        seen.append((a, kw))
        return real(*a, **kw, **({"tile_f": c["tile_f"]} if "tile_f" in c else {}))

    monkeypatch.setattr(pallas_kernels, "grouped_experts_sorted", kernel)
    if "layers" in c:
        at = {k: v[c["layer"]] if k.startswith("we_") else v
              for k, v in lp.items()}
        lp = {**at, "layer_stack": (lp, jnp.int32(c["layer"]))}
    got = np.asarray(mixtral._moe_mlp_grouped_sorted(
        cfg, lp, x, top_w, top_i, live, c.get("tm")), np.float32)
    (xs, tile_expert, used, wg, wu, wd, li), kw = seen[0]
    held, k, tm = cfg.held_experts[1], cfg.experts_per_token, kw["tm"]
    assert wg.ndim == (4 if "layers" in c else 3) and wg.shape[-3] == held
    # the layout: whole tiles, the static bound, one expert a tile in order
    assert xs.shape[0] % tm == 0
    assert xs.shape[0] == (rows * k + held * (tm - 1)) // tm * tm
    idx = np.asarray(mixtral._held(cfg, top_i)[0] if cfg.routes_elsewhere
                     else top_i)
    on = np.ones(rows, bool) if live is None else np.asarray(live)
    idx = np.where(on[:, None], idx, held)
    sizes = np.bincount(idx.reshape(-1), minlength=held + 1)[:held]
    tiles = -(-sizes // tm)
    assert int(used) == tiles.sum()
    assert np.array_equal(np.asarray(tile_expert)[:int(used)],
                          np.repeat(np.arange(held), tiles))
    want = np.asarray(experts.sorted_experts_ref(
        x, top_w, jnp.asarray(idx), wg, wu, wd, li, act=cfg.expert_act),
        np.float32)
    f32 = dtype == jnp.float32
    rtol, atol = (F32_TOL, F32_TOL) if f32 else (SPEC.rtol, SPEC.atol)
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)
    dense = np.asarray(mixtral._moe_mlp_dense(cfg, lp, x, top_w, top_i),
                       np.float32)
    np.testing.assert_allclose(got[on], dense[on], rtol=rtol, atol=4 * atol)
    assert not got[~on].any()
    # `_touched` (what the counters count) is the experts with a group
    assert np.array_equal(np.asarray(mixtral._touched(cfg, top_i, live)) > 0,
                          sizes > 0)
    if case == "an_expert_with_an_empty_group":
        assert sizes[3] == 0 and 3 not in np.asarray(tile_expert)
    if case == "a_group_of_two_whole_tiles_and_one_of_a_single_row":
        assert sizes[0] == 2 * tm and sizes[1] == 1
    if case == "no_row_live":
        assert int(used) == 0 and not got.any()
    if case in ("a_share_with_absent_picks", "kimi_4_held_of_16_top_4"):
        assert int(mixtral._route_stats(cfg, top_i, live)[3]) > 0
    if case in ("zero_compute_picks", "longcat_4_held_of_16_and_8_zero_top_4"):
        assert int(mixtral._route_stats(cfg, top_i, live)[4]) > 0
    if "f_tiles" in case:
        assert wg.shape[-1] // c["tile_f"] == 2


def test_the_sorted_regime_never_reads_an_expert_without_a_group(
        interpreted_kernels):
    """NaN in every expert that no live row picked reaches nothing, and so
    does NaN in every row that is not live."""
    cfg = BASE
    lp = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.hidden_size))
    top_w, top_i = mixtral._route(cfg, lp, x)
    live = jnp.arange(48) < 3
    touched = np.asarray(mixtral._touched(cfg, top_i, live)) > 0
    assert 0 < touched.sum() < cfg.num_experts
    poisoned = {k: jnp.where(touched.reshape(-1, 1, 1), v, jnp.nan)
                if k.startswith("we_") else v for k, v in lp.items()}
    want = mixtral._moe_mlp_grouped_sorted(cfg, lp, x, top_w, top_i, live)
    got = mixtral._moe_mlp_grouped_sorted(
        cfg, poisoned, jnp.where(live[:, None], x, jnp.nan), top_w, top_i, live)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("rows_a_group,tm", [
    (49.5, 64), (16.5, 32), (8.25, 16), (97.5, 128), (132, 256), (0.3, 16),
    (64, 64), (4000, 256)])
def test_the_row_tile_follows_the_rows_a_group_is_expected_to_have(
        rows_a_group, tm):
    assert experts.sorted_tile_rows(rows_a_group) == tm


@pytest.mark.parametrize("seed", range(4))
def test_the_layout_by_expert_is_a_stable_sort_into_whole_tiles(seed):
    """`sorted_layout` against numpy's stable sort: every pick of a group
    sits in the group's rows in the picks' own order, `src` and `pos` are
    each other's inverse, and a pick in no group sits nowhere."""
    rng = np.random.default_rng(seed)
    groups, tm, n = 6, 8, 90
    idx = rng.integers(0, groups + 1, n)
    idx[idx == 2] = 3                       # group 2 stays empty
    src, pos, tile_group, used = (np.asarray(a) for a in experts.sorted_layout(
        jnp.asarray(idx, jnp.int32), groups, tm))
    assert src.shape == ((n + groups * (tm - 1)) // tm * tm,)
    row = 0
    for g in range(groups):
        mine = np.flatnonzero(idx == g)
        assert np.array_equal(src[row:row + len(mine)], mine)
        assert np.array_equal(pos[mine], row + np.arange(len(mine)))
        tiles = -(-len(mine) // tm)
        assert (src[row + len(mine):row + tiles * tm] == n).all()
        assert (tile_group[row // tm:row // tm + tiles] == g).all()
        row += tiles * tm
    assert used == row // tm and (src[row:] == n).all()
    assert (pos[idx == groups] == 0).all()


@pytest.mark.parametrize("n,groups", [
    (1, 3), (127, 4), (128, 4), (129, 4), (3168, 64), (6336, 16), (4224, 256)])
def test_the_running_count_is_the_cumulative_sum(n, groups):
    """`_running_count` (blocks of 128 rows by a triangular product, the
    blocks' totals carried) is numpy's cumsum, exactly, at the picks of a
    mixed launch (528 rows x top-6, top-12, top-8) and around a block."""
    rng = np.random.default_rng(n)
    hot = (rng.integers(0, groups + 1, n)[:, None] == np.arange(groups)
           ).astype(np.int32)
    got = np.asarray(experts._running_count(jnp.asarray(hot)))
    assert got.dtype == np.int32
    assert np.array_equal(got, np.cumsum(hot, axis=0))


def _routed_names():
    from gridllm_tpu.models.configs import REGISTRY

    return sorted(n for n, c in REGISTRY.items() if c.num_experts)


@pytest.mark.parametrize("name", _routed_names())
def test_the_rule_of_the_shape_across_the_ridge(name, monkeypatch):
    """Every registered routed configuration, walked across the chip's
    ridge: the grouped kernel at 239 rows, its sorted regime from 240 (a
    mixed launch's 528, 1,040) on one TPU chip with kernels allowed;
    elsewhere and when GRIDLLM_MOE_RAGGED forces a form, as before."""
    cfg = get_config(name)
    want = {16: "grouped", 80: "grouped", 239: "grouped",
            240: "grouped_sorted", 528: "grouped_sorted",
            1040: "grouped_sorted"}
    for rows, form in want.items():
        assert mixtral.expert_form(cfg, rows, backend="tpu") == form
        assert mixtral.expert_form(cfg, rows) == "all_experts"
        off = dataclasses.replace(cfg, use_pallas=False)
        assert mixtral.expert_form(off, rows, backend="tpu") == "all_experts"
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "off")
    assert mixtral.expert_form(cfg, 528, backend="tpu") == "all_experts"
    monkeypatch.setenv("GRIDLLM_MOE_RAGGED", "on")
    assert mixtral.expert_form(cfg, 528, backend="tpu") == "sorted"


def test_the_engine_counts_the_sorted_regimes_rows(monkeypatch,
                                                   interpreted_kernels):
    """gridllm_moe_form_rows_total{form="grouped_sorted", launch="chunk"}
    counts every mixed launch's rows (chunk width and slots), the verify
    launches under the ridge stay on the grouped regime, and the sorted
    regime's dispatch is recorded on the kernel's path."""
    from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
    from gridllm_tpu.obs.perf import MOE_FORM_ROWS_TOTAL
    from gridllm_tpu.ops.kvcache import _KERNEL_DISPATCH

    monkeypatch.setattr(mixtral, "_SORTED_MIN_ROWS", 20)
    monkeypatch.setattr(mixtral, "expert_form",
                        partial(mixtral.expert_form, backend="tpu"))
    m = "tiny-smallthinker"
    eng = InferenceEngine(EngineConfig(
        model=m, max_slots=2, page_size=8, num_pages=64, max_pages_per_slot=16,
        prefill_buckets=(16, 32), prefill_chunk=32, seed=0, spec_decode=True))

    def rows(form, launch):
        return MOE_FORM_ROWS_TOTAL.value(model=m, form=form, launch=launch)

    before = {fl: rows(*fl) for fl in [
        ("grouped_sorted", "chunk"), ("all_experts", "chunk"),
        ("grouped", "chunk"), ("grouped", "verify"),
        ("grouped_sorted", "verify")]}
    k0 = _KERNEL_DISPATCH.value(op="grouped_experts_sorted", path="pallas")
    res = eng.generate(GenerationRequest(
        id="s1", prompt="a prompt long enough to be admitted in two chunks",
        options={"temperature": 0.0, "num_predict": 6}))
    assert res.eval_count > 0
    took = {fl: rows(*fl) - n for fl, n in before.items()}
    assert took["grouped_sorted", "chunk"] > 0
    assert took["grouped_sorted", "chunk"] % 2 == 0     # width + 2 slots
    assert took["all_experts", "chunk"] == took["grouped", "chunk"] == 0
    assert took["grouped", "verify"] > 0
    assert took["grouped_sorted", "verify"] == 0
    assert eng._expert_meta("chunk", 34)["expert_form"] == "grouped_sorted"
    assert eng._expert_meta("verify", 10)["expert_form"] == "grouped"
    assert _KERNEL_DISPATCH.value(op="grouped_experts_sorted",
                                  path="pallas") > k0


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


@pytest.mark.parametrize("model,layers,rows", [
    ("smallthinker:21b", 12, 528), ("smallthinker:21b", 12, 1040),
    ("deepseek-v2-lite:16b", 9, 528), ("laguna-xs2:33b", 1, 528),
    ("kimi-linear:48b-ep4", None, 528), ("longcat-flash:560b-ep32", 2, 528),
    ("mixtral:8x7b", 2, 528)])
def test_the_sorted_regime_compiles_for_the_chip_at_the_published_shapes(
        one_chip, model, layers, rows):
    """A mixed launch's 528 rows (512 chunk + 16 slots) through the whole
    regime (the layout by expert and the gathers in XLA, the kernel between
    them with a grid as long as the tiles in use) against the published
    expert shapes, LongCat's 16 held of 6144 x 2048 and Mixtral's 4096 x
    14336 in F-tiles: Mosaic takes the row tile the shape gives, the
    traced grid and the VMEM limit, and the custom call carries the name
    the benchmark's readers know."""
    import re

    cfg = get_config(model)
    e, f, held = cfg.hidden_size, cfg.expert_width, cfg.held_experts[1]
    k = cfg.experts_per_token
    lead = () if layers is None else (layers,)
    tm = experts.sorted_tile_rows(rows * k / cfg.router_width)

    def real(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(partial(
        experts.sorted_experts, tm=tm, act=cfg.expert_act, use_pallas=True)
    ).lower(real((rows, e)), real((rows, k), jnp.float32),
            real((rows, k), jnp.int32), real((*lead, held, e, f)),
            real((*lead, held, e, f)), real((*lead, held, f, e)),
            real((), jnp.int32)).compile()
    text = compiled.as_text()
    assert re.search(r"^\s*(ROOT )?%grouped_experts[.\d]* = .*custom-call\(",
                     text, re.M)
    padded = (rows * k + held * (tm - 1)) // tm * tm
    assert f"bf16[{padded},{e}]" in text          # the kernel's rows
    # the stacked leaves go to the kernel as they are: no slab is copied
    assert not re.search(rf"copy\(bf16\[({layers},)?{held},({e},{f}|{f},{e})\]",
                         text)
    assert tm == {"smallthinker:21b": 64 if rows == 528 else 128,
                  "deepseek-v2-lite:16b": 64, "laguna-xs2:33b": 32,
                  "kimi-linear:48b-ep4": 32, "longcat-flash:560b-ep32": 16,
                  "mixtral:8x7b": 256}[model]
