"""Numerics-sanitizer tests (gridcheck v3, ISSUE 14).

The sanitizer must (1) pass kernel outputs inside the registry
tolerance, (2) fail ones outside it — including a real dispatcher whose
kernel is deliberately skewed, the exit-3 acceptance fixture — (3) trip
on NaN/Inf, (4) sample deterministically under seeding, and (5) cost
nothing when disabled. Tests that deliberately trip the sanitizer reset
it afterwards so a GRIDLLM_SANITIZE=1 session's end-of-run verdict
(tests/conftest.py) stays clean.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.analysis import numcheck


@pytest.fixture
def armed():
    numcheck.reset()
    numcheck.configure(sample=1.0, seed=0, armed=True)
    yield
    numcheck.reset()
    numcheck.reload_from_env()  # exact restore of the session's policy


def test_shadow_within_tolerance_is_clean(armed):
    @jax.jit
    def f(x):
        return numcheck.shadow(
            "attention_prefill", x, lambda: x + 1e-4)

    jax.block_until_ready(f(jnp.ones((4, 4))))
    assert numcheck.violations() == []
    assert numcheck.report()["shadowed_dispatches"] == 1


def test_shadow_past_tolerance_records_violation(armed):
    @jax.jit
    def f(x):
        return numcheck.shadow("attention_ragged", x, lambda: x + 0.5)

    jax.block_until_ready(f(jnp.ones((4,))))
    v = numcheck.violations()
    assert len(v) == 1 and v[0]["kind"] == "tolerance"
    assert v[0]["op"] == "attention_ragged"
    assert v[0]["excess"] > 0 and v[0]["max_err"] == pytest.approx(0.5)
    with pytest.raises(numcheck.NumericsError):
        numcheck.assert_clean()


def test_shadow_honors_validity_mask(armed):
    # the mismatch sits entirely in the masked (unspecified) region
    @jax.jit
    def f(x):
        bad_ref = x.at[2].add(100.0)
        return numcheck.shadow(
            "attention_prefill", x, lambda: bad_ref,
            valid=jnp.array([True, True, False]))

    jax.block_until_ready(f(jnp.zeros((3,))))
    assert numcheck.violations() == []


def test_shadow_tuple_output_with_none_members(armed):
    # the ragged dispatcher's shape: (chunk, group), either may be None
    @jax.jit
    def f(x):
        out = (None, x)
        return numcheck.shadow(
            "attention_ragged", out, lambda: (None, x + 0.2),
            valid=(None, None))[1]

    jax.block_until_ready(f(jnp.ones((2, 2))))
    v = numcheck.violations()
    assert len(v) == 1 and v[0]["op"] == "attention_ragged"


def test_shadow_flags_nan_in_valid_region(armed):
    # NaN excess must COUNT as a violation (`x > 0` is False for NaN):
    # a kernel going non-finite where the reference is finite is the
    # exact failure mode the shadow exists to catch
    @jax.jit
    def f(x):
        return numcheck.shadow(
            "attention_prefill", x.at[0].set(jnp.nan), lambda: x)

    jax.block_until_ready(f(jnp.ones((3,))))
    v = numcheck.violations()
    assert len(v) == 1 and v[0]["kind"] == "tolerance", v


def test_nan_tripwire(armed):
    @jax.jit
    def f(x):
        numcheck.check_finite("sampler.logits", x)
        return x * 2

    jax.block_until_ready(f(jnp.ones((3,))))
    assert numcheck.violations() == []
    jax.block_until_ready(f(jnp.array([1.0, jnp.nan, jnp.inf])))
    v = numcheck.violations()
    assert len(v) == 1 and v[0]["kind"] == "nonfinite"
    assert v[0]["op"] == "sampler.logits" and v[0]["bad_elements"] == 2


def test_finite_tripwire_skips_integer_arrays(armed):
    numcheck.check_finite("kv.write", jnp.ones((2,), jnp.int32))
    assert numcheck.report()["finite_checks"] == 0


def test_sampling_determinism_under_seeding():
    try:
        numcheck.configure(sample=0.3, seed=1234, armed=True)
        first = [numcheck._decide("attention_ragged") for _ in range(64)]
        numcheck.configure(sample=0.3, seed=1234)
        again = [numcheck._decide("attention_ragged") for _ in range(64)]
        assert first == again
        # a different op draws an independent stream from the same seed,
        # and a different seed changes the sequence
        numcheck.configure(sample=0.3, seed=1234)
        other_op = [numcheck._decide("attention_prefill") for _ in range(64)]
        numcheck.configure(sample=0.3, seed=4321)
        other_seed = [numcheck._decide("attention_ragged") for _ in range(64)]
        assert first != other_op
        assert first != other_seed
    finally:
        # a mid-test failure must not leak the armed/sample override into
        # later tests (conftest judges the session on numcheck state)
        numcheck.reset()
        numcheck.reload_from_env()


def test_disabled_is_a_noop(armed):
    numcheck.configure(armed=False)

    def exploding_ref():
        raise AssertionError("reference must not be traced when disabled")

    x = jnp.ones((2,))
    out = numcheck.shadow("attention_prefill", x, exploding_ref)
    assert out is x
    numcheck.check_finite("kv.write", jnp.array([jnp.nan]))
    rep = numcheck.report()
    assert rep["violations"] == []
    assert rep["shadowed_dispatches"] == 0 and rep["finite_checks"] == 0


def test_skewed_kernel_trips_through_real_dispatcher(armed, monkeypatch):
    """The acceptance fixture: a kernel deliberately skewed past the
    registry tolerance is caught by the shadow on the REAL dispatch
    path (ops.attention.attention_prefill, kernels on)."""
    from gridllm_tpu.ops import attention, kvcache, pallas_kernels

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()

    def skewed(q, k, v, seq_lens, **kw):
        return attention.attention_prefill_ref(q, k, v, seq_lens) + 1.0

    monkeypatch.setattr(pallas_kernels, "flash_prefill", skewed)
    try:
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (1, 8, 4, 16), jnp.float32)
        k = jax.random.normal(key, (1, 8, 2, 16), jnp.float32)
        v = jax.random.normal(key, (1, 8, 2, 16), jnp.float32)
        out = attention.attention_prefill(q, k, v,
                                          jnp.asarray([8], jnp.int32))
        jax.block_until_ready(out)
    finally:
        kvcache._env_mode.cache_clear()
    v_ = numcheck.violations()
    assert any(x["kind"] == "tolerance" and x["op"] == "attention_prefill"
               for x in v_), v_


def test_unskewed_kernel_is_clean_through_real_dispatcher(armed,
                                                         monkeypatch):
    from gridllm_tpu.ops import attention, kvcache

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()
    try:
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (1, 8, 4, 16), jnp.float32)
        k = jax.random.normal(key, (1, 8, 2, 16), jnp.float32)
        v = jax.random.normal(key, (1, 8, 2, 16), jnp.float32)
        out = attention.attention_prefill(q, k, v,
                                          jnp.asarray([6], jnp.int32))
        jax.block_until_ready(out)
    finally:
        kvcache._env_mode.cache_clear()
    assert numcheck.violations() == []
    assert numcheck.report()["shadowed_dispatches"] >= 1


def test_engine_serving_path_is_shadow_covered(armed, monkeypatch):
    """Coverage gate for the numcheck-smoke CI job: a REAL engine serving
    greedy tokens with interpret-mode kernels must shadow-execute a
    nonzero number of kernel dispatches (sampling 1.0) and come out
    clean — without this assertion the gate could go green with zero
    shadow coverage (kernels silently off, suites bypassing the
    dispatchers)."""
    from gridllm_tpu.engine import (EngineConfig, GenerationRequest,
                                    InferenceEngine)
    from gridllm_tpu.ops import kvcache

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    kvcache._env_mode.cache_clear()
    try:
        eng = InferenceEngine(EngineConfig(
            model="tiny-llama", max_slots=2, num_pages=64, page_size=8,
            max_pages_per_slot=8, prefill_buckets=(16, 32)))
        res = eng.generate(GenerationRequest(
            id="numcheck-cover", prompt="hello world",
            options={"temperature": 0.0, "num_predict": 8}))
        assert len(res.token_ids) == 8
    finally:
        kvcache._env_mode.cache_clear()
    rep = numcheck.report()
    assert rep["shadowed_dispatches"] > 0, rep
    assert rep["finite_checks"] > 0, rep
    assert rep["ok"], rep["violations"]


def test_tolerance_lookup_matches_registry():
    from gridllm_tpu.ops.kernels import KERNELS, tolerance

    for spec in KERNELS:
        rtol, atol = tolerance(spec.dispatch)
        assert rtol >= spec.rtol and atol >= spec.atol
    with pytest.raises(KeyError):
        tolerance("no_such_op")


def test_violation_reaches_flight_recorder(armed):
    from gridllm_tpu.obs.flightrec import default_flight_recorder

    numcheck.check_finite("kv.write", jnp.array([np.nan], jnp.float32))
    rings = default_flight_recorder().snapshot()["rings"]
    events = [e for e in rings.get("numcheck", [])
              if e.get("event") == "nonfinite"]
    assert events, "numcheck violation should land in the flight recorder"
