"""Test harness config.

Per SURVEY.md §4: scheduler/gateway tests run against the in-memory fake bus
and fake workers (no TPU, no model); parallelism tests run on a virtual
8-device CPU mesh. The env vars below MUST be set before jax is imported
anywhere in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Keep test compiles fast & deterministic
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

from gridllm_tpu.utils.config import compile_cache_dir  # noqa: E402

# Persistent XLA compilation cache: the suite compiles the SAME tiny-model
# programs (prefill buckets, decode block, verify block, embed) in nearly
# every test process; on the CPU-share-constrained CI/verify box those
# repeat compiles are a large slice of the tier-1 wall clock. The cache is
# keyed by HLO hash (donation/aliasing included), so behavior is
# unchanged — and the jit TRIPWIRE (obs/perf.py) counts python-side
# signatures, not XLA compiles, so its tests are unaffected. Same rule as
# the engine's ensure_compile_cache: JAX_COMPILATION_CACHE_DIR if the
# environment sets it (jax holds it already), else the checkout's one.
if jax.config.jax_compilation_cache_dir is None:
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import asyncio  # noqa: E402

import pytest  # noqa: E402

# Lock-discipline sanitizer (ISSUE 8): GRIDLLM_SANITIZE=1 swaps the
# threading.Lock/RLock factories for instrumented proxies BEFORE any test
# module builds an engine/scheduler, so every lock those construct joins
# the lock-order graph. The session hook below fails the run on cycles.
# The shared-state sanitizer (ISSUE 13) rides the same switch: scheduler/
# registry/allocator register their hot state for cross-thread
# unguarded-write tracking, judged at session end alongside the graph.
from gridllm_tpu.analysis import lockcheck, numcheck, statecheck  # noqa: E402

if lockcheck.enabled():
    lockcheck.install()


def pytest_sessionfinish(session, exitstatus):
    if not (lockcheck.enabled() and lockcheck.installed()):
        return
    cycles = lockcheck.cycles()
    if cycles:
        lines = "\n  ".join(" -> ".join(c) for c in cycles)
        print(f"\nGRIDLLM_SANITIZE: lock-order cycle(s) observed:\n  {lines}")
        pytest.exit("lock-order cycle detected by the sanitizer",
                    returncode=3)
    edges = lockcheck.edges()
    print(f"\nGRIDLLM_SANITIZE: lock-order graph acyclic "
          f"({len(edges)} distinct edges observed)")
    state = statecheck.report()
    if not state["ok"]:
        lines = "\n  ".join(
            f"{v['object']}.{v['attr']}: {v['threads']} threads, no "
            f"common lock — " + "; ".join(v["sites"])
            for v in state["violations"])
        print(f"\nGRIDLLM_SANITIZE: cross-thread unguarded shared-state "
              f"mutation:\n  {lines}")
        pytest.exit("shared-state violation detected by the sanitizer",
                    returncode=3)
    print(f"GRIDLLM_SANITIZE: shared-state writes clean "
          f"({state['observed_attrs']} tracked attrs, "
          f"{state['tracked_objects']} live objects)")
    # numerics sanitizer (gridcheck v3): shadowed kernel dispatches must
    # stay inside the KERNELS-registry tolerances and tripwired arrays
    # finite — same exit-3 contract as the two checks above
    num = numcheck.report()
    if not num["ok"]:
        lines = "\n  ".join(
            f"{v['op']}: {v['kind']} " + (
                f"excess {v['excess']:.3e} (max err {v['max_err']:.3e}, "
                f"rtol={v['rtol']} atol={v['atol']})"
                if v["kind"] == "tolerance"
                else f"{v['bad_elements']} non-finite elements")
            for v in num["violations"])
        print(f"\nGRIDLLM_SANITIZE: kernel numerics violation(s):\n  {lines}")
        pytest.exit("numerics violation detected by the sanitizer",
                    returncode=3)
    print(f"GRIDLLM_SANITIZE: kernel numerics clean "
          f"({num['shadowed_dispatches']} shadowed dispatches, "
          f"{num['finite_checks']} finite tripwires)")


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """GRIDLLM_PALLAS=interpret for one test. ops/kvcache.py resolves the
    policy once a process (`_env_mode`), so what it remembered is dropped
    before the test and after it."""
    from gridllm_tpu.ops.kvcache import _env_mode

    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    _env_mode.cache_clear()
    yield
    _env_mode.cache_clear()


@pytest.fixture
def event_loop_policy():
    return asyncio.DefaultEventLoopPolicy()


# Minimal asyncio test support without pytest-asyncio: run `async def` tests.
def pytest_pyfunc_call(pyfuncitem):
    import inspect

    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        sig = inspect.signature(fn)
        kwargs = {k: v for k, v in pyfuncitem.funcargs.items() if k in sig.parameters}
        asyncio.run(fn(**kwargs))
        return True
    return None
