"""Performance-introspection tests (ISSUE 4): recompile tripwire
semantics (steady-state decode is recompile-free; an unseen shape bucket
counts exactly once with the right labels and a flight-recorder event),
device-memory accounting math on the CPU backend, the /admin/memory and
/admin/profile endpoints and the profiler-capture lifecycle."""

import os
import time

import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.obs import (
    CaptureBusy,
    ProfilerCapture,
    default_flight_recorder,
    memory_snapshot,
    register_memory_probe,
    unregister_memory_probe,
)
from gridllm_tpu.obs.perf import RECOMPILES_TOTAL, recompile_totals

TINY = dict(
    model="tiny-llama",
    max_slots=4,
    page_size=8,
    num_pages=64,
    max_pages_per_slot=8,
    prefill_buckets=(16, 32),
    # chunks of 32, a last chunk behind a prefix at 16: two widths of the
    # chunk program, which admits every prompt
    prefill_chunk=32,
    prefill_chunk_narrow=16,
)

OPTS = {"temperature": 0.0, "num_predict": 6}


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine(EngineConfig(**TINY))
    # warm + arm: the first naturally completed request flips the
    # tripwire to steady state (engine._finish)
    eng.generate(GenerationRequest(id="warm", prompt="hi", options=OPTS))
    assert eng.perf.armed
    return eng


# ---------------------------------------------------------------------------
# recompile tripwire
# ---------------------------------------------------------------------------


def test_steady_state_varying_batch_fill_zero_recompiles(engine):
    """Continuous batching varies ACTIVE slots, not shapes: decoding with
    1, 2, and 3 concurrent requests in an already-seen bucket must not
    compile anything new."""
    before = recompile_totals()["steady"]
    done = []
    for n in (1, 2, 3):
        for i in range(n):
            engine.submit(GenerationRequest(
                id=f"fill{n}-{i}", prompt="hi",
                options=OPTS,
                on_chunk=lambda d, fin, res: fin and done.append(res)))
        while len(done) < sum((1, 2, 3)[: (1, 2, 3).index(n) + 1]):
            engine.step()
    assert recompile_totals()["steady"] == before


def test_unseen_shape_bucket_counts_exactly_one(engine):
    """A prompt landing on a chunk width never launched before compiles
    ONE new program: counted under {fn="mixed_chunk", reason="new_shape"}
    with a flight-recorder event carrying the offending shapes."""
    before = RECOMPILES_TOTAL.value(fn="mixed_chunk", reason="new_shape")
    steady_before = recompile_totals()["steady"]
    long_prompt = "x" * 40  # a chunk, and a last chunk at the narrow width
    engine.generate(GenerationRequest(id="bkt", prompt=long_prompt,
                                      options=OPTS))
    assert RECOMPILES_TOTAL.value(
        fn="mixed_chunk", reason="new_shape") == before + 1
    # exactly one steady recompile total — decode/sampler shapes are
    # bucket-independent and must NOT have recompiled
    assert recompile_totals()["steady"] == steady_before + 1
    events = [e for e in default_flight_recorder().snapshot()
              ["rings"].get("engine", [])
              if e["event"] == "recompile"]
    assert events, "steady-state recompile must leave a flight event"
    last = events[-1]
    assert last["fn"] == "mixed_chunk" and last["reason"] == "new_shape"
    assert "16" in last["shapes"]  # the offending padded width

    # repeat of the SAME width: no further count
    engine.generate(GenerationRequest(id="bkt2", prompt="y" * 40,
                                      options=OPTS))
    assert RECOMPILES_TOTAL.value(
        fn="mixed_chunk", reason="new_shape") == before + 1


def test_static_arg_change_classified_new_static(engine):
    """decode_block's fused step count k is a static jit arg — a never-
    seen k recompiles with reason new_static, not new_shape."""
    # baseline signature first: a spec-on engine (ISSUE 5 default) serves
    # via the verify program and never compiles decode_block during
    # warmup, and a probe's very FIRST signature always classifies as
    # warmup — so establish k=1 (a no-op when spec is off: the runner
    # already compiled it) before probing the static change
    engine._dispatch_block(1)
    engine._inflight.clear()   # no slots are active; tokens are junk
    before = RECOMPILES_TOTAL.value(fn="decode_block", reason="new_static")
    engine._dispatch_block(3)  # k=3 never dispatched by these tests
    engine._inflight.clear()
    assert RECOMPILES_TOTAL.value(
        fn="decode_block", reason="new_static") == before + 1


# ---------------------------------------------------------------------------
# device-memory accounting
# ---------------------------------------------------------------------------


def test_memory_snapshot_sums_and_kv_math(engine):
    import jax

    register_memory_probe("test-perf", lambda: {
        "tiny-llama": engine.memory_arrays()})
    try:
        snap = memory_snapshot()
    finally:
        unregister_memory_probe("test-perf")
    # per-device: the three kinds must sum to the measured live total
    # (acceptance: within 5% of reported device memory on CPU)
    assert snap["devices"], "no devices attributed"
    for label, dev in snap["devices"].items():
        total = dev["weightsBytes"] + dev["kvPoolBytes"] + dev["workspaceBytes"]
        assert total == pytest.approx(dev["totalLiveBytes"], rel=0.05)
    m = snap["models"]["tiny-llama"]
    # weights attribution matches the params tree exactly
    params_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        engine.params) if hasattr(a, "nbytes"))
    assert m["weightsBytes"] == params_bytes
    # KV pool bytes = k + v + page table + lengths
    cache = engine.cache
    assert m["kvPoolBytes"] == (cache.k.nbytes + cache.v.nbytes
                                + cache.page_table.nbytes
                                + cache.lengths.nbytes)
    # page accounting closes: used + cached + free == num_pages
    assert (m["pagesUsed"] + m["pagesCached"] + m["pagesFree"]
            == TINY["num_pages"])
    assert m["bytesPerPage"] * TINY["num_pages"] == (
        cache.k.nbytes + cache.v.nbytes)
    # idle engine: nothing live, no fragmentation
    assert m["liveTokens"] == 0 and m["fragmentation"] == 0.0
    # a snapshot leaves nothing behind for the next one to count: walking
    # per-shard Arrays did, and every later figure came out doubled
    register_memory_probe("test-perf", lambda: {
        "tiny-llama": engine.memory_arrays()})
    try:
        again = memory_snapshot()
    finally:
        unregister_memory_probe("test-perf")
    for label, dev in snap["devices"].items():
        for kind in ("weightsBytes", "kvPoolBytes"):
            assert again["devices"][label][kind] == dev[kind]


def test_memory_fragmentation_counts_reserved_capacity(engine):
    """Mid-decode, pages are reserved up to the request's capacity; the
    fragmentation estimate is the not-yet-written share of that."""
    register_memory_probe("test-perf2", lambda: {
        "tiny-llama": engine.memory_arrays()})
    try:
        engine.submit(GenerationRequest(
            id="frag", prompt="hello", options={"temperature": 0.0,
                                                "num_predict": 20}))
        engine.step()  # admit + first decode step
        m = memory_snapshot()["models"]["tiny-llama"]
        assert m["pagesUsed"] > 0
        assert m["liveTokens"] > 0
        assert 0 < m["fragmentation"] < 1
        # drain so the module-scoped engine is idle for later tests
        while engine.step():
            pass
    finally:
        unregister_memory_probe("test-perf2")


async def test_admin_memory_endpoint(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.gateway.app import create_app
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import Config

    from .helpers import fast_config

    bus = InMemoryBus(key_prefix="G:")
    await bus.connect()
    cfg = fast_config()
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    app = create_app(bus, registry, scheduler, Config(scheduler=cfg))
    client = TestClient(TestServer(app))
    await client.start_server()
    register_memory_probe("test-perf3", lambda: {
        "tiny-llama": engine.memory_arrays()})
    try:
        resp = await client.get("/admin/memory")
        assert resp.status == 200
        body = await resp.json()
        assert "tiny-llama" in body["models"]
        dev = next(iter(body["devices"].values()))
        assert dev["weightsBytes"] > 0
        # the gauges render from the same snapshot path
        metrics = await client.get("/metrics")
        text = await metrics.text()
        assert 'gridllm_device_memory_bytes{device="cpu:0",kind="weights"}' \
            in text
    finally:
        unregister_memory_probe("test-perf3")
        await client.close()
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()


# ---------------------------------------------------------------------------
# step-time decomposition
# ---------------------------------------------------------------------------


def test_step_decomposition_histograms_populate():
    """One series decomposes a step: gridllm_engine_phase_seconds. On the
    pipelined block path a served request leaves launches, fetches and
    ingests in equal number, time in each, and host phases that can be
    held against the fetch wait (the host-stall alert's ratio)."""
    from gridllm_tpu.obs.perf import PHASE_SECONDS

    eng = InferenceEngine(EngineConfig(**TINY, spec_decode=False,
                                       decode_block=2, pipeline_depth=2))
    model = "tiny-llama"

    def read(what):
        return {p: getattr(PHASE_SECONDS, what)(model=model, phase=p)
                for p in ("ctl", "admit", "dispatch_prefill",
                          "dispatch_verify", "fetch", "ingest")}

    n0, s0 = read("count"), read("sum")
    eng.start()
    try:
        eng.generate(GenerationRequest(id="dec", prompt="hello",
                                       options={"temperature": 0.0,
                                                "num_predict": 12}))
    finally:
        eng.stop()
    n = {p: v - n0[p] for p, v in read("count").items()}
    s = {p: v - s0[p] for p, v in read("sum").items()}
    assert n["admit"] == 1 and n["dispatch_prefill"] == 1
    # 12 tokens at 2 a block, pipelined 2 deep: at least 6 launches, and
    # every launch but those still in flight at the end was fetched
    assert n["dispatch_verify"] >= 6
    assert n["dispatch_verify"] - 2 <= n["fetch"] == n["ingest"]
    assert all(v > 0 for v in s.values()), s


# ---------------------------------------------------------------------------
# profiler capture
# ---------------------------------------------------------------------------


def _wait_capture_done(prof, timeout=60.0):
    """jax.profiler.stop_trace serializes metadata for EVERY module the
    process ever compiled — after kernel-heavy test files it can take
    tens of seconds (by design it runs in the capture's daemon thread,
    never on the caller). Tests must wait it out, not race it."""
    deadline = time.time() + timeout
    while prof.active is not None and time.time() < deadline:
        time.sleep(0.05)
    assert prof.active is None, "profiler capture never finished flushing"


@pytest.mark.slow  # 3 captures × multi-second stop_trace flushes — the
# tier-1 budget can't afford them; the endpoint and watchdog tests keep
# one capture+flush each in the fast gate
def test_profiler_capture_lifecycle(tmp_path):
    from gridllm_tpu.obs import default_profiler

    # one jax profiler per process: an earlier test's singleton capture
    # (e.g. a watchdog auto-capture) must fully flush before this local
    # manager may start_trace
    _wait_capture_done(default_profiler())
    prof = ProfilerCapture(base_dir=str(tmp_path), keep=2)
    info = prof.capture(0.15, reason="unit test/odd")
    assert info["path"].startswith(str(tmp_path))
    assert os.path.isdir(info["path"])
    assert "/" not in os.path.basename(info["path"]).replace("trace-", "", 1)
    with pytest.raises(CaptureBusy):
        prof.capture(0.1)
    _wait_capture_done(prof)
    assert prof.captures and prof.captures[-1]["path"] == info["path"]
    # the trace actually wrote something (jax profiler plugin dirs)
    assert any(os.scandir(info["path"]))
    # pruning: keep=2 bounds the artifact dir (3 captures total > keep;
    # each flush costs real seconds in a compile-heavy process, so keep
    # the count minimal)
    for _ in range(2):
        prof.capture(0.01)
        _wait_capture_done(prof)
    dirs = [e for e in os.scandir(tmp_path) if e.is_dir()]
    assert len(dirs) <= 2


async def test_admin_profile_endpoint(tmp_path, monkeypatch):
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.gateway.app import create_app
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import Config

    from .helpers import fast_config

    monkeypatch.setenv("GRIDLLM_PROFILE_DIR", str(tmp_path))
    bus = InMemoryBus(key_prefix="G:")
    await bus.connect()
    cfg = fast_config()
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    app = create_app(bus, registry, scheduler, Config(scheduler=cfg))
    client = TestClient(TestServer(app))
    await client.start_server()
    from gridllm_tpu.obs import default_profiler

    # a prior test's (or watchdog auto-) capture may still be flushing
    # the process-global profiler — wait for idle before asserting 200
    _wait_capture_done(default_profiler())
    try:
        resp = await client.post("/admin/profile?seconds=0.2")
        assert resp.status == 200
        body = await resp.json()
        assert body["path"].startswith(str(tmp_path))
        # a second capture while one runs is a 409, not a crash
        resp2 = await client.post("/admin/profile?seconds=0.2")
        assert resp2.status == 409
        resp3 = await client.post("/admin/profile?seconds=nope")
        assert resp3.status == 400
        _wait_capture_done(default_profiler())
    finally:
        await client.close()
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()


def test_watchdog_hang_capture(tmp_path, monkeypatch):
    """The decode-step hang path starts a short capture and attaches the
    artifact path to the diagnosis; profile_on_hang_s=0 disables."""
    from gridllm_tpu.obs import HangWatchdog, MetricsRegistry
    from gridllm_tpu.utils.config import WatchdogConfig

    class _Sched:
        metrics = MetricsRegistry()

    monkeypatch.setenv("GRIDLLM_PROFILE_DIR", str(tmp_path))
    from gridllm_tpu.obs import default_profiler

    _wait_capture_done(default_profiler())
    wd = HangWatchdog(_Sched(), WatchdogConfig(profile_on_hang_s=0.1))
    info = wd._profile_hang("decode-step")
    assert info is not None and info["path"].startswith(str(tmp_path))
    _wait_capture_done(default_profiler())
    wd_off = HangWatchdog(_Sched(), WatchdogConfig(profile_on_hang_s=0))
    assert wd_off._profile_hang("decode-step") is None
