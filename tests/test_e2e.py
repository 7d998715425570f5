"""Full-slice e2e: gateway HTTP → scheduler → bus → REAL WorkerService →
InferenceEngine (tiny-llama, byte tokenizer) → streamed back.

This is the rebuild's "minimum end-to-end slice" milestone test
(SURVEY.md §7 step 4) — the reference's equivalent is the differential
integration harness (tests/integration/integration.ts) with Ollama swapped
for the TPU engine.
"""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from gridllm_tpu.bus.memory import InMemoryBus
from gridllm_tpu.engine import EngineConfig, InferenceEngine
from gridllm_tpu.gateway.app import create_app
from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
from gridllm_tpu.utils.config import Config, WorkerConfig
from gridllm_tpu.utils.types import WorkerInfo
from gridllm_tpu.worker.service import WorkerService
from tests.helpers import fast_config

MODEL = "tiny-llama"


@pytest.fixture(scope="module")
def tiny_engine():
    return InferenceEngine(EngineConfig(
        model=MODEL, max_slots=4, page_size=8, num_pages=64,
        max_pages_per_slot=8, prefill_buckets=(16, 32),
    ))


async def _stack(tiny_engine):
    bus = InMemoryBus()
    await bus.connect()
    sched_cfg = fast_config()
    registry = WorkerRegistry(bus, sched_cfg)
    scheduler = JobScheduler(bus, registry, sched_cfg)
    await registry.initialize()
    await scheduler.initialize()
    config = Config()
    config.scheduler = sched_cfg
    app = create_app(bus, registry, scheduler, config)
    worker = WorkerService(
        bus, {MODEL: tiny_engine},
        WorkerConfig(heartbeat_interval_ms=150, resource_monitor_interval_ms=500),
        stream_flush_ms=5,
    )
    await worker.start()
    await asyncio.sleep(0.05)  # registration propagation
    client = TestClient(TestServer(app))
    await client.start_server()
    return bus, registry, scheduler, worker, client


async def _teardown(registry, scheduler, worker, client, bus):
    await client.close()
    await worker.stop()
    await scheduler.shutdown()
    await registry.shutdown()
    await bus.disconnect()


async def test_full_slice_generate_chat_embed_stream(tiny_engine):
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        # worker registered with capabilities incl. topology (new fields)
        workers = registry.get_all_workers()
        assert len(workers) == 1
        info: WorkerInfo = workers[0]
        assert info.capabilities.systemResources is not None
        assert info.capabilities.topology is not None
        assert info.capabilities.maxConcurrentTasks == 4

        # --- non-streaming generate
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "hi", "stream": False,
            "options": {"temperature": 0, "num_predict": 6},
        })
        assert resp.status == 200
        body = await resp.json()
        assert body["model"] == MODEL and body["done"] is True
        assert body["eval_count"] == 6
        assert body["total_duration"] > 0 and body["eval_duration"] >= 0
        assert isinstance(body.get("context"), list) and body["context"]

        # --- streaming generate (NDJSON), chunks concatenate to final text
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "stream me",
            "options": {"temperature": 0, "num_predict": 8},
        })
        assert resp.status == 200
        lines = [json.loads(l) for l in (await resp.text()).strip().splitlines()]
        assert lines[-1]["done"] is True
        streamed = "".join(l.get("response", "") for l in lines[:-1])
        # non-streamed equivalent must match (greedy determinism through the
        # whole distributed stack)
        resp2 = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "stream me", "stream": False,
            "options": {"temperature": 0, "num_predict": 8},
        })
        assert streamed == (await resp2.json())["response"]

        # --- chat (structured messages path)
        resp = await client.post("/ollama/api/chat", json={
            "model": MODEL, "stream": False,
            "messages": [{"role": "user", "content": "hello there"}],
            "options": {"temperature": 0, "num_predict": 5},
        })
        assert resp.status == 200
        body = await resp.json()
        assert body["message"]["role"] == "assistant"
        assert body["eval_count"] == 5

        # --- embeddings
        resp = await client.post("/ollama/api/embed", json={
            "model": MODEL, "input": ["alpha", "beta"],
        })
        assert resp.status == 200
        body = await resp.json()
        assert len(body["embeddings"]) == 2
        assert len(body["embeddings"][0]) == 64

        # --- OpenAI chat completions over the same worker
        resp = await client.post("/v1/chat/completions", json={
            "model": MODEL, "stream": False,
            "messages": [{"role": "user", "content": "hey"}],
            "max_tokens": 4, "temperature": 0,
        })
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["role"] == "assistant"
        assert body["usage"]["completion_tokens"] == 4

        # --- /api/tags aggregates engine-backed models
        resp = await client.get("/ollama/api/tags")
        names = [m["name"] for m in (await resp.json())["models"]]
        assert MODEL in names
    finally:
        await _teardown(registry, scheduler, worker, client, bus)


async def test_worker_nacks_over_capacity(tiny_engine):
    """Over-capacity assignment is NACKed (job:failed) instead of silently
    dropped (reference defect WorkerClientService.ts:500-505) and the
    scheduler retries it."""
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        worker.max_concurrent = 0  # force: every assignment is over capacity
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "x", "stream": False,
            "options": {"temperature": 0, "num_predict": 2},
        })
        # scheduler retries (fast_config: 2 attempts) then fails the job
        assert resp.status >= 500
    finally:
        await _teardown(registry, scheduler, worker, client, bus)


async def test_job_cancellation_mid_stream(tiny_engine):
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        # long generation we cancel via DELETE /inference/{id}
        async with client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "cancel me",
            "options": {"temperature": 0, "num_predict": -1},
        }) as resp:
            # read one chunk, then cancel the active job
            await resp.content.readline()
            jobs = scheduler.get_active_jobs()
            assert jobs
            cancel = await client.delete(f"/inference/{jobs[0].jobId}")
            assert cancel.status == 200
        await asyncio.sleep(0.1)
        assert scheduler.get_active_jobs() == []
    finally:
        await _teardown(registry, scheduler, worker, client, bus)


async def test_images_travel_to_engine_and_reject_loudly(tiny_engine):
    """VERDICT missing #5: images must travel the full protocol (gateway →
    scheduler → worker → engine). No vision family exists yet, so a text
    model must reject with a structured per-model error — not drop the
    pixels silently, not crash the worker — on both generate and chat."""
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "what is in this picture?",
            "stream": False, "images": ["aGVsbG8="]})
        text = json.dumps(await resp.json())
        assert "does not support image inputs" in text, text

        resp = await client.post("/ollama/api/chat", json={
            "model": MODEL, "stream": False, "messages": [
                {"role": "user", "content": "describe",
                 "images": ["aGVsbG8="]}]})
        text = json.dumps(await resp.json())
        assert "does not support image inputs" in text, text

        # worker survives: a plain request still serves
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "hello", "stream": False,
            "options": {"num_predict": 4}})
        assert resp.status == 200 and (await resp.json())["done"]
    finally:
        await _teardown(registry, scheduler, worker, client, bus)


async def test_engine_queue_wait_lands_in_the_prefill_spans_meta(tiny_engine):
    """ISSUE 24: inside the worker a request's wait is no longer one lump.
    A request that met a stopped runner waited in the engine's pending
    queue; that wait is in `engine.prefill`'s meta beside engineNs."""
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        tiny_engine.stop()

        async def restart():
            await asyncio.sleep(0.3)
            tiny_engine.start()

        wake = asyncio.create_task(restart())
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "wait for it", "stream": False,
            "options": {"temperature": 0, "num_predict": 4}})
        assert resp.status == 200 and (await resp.json())["done"]
        await wake
        await bus.flush()
        body = await (await client.get(
            f"/admin/trace/{scheduler.tracer.ids()[-1]}")).json()
        span = next(s for s in body["spans"] if s["name"] == "engine.prefill")
        waited = span["meta"]["admitWaitNs"]
        # engineNs starts at admission: the queue wait lies before it, and
        # both lie inside the span (worker submit to first token)
        assert waited >= 0.2e9
        assert (waited + span["meta"]["engineNs"]) / 1e6 <= span["durationMs"] + 5.0
    finally:
        tiny_engine.start()
        await _teardown(registry, scheduler, worker, client, bus)


async def test_critical_path_waits_for_the_workers_half_of_the_trace(tiny_engine):
    """A worker publishes its spans after its result, so the gateway seals
    a request's root span before the engine spans land. The decomposition
    must wait for them: from the gateway's spans alone the whole prefill
    and decode read as `dispatch` (PR 24, seen on the chip)."""
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        cp = scheduler._critical_path
        publish = worker._publish_trace

        async def late(request_id):     # as over a real bus: result first
            await asyncio.sleep(0.2)
            await publish(request_id)

        worker._publish_trace = late
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "decompose me", "stream": False,
            "options": {"temperature": 0, "num_predict": 24}})
        assert resp.status == 200 and (await resp.json())["done"]
        assert cp.count(segment="dispatch") == 0    # sealed, not yet decomposed
        for _ in range(100):
            if cp.count(segment="dispatch"):
                break
            await asyncio.sleep(0.02)
        await bus.flush()
        assert cp.count(segment="dispatch") == 1
        body = await (await client.get(
            f"/admin/trace/{scheduler.tracer.ids()[-1]}")).json()
        spans = {s["name"]: s for s in body["spans"]}
        engine_ms = (spans["engine.prefill"]["durationMs"]
                     + spans["engine.decode"]["durationMs"])
        in_engine = (cp.sum(segment="prefill") + cp.sum(segment="decode_device")
                     + cp.sum(segment="decode_host_stall"))
        assert in_engine * 1e3 == pytest.approx(engine_ms, rel=0.05)
        assert cp.sum(segment="dispatch") < in_engine
    finally:
        await _teardown(registry, scheduler, worker, client, bus)


async def test_metrics_and_trace_through_real_engine(tiny_engine):
    """ISSUE 1 acceptance: after a request served by the REAL engine worker,
    /metrics carries engine token counters, KV page-pool gauges, and
    kernel-dispatch counters, and /admin/trace/{id} returns a stitched
    gateway+worker timeline including the engine stage spans."""
    bus, registry, scheduler, worker, client = await _stack(tiny_engine)
    try:
        resp = await client.post("/ollama/api/generate", json={
            "model": MODEL, "prompt": "observe me",
            "options": {"temperature": 0, "num_predict": 6},
        })
        assert resp.status == 200
        lines = [json.loads(l) for l in (await resp.text()).strip().splitlines()]
        assert lines[-1]["done"] is True
        await bus.flush()

        text = await (await client.get("/metrics")).text()
        # engine token counters (process-global registry)
        assert f'gridllm_engine_tokens_total{{model="{MODEL}",kind="decode"}}' in text
        assert f'gridllm_engine_tokens_total{{model="{MODEL}",kind="prefill"}}' in text
        # KV page-pool gauges: no pages referenced after the request; the
        # prefix cache (ISSUE 3) may retain released pages as reusable, so
        # free + cached must account for the whole pool
        assert f'gridllm_engine_kv_pages_used{{model="{MODEL}"}} 0' in text
        free = cached = None
        for line in text.splitlines():
            if line.startswith(f'gridllm_engine_kv_pages_free{{model="{MODEL}"}}'):
                free = float(line.rsplit(" ", 1)[1])
            elif line.startswith(f'gridllm_engine_kv_pages_cached{{model="{MODEL}"}}'):
                cached = float(line.rsplit(" ", 1)[1])
        assert free is not None and cached is not None
        assert free + cached == 64
        # kernel-vs-jnp dispatch counters (jnp fallback on the CPU backend)
        # the decode plane's op is attention_ragged, the one
        # paged-attention label — it proves the dispatch counters flow
        assert (
            'gridllm_kernel_dispatch_total{op="attention_ragged",path="jnp"}'
            in text
        )
        # engine step/occupancy histograms populated
        assert f'gridllm_engine_step_duration_seconds_count{{model="{MODEL}"}}' in text
        assert f'gridllm_engine_batch_occupancy_count{{model="{MODEL}"}}' in text
        # worker-plane job outcomes
        assert 'gridllm_worker_jobs_total{event="completed"}' in text
        # TTFT histogram fed by the streaming path
        assert f'gridllm_request_ttft_seconds_count{{model="{MODEL}"}} 1' in text

        # the stitched trace: gateway + worker sources, engine stage spans
        ids = scheduler.tracer.ids()
        assert ids
        body = await (await client.get(f"/admin/trace/{ids[-1]}")).json()
        names = [s["name"] for s in body["spans"]]
        for expected in ("gateway.request", "queue.wait", "scheduler.dispatch",
                         "gateway.first_token", "worker.execute",
                         "worker.first_token", "engine.prefill",
                         "engine.decode"):
            assert expected in names, (expected, names)
        assert any(s.startswith("worker:") for s in body["sources"])
        decode = next(s for s in body["spans"] if s["name"] == "engine.decode")
        assert decode["meta"]["tokens"] == 6
        # ISSUE 5: the decode span attributes speculative draft outcomes
        assert "specAccepted" in decode["meta"]
        assert "specProposed" in decode["meta"]
        # no leaked active spans on either side
        assert scheduler.tracer.active_count() == 0
        assert worker.tracer.active_count() == 0
    finally:
        await _teardown(registry, scheduler, worker, client, bus)
