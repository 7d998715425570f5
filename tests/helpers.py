"""Fake worker speaking the §2.6 bus protocol — scheduling/failover tests
need no TPU and no model (SURVEY.md §4 'rebuild test plan implications')."""

from __future__ import annotations

import asyncio
import json
import time

from gridllm_tpu.bus.base import MessageBus
from gridllm_tpu.utils.config import SchedulerConfig
from gridllm_tpu.utils.types import (
    InferenceResponse,
    JobAssignment,
    JobResult,
    ModelInfo,
    NodeCapabilities,
    StreamChunk,
    WorkerInfo,
    iso_now,
)


def ragged_decode(q, k_pool, v_pool, table, prefix, ps, k_cur, v_cur, **kw):
    """Decode through the ragged entry: a group region with Td = 1 —
    `prefix` cached tokens per slot plus the current token's fresh K/V."""
    from gridllm_tpu.ops.attention import ragged_paged_attention

    _, out = ragged_paged_attention(
        k_pool, v_pool, ps, q_group=q[:, None], page_table=table,
        group_lengths=prefix, k_group=k_cur[:, None], v_group=v_cur[:, None],
        **kw,
    )
    return out[:, 0]


def fetch_waits(eng, seconds: float) -> None:
    """The runner reads `seconds` as its wait at every fetch: held against
    an admission's host time it says whether a launch kept in flight would
    stand in a new request's way (ISSUE 54; a CPU's own timings say
    either)."""
    mark = eng._mark_ingest

    def marking():
        mark()
        return seconds

    eng._mark_ingest = marking


def turns_running_ahead(eng, monkeypatch, turns, window: int = 2):
    """Serve `turns` ((id, prompt, num_predict), ...) one after another
    through the runner thread, greedy, with a drafter that proposes nothing
    the window of quiet launches cut to `window` and the runner's waits at
    a fetch read as nothing, so the speculative runner keeps draftless
    verify launches in flight (ISSUE 54). Returns
    the results in order, and for every verify dispatch the verify
    launches then still to be fetched."""
    import threading

    from gridllm_tpu.engine import GenerationRequest
    from gridllm_tpu.engine import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_AHEAD_AFTER", window)
    monkeypatch.setattr(eng._drafter, "draft", lambda ids, k, slot=None: [])
    fetch_waits(eng, 0.0)   # launches short against an admission
    behind, dispatch = [], eng._dispatch_verify

    def watching(drafts, dlen):
        behind.append(sum(e[3] is not None for e in eng._inflight))
        dispatch(drafts, dlen)

    monkeypatch.setattr(eng, "_dispatch_verify", watching)
    results, done = [], threading.Event()

    def on_chunk(delta, fin, res):
        if fin:
            results.append(res)
            done.set()

    eng.start()
    try:
        for rid, prompt, n in turns:
            done.clear()
            eng.submit(GenerationRequest(
                id=rid, prompt=prompt, on_chunk=on_chunk,
                options={"temperature": 0.0, "num_predict": n}))
            assert done.wait(120), rid
    finally:
        eng.stop()
    return results, behind


def fast_config() -> SchedulerConfig:
    """Sub-second timers so failure-path tests run quickly."""
    return SchedulerConfig(
        worker_heartbeat_timeout_ms=600,
        worker_cleanup_interval_ms=100,
        connection_monitor_interval_ms=100,
        quick_disconnect_window_ms=400,
        orphan_assign_threshold_ms=200,
        job_timeout_ms=5_000,
        retry_attempts=2,
        retry_delay_ms=50,
        sweep_interval_ms=100,
    )


class FakeWorker:
    """Registers, heartbeats, executes canned jobs over the bus protocol."""

    def __init__(self, bus: MessageBus, worker_id: str, models: list[str],
                 max_concurrent: int = 1, heartbeat_interval_s: float = 0.2,
                 reply: str = "canned response", delay_s: float = 0.0,
                 fail_times: int = 0, stream_tokens: list[str] | None = None,
                 fail_retryable: bool = True, nack_times: int = 0,
                 layouts: list | None = None, stream_delay_s: float = 0.0):
        self.bus = bus
        self.worker_id = worker_id
        self.models = models
        self.max_concurrent = max_concurrent
        self.heartbeat_interval_s = heartbeat_interval_s
        self.reply = reply
        self.delay_s = delay_s
        self.fail_times = fail_times
        self.fail_retryable = fail_retryable
        self.nack_times = nack_times
        self.layouts = layouts or []
        self.stream_tokens = stream_tokens
        # inter-token pause for streamed replies: chaos tests kill control-
        # plane components MID-decode, so the stream must span real time
        self.stream_delay_s = stream_delay_s
        self.current_jobs = 0
        self.processed: list[str] = []
        self.cancelled: list[str] = []
        # every job_assignment delivery, in order — the double-assignment
        # detector for the control-plane chaos differentials (ISSUE 15)
        self.assignments: list[str] = []
        self._subs = []
        self._hb_task: asyncio.Task | None = None
        self._running = False

    def _info(self) -> WorkerInfo:
        return WorkerInfo(
            workerId=self.worker_id,
            capabilities=NodeCapabilities(
                workerId=self.worker_id,
                availableModels=[ModelInfo(name=m) for m in self.models],
                maxConcurrentTasks=self.max_concurrent,
                shardLayouts=self.layouts,
            ),
            status="online",
            currentJobs=self.current_jobs,
        )

    async def start(self) -> None:
        self._running = True
        self._subs.append(await self.bus.subscribe(
            f"worker:{self.worker_id}:job", self._on_job_message))
        self._subs.append(await self.bus.subscribe(
            f"worker:reregister:{self.worker_id}", self._on_reregister))
        await self.register()
        self._hb_task = asyncio.create_task(self._heartbeat_loop())

    async def register(self) -> None:
        info = self._info()
        await self.bus.hset("workers", self.worker_id, info.model_dump_json())
        await self.bus.publish("worker:registered", info.model_dump_json())

    async def stop(self, announce: bool = True) -> None:
        """Graceful stop; announce=False simulates abrupt death."""
        self._running = False
        if self._hb_task:
            self._hb_task.cancel()
            self._hb_task = None
        for s in self._subs:
            await s.unsubscribe()
        self._subs.clear()
        if announce:
            await self.bus.publish("worker:unregistered",
                                   json.dumps({"workerId": self.worker_id}))

    async def die(self) -> None:
        """Abrupt death: no unregister, heartbeat key left to expire."""
        await self.stop(announce=False)
        await self.bus.delete(f"heartbeat:{self.worker_id}")

    async def _heartbeat_loop(self) -> None:
        while self._running:
            await self.bus.set_with_expiry(
                f"heartbeat:{self.worker_id}", str(time.time()),
                ttl_s=self.heartbeat_interval_s * 2)
            await self.bus.publish("worker:heartbeat", json.dumps({
                "workerId": self.worker_id,
                "status": "busy" if self.current_jobs >= self.max_concurrent else "online",
                "currentJobs": self.current_jobs,
            }))
            await asyncio.sleep(self.heartbeat_interval_s)

    async def _on_reregister(self, _ch: str, _raw: str) -> None:
        await self.register()

    async def _on_job_message(self, _ch: str, raw: str) -> None:
        msg = json.loads(raw)
        if msg.get("type") == "job_cancellation":
            self.cancelled.append(msg["jobId"])
            return
        if msg.get("type") != "job_assignment":
            return
        assignment = JobAssignment.model_validate(msg["job"])
        self.assignments.append(assignment.jobId)
        if self.nack_times > 0:
            self.nack_times -= 1
            result = JobResult(jobId=assignment.jobId, workerId=self.worker_id,
                               success=False, error="worker at capacity",
                               nack=True)
            asyncio.ensure_future(
                self.bus.publish("job:failed", result.model_dump_json()))
            return
        asyncio.ensure_future(self._execute(assignment))

    async def _execute(self, assignment: JobAssignment) -> None:
        self.current_jobs += 1
        start = time.time()
        job_id = assignment.jobId
        try:
            if self.delay_s:
                await asyncio.sleep(self.delay_s)
            if job_id in self.cancelled:
                return
            if self.fail_times > 0:
                self.fail_times -= 1
                result = JobResult(jobId=job_id, workerId=self.worker_id,
                                   success=False, error="injected failure",
                                   retryable=self.fail_retryable,
                                   processingTimeMs=(time.time() - start) * 1000)
                await self.bus.publish("job:failed", result.model_dump_json())
                return
            if self.stream_tokens is not None and assignment.request.stream:
                offset = 0
                for i, tok in enumerate(self.stream_tokens):
                    if self.stream_delay_s and i:
                        await asyncio.sleep(self.stream_delay_s)
                    await self.bus.publish(f"job:stream:{job_id}", StreamChunk(
                        id=job_id, model=assignment.request.model,
                        created_at=iso_now(), response=tok, done=False,
                        offset=offset,
                    ).model_dump_json())
                    offset += len(tok)
                text = "".join(self.stream_tokens)
            else:
                text = self.reply
            self.processed.append(job_id)
            response = InferenceResponse(
                id=job_id, model=assignment.request.model, created_at=iso_now(),
                response=text, done=True, done_reason="stop",
                eval_count=len(text.split()),
                total_duration=int((time.time() - start) * 1e9),
            )
            result = JobResult(jobId=job_id, workerId=self.worker_id,
                               success=True, response=response,
                               processingTimeMs=(time.time() - start) * 1000)
            await self.bus.publish("job:completed", result.model_dump_json())
            await self.bus.publish(f"job:result:{job_id}", result.model_dump_json())
        finally:
            self.current_jobs -= 1
