"""Scheduler policy unit tests against the in-memory fake bus + fake workers
(SURVEY.md §4): selection, priority, retries, orphan promotion, liveness,
crash recovery — the behaviors inventoried from JobScheduler.ts/WorkerRegistry.ts."""

import asyncio
import json
import uuid

import pytest

from gridllm_tpu.bus import InMemoryBus
from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
from gridllm_tpu.scheduler.scheduler import JobTimeoutError
from gridllm_tpu.utils.types import InferenceRequest, Priority

from .helpers import FakeWorker, fast_config


def req(model="m1", priority=Priority.medium, **kw) -> InferenceRequest:
    return InferenceRequest(id=f"job-{uuid.uuid4().hex[:8]}", model=model,
                            prompt="hi", priority=priority, **kw)


async def make_stack():
    bus = InMemoryBus(key_prefix="T:")
    await bus.connect()
    cfg = fast_config()
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    return bus, registry, scheduler


async def teardown(bus, registry, scheduler, *workers):
    for w in workers:
        await w.stop(announce=False)
    await scheduler.shutdown()
    await registry.shutdown()
    await bus.disconnect()


async def test_register_and_complete_job():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"])
    await w.start()
    await bus.flush()
    assert registry.get_worker("w1") is not None

    result = await scheduler.submit_and_wait(req(), timeout_ms=3000)
    assert result.success and result.response.response == "canned response"
    assert scheduler.get_stats()["activeJobs"] == 0
    # worker freed again
    assert registry.get_worker("w1").currentJobs == 0
    assert registry.get_worker("w1").totalJobsProcessed == 1
    await teardown(bus, registry, scheduler, w)


async def test_least_loaded_selection():
    bus, registry, scheduler = await make_stack()
    w1 = FakeWorker(bus, "w1", ["m1"], max_concurrent=4, delay_s=0.3)
    w2 = FakeWorker(bus, "w2", ["m1"], max_concurrent=4, delay_s=0.3)
    await w1.start()
    await w2.start()
    await bus.flush()

    results = await asyncio.gather(
        *[scheduler.submit_and_wait(req(), timeout_ms=4000) for _ in range(4)])
    assert all(r.success for r in results)
    # least-loaded spread: both workers got work
    assert len(w1.processed) == 2 and len(w2.processed) == 2
    await teardown(bus, registry, scheduler, w1, w2)


async def test_model_routing():
    bus, registry, scheduler = await make_stack()
    w1 = FakeWorker(bus, "w1", ["llama"], reply="from-llama")
    w2 = FakeWorker(bus, "w2", ["mixtral"], reply="from-mixtral")
    await w1.start()
    await w2.start()
    await bus.flush()

    r1 = await scheduler.submit_and_wait(req(model="llama"), timeout_ms=3000)
    r2 = await scheduler.submit_and_wait(req(model="mixtral"), timeout_ms=3000)
    assert r1.response.response == "from-llama"
    assert r2.response.response == "from-mixtral"
    await teardown(bus, registry, scheduler, w1, w2)


async def test_priority_ordering():
    """With one single-slot worker busy, a later high-priority job must run
    before earlier low-priority jobs."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], delay_s=0.15)
    await w.start()
    await bus.flush()

    order = []

    async def submit(r):
        res = await scheduler.submit_and_wait(r, timeout_ms=8000)
        order.append(r.id)
        return res

    blocker = asyncio.ensure_future(submit(req()))
    await asyncio.sleep(0.05)  # blocker assigned; queue empty
    low1, low2, high = req(priority=Priority.low), req(priority=Priority.low), req(priority=Priority.high)
    tasks = [asyncio.ensure_future(submit(low1)), asyncio.ensure_future(submit(low2))]
    await asyncio.sleep(0.01)
    tasks.append(asyncio.ensure_future(submit(high)))
    await asyncio.gather(blocker, *tasks)
    assert order[1] == high.id, f"high-priority job should run first after blocker, got {order}"
    await teardown(bus, registry, scheduler, w)


async def test_job_queued_until_model_owner_appears():
    bus, registry, scheduler = await make_stack()
    fut = asyncio.ensure_future(scheduler.submit_and_wait(req(model="late"), timeout_ms=5000))
    await asyncio.sleep(0.2)
    assert scheduler.get_stats()["queuedJobs"] == 1
    w = FakeWorker(bus, "w1", ["late"])
    await w.start()
    result = await fut
    assert result.success
    await teardown(bus, registry, scheduler, w)


async def test_retry_then_success_transparent_to_waiter():
    """Failures below the retry limit are invisible to the waiter."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], fail_times=2)  # retry_attempts=2
    await w.start()
    await bus.flush()
    result = await scheduler.submit_and_wait(req(), timeout_ms=5000)
    assert result.success
    assert result.response.response == "canned response"
    await teardown(bus, registry, scheduler, w)


async def test_retries_exhausted_delivers_error():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], fail_times=99)
    await w.start()
    await bus.flush()
    result = await scheduler.submit_and_wait(req(), timeout_ms=5000)
    assert not result.success
    assert "injected failure" in result.error
    r = req()
    r.metadata["retryCount"] = 0
    assert scheduler.total_failed >= 1
    await teardown(bus, registry, scheduler, w)


async def test_orphan_on_worker_death_reassigned():
    """Kill a worker mid-job: the job is promoted to high priority, requeued
    at the front, and completed by a surviving worker — transparently."""
    bus, registry, scheduler = await make_stack()
    slow = FakeWorker(bus, "doomed", ["m1"], delay_s=10)
    await slow.start()
    await bus.flush()

    fut = asyncio.ensure_future(scheduler.submit_and_wait(req(), timeout_ms=8000))
    await asyncio.sleep(0.1)
    assert scheduler.get_stats()["activeJobs"] == 1
    await slow.die()  # abrupt: no unregister, heartbeat TTL gone

    # registry notices via aliveness probe / cleanup; scheduler orphans
    backup = FakeWorker(bus, "backup", ["m1"], reply="rescued")
    await backup.start()
    result = await asyncio.wait_for(fut, 8)
    assert result.success and result.response.response == "rescued"
    assert result.workerId == "backup"
    # audit metadata recorded on the requeued request path
    await teardown(bus, registry, scheduler, slow, backup)


async def test_orphan_metadata_recorded():
    bus, registry, scheduler = await make_stack()
    slow = FakeWorker(bus, "doomed", ["m1"], delay_s=10)
    await slow.start()
    await bus.flush()
    orphaned = []
    scheduler.on("job_orphaned", lambda r: orphaned.append(r))
    fut = asyncio.ensure_future(scheduler.submit_and_wait(req(), timeout_ms=6000))
    await asyncio.sleep(0.1)
    await slow.die()
    await asyncio.sleep(1.0)
    assert len(orphaned) == 1
    r = orphaned[0]
    assert r.metadata["orphaned"] is True
    assert r.metadata["originalWorkerId"] == "doomed"
    assert r.metadata["requeueCount"] == 1
    assert r.priority == Priority.high
    fut.cancel()
    await teardown(bus, registry, scheduler, slow)


async def test_graceful_unregister_removes_worker():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"])
    await w.start()
    await bus.flush()
    assert registry.get_worker("w1") is not None
    await w.stop(announce=True)
    await bus.flush()
    assert registry.get_worker("w1") is None
    await teardown(bus, registry, scheduler)


async def test_heartbeat_timeout_eviction():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"])
    await w.start()
    await bus.flush()
    # stop heartbeating without announcing; TTL key expires (0.4s)
    await w.stop(announce=False)
    await bus.delete("heartbeat:w1")
    await asyncio.sleep(1.0)  # heartbeat timeout 0.6s + cleanup 0.1s
    assert registry.get_worker("w1") is None
    await teardown(bus, registry, scheduler)


async def test_unknown_heartbeat_triggers_reregistration():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "ghost", ["m1"])
    # heartbeat without registering or bus record
    await w.bus.publish("worker:heartbeat", json.dumps(
        {"workerId": "ghost", "status": "online", "currentJobs": 0}))
    reregister_requests = []

    async def spy(ch, m):
        reregister_requests.append(m)

    await bus.subscribe("worker:reregister:ghost", spy)
    await bus.publish("worker:heartbeat", json.dumps(
        {"workerId": "ghost", "status": "online", "currentJobs": 0}))
    await bus.flush()
    assert len(reregister_requests) >= 1
    await teardown(bus, registry, scheduler)


async def test_submit_timeout_and_cancellation():
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], delay_s=10)
    await w.start()
    await bus.flush()
    with pytest.raises(JobTimeoutError):
        await scheduler.submit_and_wait(req(), timeout_ms=300)
    await asyncio.sleep(0.05)
    assert scheduler.get_stats()["activeJobs"] == 0
    assert len(w.cancelled) == 1  # worker received job_cancellation
    await teardown(bus, registry, scheduler, w)


async def test_streaming_job_chunks_in_order():
    bus, registry, scheduler = await make_stack()
    toks = [f"t{i} " for i in range(10)]
    w = FakeWorker(bus, "w1", ["m1"], stream_tokens=toks)
    await w.start()
    await bus.flush()
    got = []

    async def on_chunk(chunk):
        got.append(chunk.response)

    r = req(stream=True)
    result = await scheduler.submit_streaming_job(r, on_chunk, timeout_ms=5000)
    assert result.success
    assert got == toks
    assert result.response.response == "".join(toks)
    await teardown(bus, registry, scheduler, w)


async def test_crash_recovery_reload_from_bus():
    """Server restart: queued + active jobs and workers reload from the bus
    (reference: loadExistingJobs/loadExistingWorkers)."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], delay_s=0.4)
    await w.start()
    await bus.flush()
    # one active + two queued (worker has 1 slot)
    fut1 = asyncio.ensure_future(scheduler.submit_and_wait(req(), timeout_ms=8000))
    await asyncio.sleep(0.1)
    q1, q2 = req(), req()
    await scheduler.add_job(q1)
    await scheduler.add_job(q2)

    # "crash": drop in-memory state, build a new registry+scheduler on same bus
    await scheduler.shutdown()
    await registry.shutdown()
    cfg = fast_config()
    registry2 = WorkerRegistry(bus, cfg)
    scheduler2 = JobScheduler(bus, registry2, cfg)
    await registry2.initialize()
    await scheduler2.initialize()
    assert registry2.get_worker("w1") is not None
    # both queued jobs recovered, eventually processed
    await asyncio.sleep(2.0)
    assert {q1.id, q2.id} <= set(w.processed)
    fut1.cancel()
    await teardown(bus, registry2, scheduler2, w)


async def test_cancel_during_retry_window():
    """A job failed into its retry-delay window must be cancellable (no
    zombie resurrection)."""
    bus = InMemoryBus(key_prefix="T:")
    await bus.connect()
    cfg = fast_config()
    cfg = cfg.model_copy(update={"retry_delay_ms": 1_000})  # wide retry window
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    w = FakeWorker(bus, "w1", ["m1"], fail_times=99)
    await w.start()
    await bus.flush()
    r = req()
    await scheduler.add_job(r)
    await asyncio.sleep(0.2)  # first failure landed; job sits in retry window
    assert r.id in scheduler._retry_handles
    assert await scheduler.cancel_job(r.id) is True
    failures_before = w.fail_times
    await asyncio.sleep(1.2)
    assert w.fail_times == failures_before  # never resurrected
    await teardown(bus, registry, scheduler, w)


async def test_heartbeat_does_not_erase_busy_accounting():
    """A stale heartbeat self-reporting idle must not reopen a full worker."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], delay_s=0.5)
    await w.start()
    await bus.flush()
    fut = asyncio.ensure_future(scheduler.submit_and_wait(req(), timeout_ms=5000))
    await asyncio.sleep(0.1)
    info = registry.get_worker("w1")
    assert info.currentJobs == 1 and info.status == "busy"
    # stale heartbeat claims idle
    await bus.publish("worker:heartbeat", json.dumps(
        {"workerId": "w1", "status": "online", "currentJobs": 0}))
    await bus.flush()
    info = registry.get_worker("w1")
    assert info.currentJobs == 1, "registry accounting must be authoritative"
    assert registry.get_available_workers_by_model("m1") == []
    await fut
    await teardown(bus, registry, scheduler, w)


async def test_non_retryable_failure_fails_fast():
    """retryable=False on job:failed skips the retry ladder entirely —
    the waiter gets the error after ONE attempt (permanent errors like
    generation-on-embedding-model must not burn retry delays)."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], fail_times=99, fail_retryable=False)
    await w.start()
    await bus.flush()
    t0 = asyncio.get_running_loop().time()
    result = await scheduler.submit_and_wait(req(), timeout_ms=5000)
    elapsed = asyncio.get_running_loop().time() - t0
    assert not result.success and "injected failure" in result.error
    assert w.fail_times == 98  # exactly one attempt
    assert elapsed < 2.0       # no retry delays burned
    await teardown(bus, registry, scheduler, w)


async def test_nack_does_not_consume_retry_ladder():
    """VERDICT #8: a capacity NACK requeues without retryCount++ — more
    NACKs than retry_attempts must still end in success once capacity
    frees (the reference burned a retry per NACK; 3 races = permafail)."""
    bus, registry, scheduler = await make_stack()
    # 5 NACKs > retry_attempts=2, then the worker accepts
    w = FakeWorker(bus, "w1", ["m1"], nack_times=5)
    await w.start()
    await bus.flush()

    result = await scheduler.submit_and_wait(req(), timeout_ms=5000)
    assert result.success
    assert scheduler.total_failed == 0
    await teardown(bus, registry, scheduler, w)


async def test_a_job_nacked_back_while_its_pass_still_assigns_stays_queued():
    """A dispatch pass assigns A, then awaits B's assignment; A's capacity
    NACK comes back meanwhile and A is requeued as a new entry. The pass's
    clean-up drops the entries it assigned, not every entry of their jobs:
    A is assigned again and both finish (a burst behind a stalled host lost
    four streams of 230 this way: PERF.md section 6, PR 54)."""
    bus, registry, scheduler = await make_stack()
    w = FakeWorker(bus, "w1", ["m1"], max_concurrent=2, nack_times=1)
    a, b = req(), req()
    assign, calls = scheduler._assign_job, []

    async def assigning(qj, worker, disagg=None):
        calls.append(qj)
        ok = await assign(qj, worker, disagg=disagg)
        if len(calls) == 2:
            # the pass is still open: wait here until A is back in the
            # queue, as a new entry beside the one this pass holds
            first = calls[0]
            for _ in range(400):
                if any(q.request.id == first.request.id and q is not first
                       for q in scheduler.job_queue):
                    break
                await asyncio.sleep(0.005)
            else:
                raise AssertionError("the NACKed job was not requeued")
        return ok

    scheduler._assign_job = assigning
    waiters = [asyncio.ensure_future(scheduler.submit_and_wait(r, timeout_ms=5000))
               for r in (a, b)]
    while len(scheduler.job_queue) < 2:     # both held: no worker yet
        await asyncio.sleep(0.005)
    await w.start()                         # one pass finds both
    results = await asyncio.gather(*waiters)
    assert [r.success for r in results] == [True, True]
    assert [q.request.id for q in calls] == [
        calls[0].request.id, calls[1].request.id, calls[0].request.id]
    assert calls[2] is not calls[0]
    assert sorted(w.processed) == sorted([a.id, b.id])
    assert scheduler.total_failed == 0
    await teardown(bus, registry, scheduler, w)


async def test_layout_tiebreak_discriminates():
    """VERDICT #8: the shard-layout tiebreak must distinguish workers.
    (a) context fit: a request with num_ctx beyond one worker's layout
    routes to the worker whose layout can hold it; (b) slot headroom:
    at equal load, the layout with more batch slots wins."""
    from gridllm_tpu.utils.types import ModelShardLayout

    bus, registry, scheduler = await make_stack()
    small = FakeWorker(bus, "small", ["m1"], layouts=[
        ModelShardLayout(name="m1", maxSeqLen=512, maxBatchSlots=4)])
    big = FakeWorker(bus, "big", ["m1"], layouts=[
        ModelShardLayout(name="m1", strategy="tensor",
                         meshAxes={"tp": 8}, maxSeqLen=8192,
                         maxBatchSlots=16)])
    await small.start()
    await big.start()
    await bus.flush()

    # (a) long-context request → only `big`'s layout fits
    r = await scheduler.submit_and_wait(
        req(options={"num_ctx": 4096}), timeout_ms=3000)
    assert r.success and r.workerId == "big"
    # (b) no ctx hint, equal load → more slot headroom wins
    r = await scheduler.submit_and_wait(req(), timeout_ms=3000)
    assert r.success and r.workerId == "big"
    await teardown(bus, registry, scheduler, small, big)
