"""Fleet usage attribution + capacity signals (ISSUE 16).

The headline invariant is CONSERVATION of the two-sided usage ledger:
whatever the engines actually spend (the process-global
``gridllm_usage_engine_*`` counters, incremented only after a result's
publishes succeeded) equals what the owning shards attribute to tenants
(the per-scheduler ``gridllm_usage_*`` counters) — per token kind and
per resource, exactly, across a 2-gateway/2-shard fleet with a
SIGKILL-style worker loss mid-decode (the killed attempt must stay
invisible on BOTH sides) and across a disagg prefill→decode handoff
(whose migrated bytes must land on both sides once).

The kill facade here RAISES on publish, unlike test_fault_tolerance's
silent PartitionableBus: a worker whose result publish silently returns
would still count its usage engine-side while the shard never sees the
payload — the raising facade is what a real dead connection does, and
what the worker's publish-then-account ordering is designed for.
"""

from __future__ import annotations

import asyncio
import hashlib
import re
import uuid

import pytest

from gridllm_tpu.bus import InMemoryBus
from gridllm_tpu.controlplane.client import GatewaySubmitter
from gridllm_tpu.controlplane.partition import shard_of
from gridllm_tpu.controlplane.shard import SchedulerShard, wait_for_ownership
from gridllm_tpu.controlplane.status import FleetView, StatusPublisher
from gridllm_tpu.engine import EngineConfig, InferenceEngine
from gridllm_tpu.obs import MetricsRegistry
from gridllm_tpu.obs import usage as usage_mod
from gridllm_tpu.obs.capacity import (
    DemandTracker,
    _scale_hint,
    aggregate_worker_capacity,
    merge_capacity,
)
from gridllm_tpu.obs.usage import (
    TenantLRU,
    UsageAccountant,
    account_engine_usage,
    build_usage,
    engine_usage_totals,
    resolve_tenant,
)
from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
from gridllm_tpu.utils.config import (
    Config,
    ControlPlaneConfig,
    SchedulerConfig,
    WorkerConfig,
)
from gridllm_tpu.utils.types import InferenceRequest
from gridllm_tpu.worker.service import WorkerService

from .helpers import FakeWorker, fast_config

MODEL = "tiny-llama"
PROMPT = "the quick brown fox jumps over the lazy dog " * 2
N_PREDICT = 48
CHAOS_TOKENS = 4


def make_engine(**kw) -> InferenceEngine:
    cfg = dict(
        model=MODEL, max_slots=2, page_size=8, num_pages=96,
        max_pages_per_slot=16, prefill_buckets=(16, 64, 128), seed=42,
        prefill_chunk=16,
    )
    cfg.update(kw)
    return InferenceEngine(EngineConfig(**cfg))


# ------------------------------------------------- tenant resolution + LRU


def test_resolve_tenant_header_hash_anonymous():
    assert resolve_tenant({}) == "anonymous"
    # configured header wins, sanitized to a safe label value
    assert resolve_tenant({"X-GridLLM-Tenant": "acme corp!"}) == "acme_corp_"
    assert resolve_tenant({"x-gridllm-tenant": "a.b:c-d_e"}) == "a.b:c-d_e"
    assert resolve_tenant({"X-GridLLM-Tenant": "t" * 100}) == "t" * 64
    # Authorization fallback: a stable truncated digest, never the key
    auth = "Bearer sk-secret-123"
    digest = hashlib.sha256(auth.encode()).hexdigest()[:12]
    assert resolve_tenant({"Authorization": auth}) == f"key-{digest}"
    assert "sk-secret" not in resolve_tenant({"Authorization": auth})
    # the explicit header beats the Authorization fallback
    assert resolve_tenant({"X-GridLLM-Tenant": "acme",
                           "Authorization": auth}) == "acme"


def test_tenant_lru_bounds_label_cardinality():
    lru = TenantLRU(cap=2)
    assert lru.label("a") == "a"
    assert lru.label("b") == "b"
    # full: a new tenant folds into the overflow bucket...
    assert lru.label("c") == "other"
    # ...while resident tenants keep their own label
    assert lru.label("a") == "a"
    assert lru.label("") == "other"  # anonymous competes like anyone else


def test_build_usage_and_engine_ledger_roundtrip():
    before = engine_usage_totals()
    u = build_usage(tenant="acme", model="m-roundtrip",
                    prompt_tokens=11, output_tokens=7,
                    prefix_saved_tokens=3, spec_wasted_tokens=2,
                    decode_device_s=0.5, kv_page_s=1.25,
                    migrated_bytes=4096)
    assert u["tenant"] == "acme" and u["model"] == "m-roundtrip"
    assert u["promptTokens"] == 11 and u["outputTokens"] == 7
    account_engine_usage(u)
    after = engine_usage_totals()
    # the engine counters are process-global: assert the DIFF, not totals
    assert after["prompt"] - before.get("prompt", 0.0) == 11
    assert after["output"] - before.get("output", 0.0) == 7
    assert after["prefix_saved"] - before.get("prefix_saved", 0.0) == 3
    assert after["spec_wasted"] - before.get("spec_wasted", 0.0) == 2


def test_kv_page_seconds_count_the_pages_a_slot_holds_not_its_table_row():
    """A request's kvPageSeconds is the pages its slot held times the time
    it held them. The table row is padded to max_pages_per_slot (16 here,
    128 in a deployment): counting its width billed every request the
    whole row (PR 45 (d2), repaired in PR 59)."""
    from gridllm_tpu.engine import GenerationRequest

    eng = make_engine()
    res = eng.generate(GenerationRequest(
        id="pages", prompt="hello there",
        options={"temperature": 0.0, "num_predict": 4}))
    held = -(-(res.prompt_eval_count + 4) // 8)     # pages of 8 tokens
    assert held == 2 < 16
    # the slot's seconds are not on the result: bound them by the request's
    seconds = res.total_duration_ns / 1e9
    assert 0.0 < res.kv_page_s <= held * (seconds + 0.05)


def test_usage_accountant_folds_exactly_once_and_snapshots():
    acc = UsageAccountant(MetricsRegistry(), lru_cap=2)
    u = build_usage(tenant="acme", model="m1", prompt_tokens=10,
                    output_tokens=5, decode_device_s=0.25, kv_page_s=0.5,
                    migrated_bytes=128)
    acc.account(u, "completed")
    acc.account(None, "completed")  # no payload → no-op, never a crash
    acc.note_outcome("acme", "m1", "failed")
    acc.account(dict(u, tenant="burst-1"), "completed")
    acc.account(dict(u, tenant="burst-2"), "duplicate")  # LRU full → other
    totals = acc.token_totals()
    assert totals["prompt"] == 30 and totals["output"] == 15
    snap = acc.snapshot()
    cell = snap["tenants"]["acme"]["m1"]
    assert cell["outcomes"] == {"completed": 1, "failed": 1}
    assert cell["migratedBytes"] == 128
    assert cell["seconds"]["decode_device"] == pytest.approx(0.25)
    assert snap["tenants"]["other"]["m1"]["outcomes"]["duplicate"] == 1


# --------------------------------------------------- demand/capacity model


def test_scale_hint_steers_toward_target_utilization():
    # no workers: live demand asks for the first replica
    assert _scale_hint(workers=0, utilization=0.0, arrival_rate=0.0,
                       queue_depth=0) == 0
    assert _scale_hint(workers=0, utilization=0.0, arrival_rate=1.0,
                       queue_depth=0) == 1
    # saturated: ceil(2 * 1.0 / 0.8) = 3 workers needed
    assert _scale_hint(workers=2, utilization=1.0, arrival_rate=5.0,
                       queue_depth=0) == 1
    # a standing queue always asks for at least one more
    assert _scale_hint(workers=2, utilization=0.5, arrival_rate=1.0,
                       queue_depth=3) == 1
    # scale-down never drops below a single replica
    assert _scale_hint(workers=4, utilization=0.0, arrival_rate=0.0,
                       queue_depth=0) == -3


def test_aggregate_worker_capacity_sums_heartbeat_blocks():
    class W:
        def __init__(self, mc):
            self.modelCapacity = mc

    agg = aggregate_worker_capacity([
        W({"m1": {"slotsFree": 1, "slotsTotal": 2, "kvPagesFree": 10}}),
        W({"m1": {"slotsFree": 2, "slotsTotal": 2, "kvPagesFree": 4},
           "m2": {"slotsFree": 1, "slotsTotal": 1, "kvPagesFree": 3}}),
        W(None),  # a worker that advertises nothing contributes nothing
    ])
    assert agg["m1"] == {"slotsFree": 3, "slotsTotal": 4,
                         "kvPagesFree": 14, "workers": 2}
    assert agg["m2"]["workers"] == 1


def test_demand_tracker_snapshot_agrees_with_its_gauges():
    reg = MetricsRegistry()
    queues = {"m1": 2}
    caps = {"m1": {"slotsFree": 1, "slotsTotal": 4, "kvPagesFree": 10,
                   "workers": 2}}
    # an hour-long half-life makes decay negligible inside the test
    t = DemandTracker(reg, halflife_s=3600.0,
                      queue_depths=lambda: queues,
                      worker_capacity=lambda: caps)
    for _ in range(4):
        t.note_arrival("m1")
    t.note_dispatch("m1", 0.5)
    t.note_completion("m1", 2.0)
    m = t.snapshot()["models"]["m1"]
    assert m["queueDepth"] == 2
    assert m["arrivalRate"] > 0 and m["serviceRate"] > 0
    assert m["waitEwmaS"] == pytest.approx(0.5, rel=0.01)
    assert m["serviceEwmaS"] == pytest.approx(2.0, rel=0.01)
    assert m["utilization"] == pytest.approx(0.75, abs=0.01)
    assert m["headroom"] == {"slots": 1, "kvPages": 10}
    assert m["slotsTotal"] == 4 and m["workers"] == 2
    assert m["scaleHint"] >= 1  # standing queue
    # the gauges /metrics renders show the SAME numbers as the JSON
    t._collect()
    assert t._g_queue.value(model="m1") == m["queueDepth"]
    assert t._g_hint.value(model="m1") == m["scaleHint"]
    assert t._g_headroom.value(model="m1", resource="slots") == 1
    assert t._g_headroom.value(model="m1", resource="kv_pages") == 10


def test_merge_capacity_sums_demand_maxes_headroom():
    def snap(arrival, queue, wait, slots_free):
        return {"halflifeS": 60.0, "models": {"m1": {
            "arrivalRate": arrival, "serviceRate": 0.5,
            "queueDepth": queue, "waitEwmaS": wait,
            "headroom": {"slots": slots_free, "kvPages": slots_free * 4},
            "slotsTotal": 4, "workers": 2}}}

    merged = merge_capacity([snap(1.0, 2, 1.0, 1), snap(3.0, 1, 2.0, 2)])
    assert merged["shards"] == 2
    m = merged["models"]["m1"]
    # demand is partitioned across shards → sums
    assert m["arrivalRate"] == 4.0
    assert m["serviceRate"] == 1.0
    assert m["queueDepth"] == 3
    # worker headroom is the SAME workers seen twice → max, never sum
    assert m["headroom"] == {"slots": 2, "kvPages": 8}
    assert m["slotsTotal"] == 4 and m["workers"] == 2
    # arrival-weighted wait: (1.0*1 + 2.0*3) / 4
    assert m["waitEwmaS"] == pytest.approx(1.75, abs=0.01)
    assert m["utilization"] == pytest.approx(0.5, abs=0.01)
    assert "scaleHint" in m


# ------------------------------------------- gateway stamping end to end


async def test_gateway_stamps_tenant_on_success_and_failure_paths():
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.gateway.app import create_app

    bus = InMemoryBus()
    await bus.connect()
    cfg = fast_config()
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    ok_worker = FakeWorker(bus, "w-ok", ["m1"], max_concurrent=4)
    bad_worker = FakeWorker(bus, "w-bad", ["m2"], fail_times=5,
                            fail_retryable=False)
    await ok_worker.start()
    await bad_worker.start()
    config = Config()
    config.scheduler = cfg
    app = create_app(bus, registry, scheduler, config)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await bus.flush()
        # success path: the sanitized header value rides the root span
        resp = await client.post(
            "/inference", json={"model": "m1", "prompt": "x"},
            headers={"X-GridLLM-Tenant": "acme corp!"})
        assert resp.status == 200
        rid = (await resp.json())["id"]
        spans = scheduler.tracer.export(rid)
        root = next(s for s in spans if s["name"] == "gateway.request")
        assert root["meta"]["tenant"] == "acme_corp_"

        # failure path: the Authorization-hash tenant lands in the usage
        # ledger under outcome=failed (no payload — the job never ran)
        auth = "Bearer sk-usage-test"
        tenant = "key-" + hashlib.sha256(auth.encode()).hexdigest()[:12]
        resp = await client.post(
            "/inference", json={"model": "m2", "prompt": "x"},
            headers={"Authorization": auth})
        assert resp.status >= 400
        assert scheduler.usage.requests.value(
            tenant=tenant, model="m2", outcome="failed") == 1

        # /admin/capacity agrees with /metrics on the decay-stable
        # integers (the acceptance criterion's agreement check)
        cap = await (await client.get("/admin/capacity")).json()
        assert cap["shard"]["role"] == "local"
        assert cap["models"]["m1"]["queueDepth"] == 0
        assert cap["models"]["m1"]["arrivalRate"] > 0
        # FakeWorkers advertise no modelCapacity → no workers → live
        # demand asks for the first replica
        assert cap["models"]["m1"]["workers"] == 0
        assert cap["models"]["m1"]["scaleHint"] == 1
        assert cap["usage"]["tenants"][tenant]["m2"]["outcomes"] == {
            "failed": 1}
        text = await (await client.get("/metrics")).text()
        for model in ("m1", "m2"):
            mq = re.search(
                r'gridllm_capacity_queue_depth\{model="%s"\} (\S+)' % model,
                text)
            assert mq, f"no queue-depth gauge rendered for {model}"
            assert float(mq.group(1)) == cap["models"][model]["queueDepth"]
        mh = re.search(
            r'gridllm_capacity_scale_hint\{model="m1"\} (\S+)', text)
        assert mh and float(mh.group(1)) == 1
        assert "gridllm_usage_requests_total" in text
        assert tenant in text  # the tenant label reaches the exposition
    finally:
        await client.close()
        await ok_worker.stop(announce=False)
        await bad_worker.stop(announce=False)
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()


# ------------------------------------------------- conservation helpers


def _engine_token_totals() -> dict[str, float]:
    return dict(engine_usage_totals())


def _engine_seconds_totals() -> dict[str, float]:
    out: dict[str, float] = {}
    for labels, value in usage_mod._ENGINE_SECONDS.items():
        r = dict(labels).get("resource", "")
        out[r] = out.get(r, 0.0) + value
    return out


def _engine_migrated_total() -> float:
    return sum(v for _, v in usage_mod._ENGINE_MIGRATED.items())


def _diff(after: dict[str, float], before: dict[str, float]) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0) > 1e-9}


def _shard_token_totals(schedulers) -> dict[str, float]:
    out: dict[str, float] = {}
    for s in schedulers:
        for kind, v in s.usage.token_totals().items():
            out[kind] = out.get(kind, 0.0) + v
    return out


def _shard_seconds_totals(schedulers) -> dict[str, float]:
    out: dict[str, float] = {}
    for s in schedulers:
        for labels, v in s.usage.seconds.items():
            r = dict(labels)["resource"]
            out[r] = out.get(r, 0.0) + v
    return out


def _shard_migrated(schedulers) -> float:
    return sum(v for s in schedulers for _, v in s.usage.migrated.items())


def _shard_outcomes(schedulers) -> dict[str, int]:
    out: dict[str, int] = {}
    for s in schedulers:
        for labels, v in s.usage.requests.items():
            o = dict(labels)["outcome"]
            out[o] = out.get(o, 0) + int(v)
    return out


class ConnLossBus:
    """Per-worker facade whose death RAISES on every outbound call — a
    torn TCP connection, not a black hole. This matters for the ledger:
    the worker accounts engine-side usage only after its result publish
    SUCCEEDS, so a raising publish keeps the killed attempt invisible on
    both sides of the conservation invariant (a silently-dropping bus
    would let the worker count usage the shard never receives)."""

    def __init__(self, inner):
        self._inner = inner
        self.dead = False

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def publish(self, channel: str, message: str):
        if self.dead:
            raise ConnectionError("bus connection lost")
        return await self._inner.publish(channel, message)

    async def hset(self, key: str, field: str, value: str):
        if self.dead:
            raise ConnectionError("bus connection lost")
        return await self._inner.hset(key, field, value)

    async def set_with_expiry(self, key: str, value: str, ttl_s: float):
        if self.dead:
            raise ConnectionError("bus connection lost")
        return await self._inner.set_with_expiry(key, value, ttl_s)


def _job_for_shard(idx: int, num_shards: int = 2) -> str:
    while True:
        jid = f"job-{uuid.uuid4().hex[:10]}"
        if shard_of(jid, num_shards) == idx:
            return jid


def usage_fleet_config() -> SchedulerConfig:
    """Sub-second liveness (the killed worker must orphan fast) with a
    generous job timeout (first-compile costs)."""
    return SchedulerConfig(
        worker_heartbeat_timeout_ms=600,
        worker_cleanup_interval_ms=100,
        connection_monitor_interval_ms=100,
        quick_disconnect_window_ms=400,
        orphan_assign_threshold_ms=200,
        job_timeout_ms=180_000,
        retry_attempts=3,
        retry_delay_ms=50,
        sweep_interval_ms=100,
    )


async def _settle_outcomes(bus, schedulers, want: int,
                           timeout_s: float = 10.0) -> None:
    """The client sees job:result before the owning shard's job:completed
    handler folds the ledger — wait for the fold, don't race it."""
    for _ in range(int(timeout_s / 0.05)):
        await bus.flush()
        got = _shard_outcomes(schedulers)
        if got.get("completed", 0) + got.get("duplicate", 0) >= want:
            return
        await asyncio.sleep(0.05)
    raise AssertionError(
        f"shards never folded {want} completions: {_shard_outcomes(schedulers)}")


# ------------------------- THE conservation differential (2×2 fleet + kill)


async def test_conservation_two_shard_fleet_with_worker_kill():
    """Acceptance criterion: a 2-gateway/2-shard fleet serves one request
    per partition; the worker serving the shard-0 request is killed
    mid-decode (raising bus). The resumed execution completes on the
    survivor, and the per-tenant shard ledgers sum EXACTLY to the
    engine-side counters — the killed attempt is invisible on both
    sides, per token kind and per resource-second."""
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.gateway.app import create_app

    bus = InMemoryBus()
    await bus.connect()
    cfg = usage_fleet_config()
    shards = []
    for i in range(2):
        reg = WorkerRegistry(bus, cfg)
        sh = SchedulerShard(
            bus, reg, cfg,
            ControlPlaneConfig(mode="gateway", num_shards=2, shard_id=i,
                               lease_ttl_ms=400, renew_interval_ms=80,
                               status_interval_ms=100),
            member_id=f"shard-{i}", settle_s=0.01 + 0.005 * i)
        await reg.initialize()
        await sh.start()
        shards.append(sh)
    assert await wait_for_ownership(shards, 2, timeout_s=5.0)
    gws = []
    for i in range(2):
        reg = WorkerRegistry(bus, cfg, observer=True)
        gw = GatewaySubmitter(bus, reg, cfg, member_id=f"gw-{i}")
        await reg.initialize()
        await gw.initialize()
        gws.append(gw)
    workers: list[WorkerService] = []
    for i in range(2):
        svc = WorkerService(
            ConnLossBus(bus), {MODEL: make_engine()},
            WorkerConfig(worker_id=f"cap-w{i}", heartbeat_interval_ms=150),
            stream_flush_ms=5)
        svc._snap_every = 2
        await svc.start()
        workers.append(svc)
    await asyncio.sleep(0.4)  # first heartbeats land
    tok0 = _engine_token_totals()
    sec0 = _engine_seconds_totals()
    scheds = [sh.scheduler for sh in shards]
    try:
        # capacity signals from REAL heartbeats: both workers advertise
        # per-model slot/KV headroom, every shard's registry sums them
        m = None
        for _ in range(100):
            m = shards[0].scheduler.capacity.snapshot()["models"].get(MODEL)
            if m and m["workers"] == 2:
                break
            await asyncio.sleep(0.05)
        assert m and m["workers"] == 2, m
        assert m["slotsTotal"] == 4  # 2 workers × max_slots=2
        assert m["headroom"]["slots"] == 4 and m["headroom"]["kvPages"] > 0

        async def run(gw, jid: str, chaos=None):
            chunks: list[str] = []

            async def on_chunk(c) -> None:
                chunks.append(c.response)

            req = InferenceRequest(
                id=jid, model=MODEL, prompt=PROMPT, stream=True,
                options={"temperature": 0, "num_predict": N_PREDICT},
                metadata={"requestType": "inference", "tenant": "acme"})
            task = asyncio.create_task(gw.submit_streaming_job(
                req, on_chunk, timeout_ms=120_000))
            if chaos is not None:
                owner = shards[shard_of(jid, 2)].scheduler
                for _ in range(9000):
                    snap = owner._resume_snap.get(jid)
                    if snap is not None and len(snap["tokens"]) >= CHAOS_TOKENS:
                        break
                    await asyncio.sleep(0.01)
                else:
                    raise AssertionError("decode never reached the chaos point")
                await chaos(jid)
            res = await task
            return "".join(chunks), res

        async def kill(jid: str) -> None:
            wid = shards[0].scheduler.active_jobs[jid].workerId
            victim = next(w for w in workers if w.worker_id == wid)
            victim.bus.dead = True  # type: ignore[attr-defined]

        # chaos request on shard 0's partition, clean one on shard 1's
        text0, res0 = await run(gws[0], _job_for_shard(0), chaos=kill)
        assert res0.success, res0.error
        assert text0
        text1, res1 = await run(gws[1], _job_for_shard(1))
        assert res1.success, res1.error

        st0 = shards[0].scheduler
        assert int(st0._jobs_total.value(event="orphaned")) >= 1
        assert int(st0._resume_total.value(event="stamped")) >= 1

        await _settle_outcomes(bus, scheds, want=2)
        outcomes = _shard_outcomes(scheds)
        # exactly the two resolving executions — the killed attempt never
        # published, so there is no duplicate to account
        assert outcomes.get("completed", 0) == 2, outcomes
        assert outcomes.get("duplicate", 0) == 0, outcomes

        # CONSERVATION: engine-side diff == shard-side sums, per kind
        tok_diff = _diff(_engine_token_totals(), tok0)
        assert tok_diff.get("prompt", 0) > 0
        assert tok_diff.get("output", 0) > 0
        shard_tok = _shard_token_totals(scheds)
        for kind in set(tok_diff) | set(shard_tok):
            assert shard_tok.get(kind, 0.0) == pytest.approx(
                tok_diff.get(kind, 0.0)), kind
        sec_diff = _diff(_engine_seconds_totals(), sec0)
        assert sec_diff.get("decode_device", 0) > 0
        shard_sec = _shard_seconds_totals(scheds)
        for resource in set(sec_diff) | set(shard_sec):
            assert shard_sec.get(resource, 0.0) == pytest.approx(
                sec_diff.get(resource, 0.0)), resource
        # attribution: every accounted token belongs to the stamped tenant
        for s in scheds:
            tenants = s.usage.snapshot()["tenants"]
            assert set(tenants) <= {"acme"}, tenants

        # any gateway replica serves the fleet-merged capacity view
        view = FleetView(bus, gws[0].metrics, stale_after_ms=5000)
        await view.start()
        pubs = [StatusPublisher(bus, sh.scheduler, "shard", sh.member_id,
                                100, lease=sh.lease) for sh in shards]
        for p in pubs:
            await p.publish_once()
        await bus.flush()
        app = create_app(bus, gws[0].registry, gws[0], Config(), fleet=view)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            body = await (await client.get("/admin/capacity")).json()
            assert body["shard"]["role"] == "gateway"
            assert body["fleet"]["numShards"] == 2
            assert set(body["fleet"]["perMember"]) == {"shard-0", "shard-1"}
            fm = body["fleet"]["fleet"]["models"][MODEL]
            assert fm["queueDepth"] == 0
            assert fm["arrivalRate"] > 0  # both shards' demand summed
        finally:
            await client.close()
            await view.stop()
    finally:
        for w in workers:
            w.bus.dead = False  # resurrect so teardown can announce/stop
        for w in workers:
            await w.stop(announce=False)
        for gw in gws:
            await gw.shutdown()
            await gw.registry.shutdown()
        for sh in shards:
            await sh.stop()
            await sh.registry.shutdown()
        await bus.disconnect()


# ---------------------------------------- disagg handoff conservation


async def test_disagg_handoff_conserves_and_attributes_migration():
    """Prefill on A, decode on B after a KV migration: the handoff
    itself carries NO usage payload — only the worker that RESOLVES the
    request publishes one, with the imported KV bytes attributed as
    migration cost. Conservation must hold across the handoff, and the
    migrated bytes must appear once on each side of the ledger."""
    bus = InMemoryBus()
    await bus.connect()
    cfg = SchedulerConfig(worker_heartbeat_timeout_ms=60_000,
                          job_timeout_ms=180_000, sweep_interval_ms=200)
    registry = WorkerRegistry(bus, cfg)
    scheduler = JobScheduler(bus, registry, cfg)
    await registry.initialize()
    await scheduler.initialize()
    workers = []
    for i, role in enumerate(["prefill", "decode"]):
        svc = WorkerService(
            bus, {MODEL: make_engine()},
            WorkerConfig(worker_id=f"w-{role}-{i}", role=role,
                         heartbeat_interval_ms=200),
            stream_flush_ms=5)
        await svc.start()
        workers.append(svc)
    await asyncio.sleep(0.5)
    tok0 = _engine_token_totals()
    sec0 = _engine_seconds_totals()
    mig0 = _engine_migrated_total()
    try:
        chunks: list[str] = []

        async def on_chunk(c) -> None:
            chunks.append(c.response)

        req = InferenceRequest(
            id=f"job-{uuid.uuid4().hex[:8]}", model=MODEL, prompt=PROMPT,
            stream=True, options={"temperature": 0, "num_predict": 16},
            metadata={"requestType": "inference", "tenant": "acme"})
        res = await scheduler.submit_streaming_job(req, on_chunk,
                                                   timeout_ms=120_000)
        assert res.success, res.error
        assert res.workerId.startswith("w-decode")
        await _settle_outcomes(bus, [scheduler], want=1)

        mig_diff = _engine_migrated_total() - mig0
        assert mig_diff > 0  # the migration really moved KV bytes
        assert _shard_migrated([scheduler]) == pytest.approx(mig_diff)
        tok_diff = _diff(_engine_token_totals(), tok0)
        shard_tok = scheduler.usage.token_totals()
        for kind in set(tok_diff) | set(shard_tok):
            assert shard_tok.get(kind, 0.0) == pytest.approx(
                tok_diff.get(kind, 0.0)), kind
        sec_diff = _diff(_engine_seconds_totals(), sec0)
        assert sec_diff.get("decode_device", 0) > 0
        assert sec_diff.get("kv_page", 0) > 0
        cell = scheduler.usage.snapshot()["tenants"]["acme"][MODEL]
        assert cell["migratedBytes"] > 0
        assert cell["outcomes"] == {"completed": 1}
    finally:
        for svc in workers:
            await svc.stop(announce=False)
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()
