"""Worker process entry (``gridllm-worker``).

Reference analogue: client/src/index.ts (WorkerApplication) — health-only
HTTP app + the worker service. Models to serve come from GRIDLLM_MODELS
(comma-separated registry names); checkpoints from GRIDLLM_CHECKPOINT_DIR
({dir}/{name-with-:-replaced-by-_}).
"""

from __future__ import annotations

import asyncio
import os
import platform

from aiohttp import web

import gridllm_tpu
from gridllm_tpu.bus import create_bus
from gridllm_tpu.engine import EngineConfig, InferenceEngine
from gridllm_tpu.parallel.mesh import MeshConfig
from gridllm_tpu.utils.config import Config, env_bool, load_config
from gridllm_tpu.utils.logging import get_logger
from gridllm_tpu.utils.types import iso_now
from gridllm_tpu.worker.capabilities import system_resources
from gridllm_tpu.worker.plan import (
    PlanFollower,
    PlanPublisher,
    plan_channel,
    ready_key,
)
from gridllm_tpu.worker.service import WorkerService

log = get_logger("worker.main")


def resolve_checkpoint(root: str | None, model: str) -> tuple[str | None, str | None]:
    """(checkpoint_path, tokenizer_path) for `model` under a checkpoint
    root: weights at {root}/{name-with-:-replaced-by-_}, tokenizer either
    in a tokenizer/ subdir or alongside the weights. Single source of
    truth."""
    if not root:
        return None, None
    cand = os.path.join(root, model.replace(":", "_"))
    if not os.path.isdir(cand):
        return None, None
    tok_sub = os.path.join(cand, "tokenizer")
    return cand, tok_sub if os.path.isdir(tok_sub) else cand


def _mesh_config(config: Config) -> MeshConfig | None:
    if not config.engine.mesh_shape:
        return None
    axes = dict(
        kv.split(":") for kv in config.engine.mesh_shape.split(",") if kv
    )
    return MeshConfig(**{k: int(v) for k, v in axes.items()})


def pull_engine_factory(config: Config):
    """WorkerService.engine_factory for /api/pull: like build_one_engine
    but REFUSES models whose checkpoint does not resolve — a pull that
    "succeeds" onto random weights would serve gibberish with a success
    status. GRIDLLM_ALLOW_SYNTHETIC_WEIGHTS=1 overrides (test/bench
    deployments that intentionally run synthetic weights)."""

    def factory(name: str) -> InferenceEngine:
        ckpt, _ = resolve_checkpoint(config.engine.checkpoint_dir, name)
        if ckpt is None and not env_bool(
            "GRIDLLM_ALLOW_SYNTHETIC_WEIGHTS"
        ):
            raise ValueError(
                f"no checkpoint for {name!r} under "
                f"{config.engine.checkpoint_dir or '$GRIDLLM_CHECKPOINT_DIR'}"
                " — refusing to serve random weights (set "
                "GRIDLLM_ALLOW_SYNTHETIC_WEIGHTS=1 to override)"
            )
        return build_one_engine(config, name)

    return factory


def build_one_engine(config: Config, name: str,
                     prewarm: bool = False) -> InferenceEngine:
    """Engine for one model under this worker's settings — used at startup
    and by /api/pull load-on-demand (via pull_engine_factory). The KV
    pool is sized by the engine from the device it lands on. `prewarm`
    runs the start-up programs before returning: worker start-up asks
    for it, so a worker registers compiled; a model loaded into a
    running worker (/api/pull, a placement swap-in) does not — there
    the time to the first answer is what is being paid for, and the
    first request compiles what the persistent cache does not hold."""
    ckpt, tok = resolve_checkpoint(config.engine.checkpoint_dir, name)
    buckets = tuple(
        int(b) for b in config.engine.prefill_buckets.split(",") if b
    )
    eng = InferenceEngine(EngineConfig(
        model=name,
        checkpoint_path=ckpt,
        tokenizer=tok,
        dtype=config.engine.dtype,
        max_slots=config.engine.max_batch_slots,
        page_size=config.engine.kv_page_size,
        prefill_buckets=buckets,
        mesh=_mesh_config(config),
    ))
    if prewarm:
        eng.prewarm()
    log.info("engine ready", model=name, checkpoint=ckpt or "random-init",
             kvPages=eng.config.num_pages,
             loadMs=eng.load_duration_ns // 1_000_000,
             prewarmMs=eng.prewarm_duration_ns // 1_000_000)
    return eng


def build_engines(config: Config) -> dict[str, InferenceEngine]:
    names = [m.strip() for m in config.engine.models.split(",") if m.strip()]
    return {name: build_one_engine(config, name, prewarm=True)
            for name in names}


def build_health_app(service: WorkerService) -> web.Application:
    """reference: client/src/routes/health.ts:8-59 + /worker/status
    (client/src/index.ts:75-82)."""
    # client_max_size: the /kvx/ migration route receives whole KV
    # payloads in one POST (aiohttp's 1 MB default would 413 any real
    # transfer — that is exactly the path chosen for LARGE payloads)
    app = web.Application(client_max_size=1024**3)
    started = iso_now()

    async def health(_):
        return web.json_response({
            "status": "healthy", "timestamp": iso_now(),
            "worker": service.worker_id, "version": gridllm_tpu.__version__,
        })

    async def live(_):
        return web.json_response({"status": "alive", "timestamp": iso_now()})

    async def ready(_):
        return web.json_response({"status": "ready", "timestamp": iso_now()})

    async def system(_):
        res = system_resources()
        return web.json_response({
            "status": "ok", "timestamp": iso_now(), "startedAt": started,
            "resources": res.model_dump(), "platform": platform.system().lower(),
        })

    async def status(_):
        return web.json_response({
            "workerId": service.worker_id,
            "status": service._status(),
            "currentJobs": service.current_jobs,
            "totalJobsProcessed": service.total_processed,
            "models": list(service.engines),
        })

    async def metrics(_):
        # the process-global registry carries every worker-plane series:
        # engine tokens/steps/KV pool, kernel-dispatch paths, bus, jobs
        from gridllm_tpu.obs import PROMETHEUS_CONTENT_TYPE, default_registry
        from gridllm_tpu.obs.perf import capture_span

        # the render walks jax.live_arrays() on this event loop (the
        # device-memory collector): a capture names it, so a stream frame
        # that waited behind a scrape reads as such in the trace
        with capture_span("gridllm.metrics_scrape"):
            text = default_registry().render()
        return web.Response(text=text,
                            headers={"Content-Type": PROMETHEUS_CONTENT_TYPE})

    async def dump(_):
        # worker-side flight recorder artifact: this process's event rings
        # + live engine batch state. No scheduler here — the gateway's
        # /admin/dump carries the control-plane view; the worker service's
        # ACTIVE execution spans ride along so a wedged request's trace is
        # readable from the worker even before it resolves.
        from gridllm_tpu.obs import build_dump

        artifact = build_dump(reason="on_demand")
        artifact["worker"] = {
            "workerId": service.worker_id,
            "currentJobs": service.current_jobs,
            "models": list(service.engines),
        }
        artifact["activeTraces"] = {
            rid: service.tracer.export(rid)
            for rid in service.tracer.active_ids()
        }
        return web.json_response(artifact)

    async def memory(_):
        # engine-side device-memory breakdown (obs/perf.py): THIS process
        # holds the weights and KV pools, so this is the authoritative
        # weights/KV/workspace + headroom view in split deployments.
        # to_thread: the live_arrays walk is synchronous.
        from gridllm_tpu.obs import memory_snapshot

        return web.json_response(await asyncio.to_thread(memory_snapshot))

    async def profile(request):
        from gridllm_tpu.obs.perf import handle_profile_request

        # to_thread: capture start does blocking dir-prune/start_trace
        # work — the health port must keep answering liveness probes
        status, payload = await asyncio.to_thread(
            handle_profile_request, request.query.get("seconds"),
            request.query.get("python"))
        return web.json_response(payload, status=status)

    async def drain(request):
        # graceful drain (ISSUE 9): stop accepting work, finish short
        # decodes within the budget, live-migrate the rest. The worker
        # keeps running afterward (status "draining") — process exit is
        # the SIGTERM path's job; this route is for rolling restarts
        # orchestrated from outside.
        budget = request.query.get("budget_ms")
        try:
            budget_ms = int(budget) if budget else None
        except ValueError:
            return web.json_response(
                {"error": f"budget_ms must be an integer, got {budget!r}"},
                status=400)
        report = await service.drain(budget_ms)
        return web.json_response(report)

    async def kvx(request):
        # direct worker-to-worker KV migration (ISSUE 7): the whole wire
        # payload in one POST — the large-transfer fast path that skips
        # the bus. The header arrived via the bus prepare message; an
        # unknown request id means no prepare was seen and the sender
        # falls back to bus chunks (or local serving).
        rid = request.match_info["request_id"]
        body = await request.read()
        result = await service.kvx.feed_http(rid, body)
        return web.json_response(result,
                                 status=200 if result.get("ok") else 409)

    app.add_routes([
        web.get("/health", health), web.get("/health/live", live),
        web.get("/health/ready", ready), web.get("/health/system", system),
        web.get("/worker/status", status), web.get("/metrics", metrics),
        web.get("/admin/dump", dump), web.get("/admin/memory", memory),
        web.post("/admin/profile", profile),
        web.post("/admin/drain", drain),
        web.post("/kvx/{request_id}", kvx),
    ])
    return app


async def run(config: Config | None = None) -> None:
    """Worker process entry. Single-host: bus + engines + WorkerService.

    Multi-host slice (GRIDLLM_NUM_PROCS > 1, SURVEY.md §5.8b): every
    process joins the jax group FIRST (so jax.devices() is the global
    slice and engine meshes emit cross-host collectives), then:
      - process 0 (liaison) runs the full bus worker — ONE logical worker;
      - followers hold the jax runtime open and watch slice health.
    Any member death fails the WHOLE logical worker: the liaison announces
    `worker:disconnected` (scheduler orphans its jobs, scheduler.py orphan
    path) and every process exits so the supervisor restarts the slice
    together.
    """
    from gridllm_tpu.parallel.distributed import initialize_group, shutdown_group
    from gridllm_tpu.worker.group import GroupMembership, fail_logical_worker

    config = config or load_config()
    from gridllm_tpu.obs import default_flight_recorder

    default_flight_recorder().set_capacity(config.obs.flightrec_capacity)
    group = initialize_group()
    import jax

    devices = jax.devices()
    log.info("jax backend", platform=devices[0].platform,
             deviceKind=devices[0].device_kind, devices=len(devices),
             jax=jax.__version__)
    if group.is_group and not os.environ.get("WORKER_ID"):
        # ALL slice processes must agree on the logical worker id or the
        # member heartbeat keys never match and slice-failure detection is
        # a silent no-op. Without an explicit WORKER_ID, derive a shared,
        # slice-unique id from the coordinator address.
        import hashlib

        wid = "worker-slice-" + hashlib.sha1(
            (group.coordinator or "").encode()
        ).hexdigest()[:12]
        config.worker = config.worker.model_copy(update={"worker_id": wid})
    bus = create_bus(config.bus.url, key_prefix=config.bus.key_prefix,
                     password=config.bus.password, db=config.bus.db,
                     endpoints=config.bus.endpoints)
    await bus.connect()

    # fleet timeline (ISSUE 17): the worker publishes its flight-recorder
    # lifecycle events on obs:event so gateway/shard timelines include the
    # execution side. Publisher only — incident stores live control-plane
    # side. Batched + drop-counted: the decode loop never blocks on it.
    timeline_pub = None
    tl = config.obs.timeline
    if tl.enabled:
        from gridllm_tpu.obs import TimelinePublisher

        timeline_pub = TimelinePublisher(
            config.worker.worker_id, queue_capacity=tl.queue_capacity,
            flush_ms=tl.flush_ms, batch_max=tl.batch_max)
        timeline_pub.install()
        await timeline_pub.start(bus)

    stop = asyncio.Event()
    slice_broken: list[str] = []
    if group.is_liaison:
        engines = build_engines(config)
        if not engines:
            raise SystemExit("no models configured: set GRIDLLM_MODELS")
        service = WorkerService(
            bus, engines, config.worker,
            stream_flush_ms=config.engine.stream_flush_ms,
            # model management only outside a worker group: a slice's
            # engines must be built (and torn down) in lockstep on every
            # process — plan replay has no engine-construction op
            engine_factory=(
                None if group.is_group else pull_engine_factory(config)
            ),
        )
        if group.is_group:
            service.admin_ops_enabled = False

        async def on_slice_failure(reason: str) -> None:
            await fail_logical_worker(bus, service.worker_id, reason)
            await service.stop(announce=False)
            slice_broken.append(reason)
            stop.set()

        membership = GroupMembership(
            bus, service.worker_id, group,
            heartbeat_interval_s=config.worker.heartbeat_interval_ms / 1000.0,
            on_slice_failure=on_slice_failure,
        )
        await membership.start()
        # multi-host SPMD: broadcast every device-dispatching action so
        # followers issue the same computations (worker/plan.py; VERDICT
        # r03 missing #1 — liaison-only dispatch deadlocks the collectives)
        publishers: list[PlanPublisher] = []
        if group.is_group:
            import threading

            loop = asyncio.get_running_loop()
            pub = PlanPublisher(bus, plan_channel(service.worker_id), loop)
            pub.start()
            publishers.append(pub)
            # ONE dispatch lock across every engine: the liaison's
            # cross-engine dispatch order must equal the plan order
            shared_lock = threading.RLock()
            for model, eng in engines.items():
                eng.dispatch_lock = shared_lock
                eng.plan_sink = (
                    lambda rec, m=model: pub.sink({**rec, "model": m})
                )
            # barrier: every follower's plan subscription must be LIVE
            # before the first job can be assigned — pub/sub has no replay
            for pid in range(1, group.num_processes):
                for _ in range(1200):
                    if await bus.get(ready_key(service.worker_id, pid)):
                        break
                    await asyncio.sleep(0.1)
                else:
                    raise SystemExit(
                        f"slice follower {pid} never became plan-ready"
                    )
        await service.start()
        app = build_health_app(service)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, config.worker.host, config.worker.port)
        await site.start()
        log.info("worker http listening", port=config.worker.port)

        # Graceful drain on SIGTERM (ISSUE 9): rolling deploys and TPU
        # preemption notices deliver SIGTERM first — finish short decodes
        # within the drain budget, live-migrate the rest, then exit. The
        # service.stop() in the finally publishes the unregister, so any
        # job the drain could not hand off orphan-requeues WITH its
        # resume snapshot preserved scheduler-side.
        import signal as _signal

        # the drain task must be held somewhere that outlives the signal
        # handler: the loop keeps only a weak reference, and a collected
        # task would silently skip stop.set() — the worker would ignore
        # SIGTERM until the orchestrator escalates to SIGKILL
        drain_tasks: list[asyncio.Task] = []

        def _on_sigterm() -> None:
            async def _graceful() -> None:
                try:
                    await service.drain()
                finally:
                    stop.set()

            log.info("SIGTERM received; draining before exit")
            drain_tasks.append(asyncio.ensure_future(_graceful()))

        try:
            asyncio.get_running_loop().add_signal_handler(
                _signal.SIGTERM, _on_sigterm)
        except (NotImplementedError, RuntimeError):  # non-unix platforms
            pass
        try:
            await stop.wait()
        finally:
            await membership.stop()
            await service.stop()
            for pub in publishers:
                await pub.stop()
            await runner.cleanup()
            if timeline_pub is not None:
                await timeline_pub.stop()
            await bus.disconnect()
            if slice_broken:
                # jax.distributed teardown blocks on dead slice members —
                # fail fast so the supervisor restarts the slice together
                from gridllm_tpu.obs import default_flight_recorder

                default_flight_recorder().record(
                    "worker", "fatal_exit", worker=service.worker_id,
                    reason=slice_broken[0])
                log.error("slice broken; exiting", reason=slice_broken[0])
                os._exit(1)
            shutdown_group(group)
    else:
        # follower: build the SAME engines (identical jit programs over the
        # global mesh) and replay the liaison's step plan — every process
        # must issue the same computation or the collectives deadlock
        engines = build_engines(config)

        async def on_slice_failure(reason: str) -> None:
            slice_broken.append(reason)
            stop.set()

        membership = GroupMembership(
            bus, config.worker.worker_id, group,
            heartbeat_interval_s=config.worker.heartbeat_interval_ms / 1000.0,
            on_slice_failure=on_slice_failure,
        )
        await membership.start()
        follower = PlanFollower(
            bus, plan_channel(config.worker.worker_id), engines,
            on_divergence=on_slice_failure,
        )
        await follower.start()

        # signal the liaison this process can hear the plan (it holds
        # registration until every follower is ready). TTL + refresh, NOT
        # a plain set: a persistent key from a previous slice incarnation
        # would let a restarted liaison pass the barrier while this
        # process is still building engines — publishing to a channel
        # with no subscriber (pub/sub has no replay).
        rk = ready_key(config.worker.worker_id, group.process_id)

        async def refresh_ready() -> None:
            # transient bus errors must not kill the heartbeat: a dead
            # refresh loop lets the key expire and a later liaison restart
            # then waits out its whole barrier timeout on a live follower
            # (same per-beat guard as GroupMembership._beacon_loop)
            while True:
                try:
                    await bus.set_with_expiry(rk, "1", ttl_s=10.0)
                except Exception as e:  # noqa: BLE001
                    log.warning("ready-key refresh failed; retrying",
                                key=rk, error=str(e))
                await asyncio.sleep(3.0)

        ready_task = asyncio.create_task(refresh_ready())
        log.info("follower replaying step plan", models=list(engines))
        try:
            await stop.wait()
        finally:
            ready_task.cancel()
            await follower.stop()
            await membership.stop()
            if timeline_pub is not None:
                await timeline_pub.stop()
            await bus.disconnect()
            if slice_broken:
                log.error("slice broken; follower exiting",
                          reason=slice_broken[0])
                os._exit(1)
            shutdown_group(group)


def main() -> None:  # pragma: no cover
    asyncio.run(run())


if __name__ == "__main__":  # pragma: no cover
    main()
