#!/usr/bin/env python
"""Headline benchmark (driver contract: ONE JSON line on stdout).

Metric (BASELINE.md): output tokens/sec via /ollama/api/generate. The run
drives the FULL stack in one process — gateway HTTP → scheduler → in-memory
bus → WorkerService → InferenceEngine on whatever accelerator jax sees —
with N concurrent streaming requests (continuous batching), and reports
aggregate decode throughput + p50 TTFT.

vs_baseline anchors to BASELINE.json's comparison point ("Ollama-on-A100
output tokens/sec"); the reference publishes no numbers (BASELINE.md), so
the anchor values below are approximate public single-stream Ollama-on-A100
figures for each model. vs_baseline = measured_aggregate / anchor.

Usage: python bench.py [--model llama3.2:3b] [--requests 8] [--tokens 128]
       [--tiny] (the explicit CPU run: tiny-llama, a smoke test)

Without --tiny the bench measures a TPU or nothing: no TPU is an error, a
kernel that fails on the chip is the finding, and a run that raises exits
nonzero. It never pins the CPU, swaps the model or disables kernels on
its own.

Perf trajectory (ISSUE 4): ``--emit BENCH_rNN.json`` writes a standardized
machine-readable result record (schema gridllm-bench/v1: p50/p95 TTFT, ITL,
tok/s, steady-state recompile count from the jit tripwire, peak HBM);
``--compare old.json`` checks the current run against a previous record and
exits nonzero on a >10% regression in any shared metric — the perf gate CI
runs (.github/workflows/tier1.yml perf-smoke).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import sys
import time
import uuid

from gridllm_tpu.utils.config import env_raw

# Approximate public Ollama single-stream numbers on A100 (the BASELINE.json
# comparison anchor; nothing is published by the reference itself).
A100_OLLAMA_TOK_S = {
    "llama3:8b": 110.0,
    "llama3.1:8b": 110.0,
    "llama3.2:3b": 220.0,
    "llama3.2:1b": 350.0,
    "tiny-llama": 1.0,  # smoke-test placeholder
}

# Approximate public Ollama batch-embedding throughput on A100 for the
# BASELINE config #5 anchor (nothing published by the reference itself).
EMBED_BASELINE_QPS = {
    "all-minilm": 2500.0,
    "tiny-bert": 1.0,  # smoke-test placeholder
    "tiny-llama": 1.0,
}


async def _build_stack(engine, model: str, stream_flush_ms: int = 5,
                       trace_capacity: int = 0):
    """The full in-process serving stack (gateway → scheduler → in-memory
    bus → WorkerService → engine) every bench scenario drives — ONE copy
    so harness wiring changes land everywhere at once."""
    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.gateway.app import create_app
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import Config, WorkerConfig
    from gridllm_tpu.worker.service import WorkerService

    bus = InMemoryBus()
    await bus.connect()
    config = Config()
    registry = WorkerRegistry(bus, config.scheduler)
    scheduler = JobScheduler(bus, registry, config.scheduler)
    if trace_capacity:
        # stage stats read measured timelines — outgrow the default trace
        # LRU so large --requests runs aren't silently truncated to its tail
        scheduler.tracer.max_traces = max(scheduler.tracer.max_traces,
                                          trace_capacity)
    await registry.initialize()
    await scheduler.initialize()
    app = create_app(bus, registry, scheduler, config)
    worker = WorkerService(bus, {model: engine}, WorkerConfig(),
                           stream_flush_ms=stream_flush_ms)
    return bus, registry, scheduler, app, worker


async def _teardown_stack(bus, registry, scheduler, worker, client=None):
    """Teardown ALSO on failure, so a scenario that raises leaves no
    runner thread behind to keep the process from exiting."""
    if client is not None:
        try:
            await client.close()
        except Exception:  # noqa: BLE001
            pass
    try:
        await worker.stop()
    except Exception:  # noqa: BLE001
        pass
    try:
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()
    except Exception:  # noqa: BLE001
        pass


async def run_bench(model: str, n_requests: int, n_tokens: int,
                    max_slots: int, prompt_len: int,
                    profile_dir: str | None = None) -> dict:

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.worker.main import resolve_checkpoint

    # bench honesty (VERDICT r03 weak #4): with no checkpoint the run uses
    # random weights + the byte tokenizer (representative compute,
    # unrepresentative tokenization) and the metric string says so. Same
    # resolution logic as the worker entrypoint — one source of truth.
    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    engine = InferenceEngine(EngineConfig(
        model=model,
        checkpoint_path=ckpt,
        tokenizer=tok,
        max_slots=max_slots,
        page_size=64,
        num_pages=max(256, max_slots * 48),
        max_pages_per_slot=48,
        prefill_buckets=(256, 1024),
    ))
    bus, registry, scheduler, app, worker = await _build_stack(
        engine, model, trace_capacity=n_requests * 2 + 16)
    try:
        return await _run_bench_inner(
            client_ctx=(app, worker), engine=engine, model=model,
            n_requests=n_requests, n_tokens=n_tokens,
            prompt_len=prompt_len, profile_dir=profile_dir, ckpt=ckpt,
            scheduler=scheduler,
        )
    finally:
        await _teardown_stack(bus, registry, scheduler, worker)


def _p95(values: list[float]) -> float | None:
    if not values:
        return None
    vs = sorted(values)
    return vs[min(len(vs) - 1, max(0, -(-95 * len(vs) // 100) - 1))]


def _perf_sidecar() -> dict:
    """Recompile + peak-HBM accounting from the obs perf layer (ISSUE 4),
    read BEFORE teardown while the engine's arrays and memory probe are
    still live. recompiles_steady > 0 in a fixed-shape bench run means
    shape bucketing regressed — the perf-smoke CI gate asserts it is 0."""
    from gridllm_tpu.obs import memory_snapshot, recompile_totals

    rec = recompile_totals()
    peak = 0
    source = "none"
    for dev in memory_snapshot()["devices"].values():
        for key, src in (("peakBytesInUse", "allocator_peak"),
                         ("bytesInUse", "allocator_in_use"),
                         ("totalLiveBytes", "end_of_run_live")):
            cand = dev.get(key)
            if cand:
                if int(cand) > peak:
                    peak, source = int(cand), src
                break
    return {
        "recompiles_warmup": rec["warmup"],
        "recompiles_steady": rec["steady"],
        "recompiles_by_fn": rec["byFn"],
        "peak_hbm_bytes": peak,
        # honesty marker: only "allocator_peak" (TPU/GPU memory_stats) is
        # a true high-water mark; CPU backends report end-of-run live
        # bytes, which cannot see transient mid-decode spikes
        "peak_hbm_source": source,
    }


def _stage_stats(tracer, request_ids) -> dict:
    """p50 per-stage durations (ms) from the obs tracer's stitched
    timelines — the per-stage breakdown that explains the end-to-end
    numbers, read from the SAME spans /admin/trace serves instead of being
    re-timed here (ISSUE 1 satellite)."""
    keymap = {"queue.wait": "p50_queue_wait_ms",
              "engine.prefill": "p50_prefill_ms",
              "engine.decode": "p50_decode_ms"}
    stages: dict[str, list[float]] = {k: [] for k in keymap}
    ttfts: list[float] = []
    for rid in request_ids:
        for s in tracer.export(rid) or []:
            if s["name"] in stages and s.get("durationMs") is not None:
                stages[s["name"]].append(s["durationMs"])
            elif s["name"] == "gateway.first_token":
                t = (s.get("meta") or {}).get("ttftMs")
                if t is not None:
                    ttfts.append(float(t))
    out = {keymap[name]: round(statistics.median(vals), 2)
           for name, vals in stages.items() if vals}
    if ttfts:
        # gateway-side TTFT (submit → first stream frame) — the top-level
        # p50_ttft_ms stays the client-observed HTTP number; the delta
        # between them is gateway/HTTP overhead
        out["p50_ttft_gateway_ms"] = round(statistics.median(ttfts), 2)
    return out


def _critical_path_stats(tracer, request_ids) -> dict:
    """p50 per-segment critical-path decomposition (ms) across the
    measured requests (ISSUE 17). Unlike _stage_stats' raw span
    durations these segments are ADDITIVE — per request they sum to the
    traced e2e latency — so the record carries a decomposition that
    explains 100% of the latency, not a set of overlapping timers."""
    from gridllm_tpu.obs.timeline import critical_path

    per_seg: dict[str, list[float]] = {}
    for rid in request_ids:
        segs = critical_path(tracer.export(rid) or [])
        if not segs:
            continue  # root span not sealed (request still in flight)
        for seg, seconds in segs.items():
            per_seg.setdefault(seg, []).append(seconds * 1000.0)
    return {seg: round(statistics.median(vals), 2)
            for seg, vals in sorted(per_seg.items()) if vals}


async def _run_bench_inner(client_ctx, engine, model, n_requests, n_tokens,
                           prompt_len, profile_dir, ckpt,
                           scheduler=None) -> dict:
    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    app, worker = client_ctx
    await worker.start()
    await asyncio.sleep(0.1)
    client = TestClient(TestServer(app))
    await client.start_server()

    prompt = "The quick brown fox jumps over the lazy dog. " * (prompt_len // 10)

    # warmup: trigger prefill+decode compiles before timing — MUST use the
    # same prompt length as the measured run, or the real bucket's prefill
    # compile (tens of seconds on first use) lands inside the timed window.
    # Bounded wait: a device-level failure must surface as a fast, retryable
    # error (main() falls back to GRIDLLM_PALLAS=0), not a 300 s job timeout
    # that eats the whole bench window.
    warm = await client.post("/ollama/api/generate", json={
        "model": model, "prompt": prompt, "stream": False,
        "options": {"temperature": 0, "num_predict": 4},
    }, timeout=aiohttp.ClientTimeout(total=240))
    assert warm.status == 200, await warm.text()
    if not engine.running and not engine.embedding_only:
        raise RuntimeError("engine runner died during warmup "
                           "(device-level failure)")
    # stage stats must cover the MEASURED requests only, not the warmup
    warm_ids = set(scheduler.tracer.ids()) if scheduler is not None else set()

    ttfts: list[float] = []
    itls: list[float] = []  # per-stream mean inter-token latency
    tokens_out = [0]

    if profile_dir:
        # SURVEY §5.1 / VERDICT r03 #1: capture a device trace of the
        # measured window for op-level attribution (view with
        # tensorboard --logdir or xprof)
        import jax

        jax.profiler.start_trace(profile_dir)

    async def one(i: int) -> None:
        t0 = time.perf_counter()
        t_first = t_last = None
        async with client.post("/ollama/api/generate", json={
            "model": model, "prompt": f"[{i}] {prompt}",
            "options": {"temperature": 0.7, "seed": i, "num_predict": n_tokens},
        }) as resp:
            assert resp.status == 200, await resp.text()
            async for line in resp.content:
                if not line.strip():
                    continue
                now = time.perf_counter()
                if t_first is None:
                    t_first = now
                    ttfts.append(now - t0)
                t_last = now
                frame = json.loads(line)
                if frame.get("done"):
                    n = frame.get("eval_count") or 0
                    tokens_out[0] += n
                    if n > 1 and t_first is not None:
                        # streaming smoothness: a healthy pipeline spreads
                        # tokens across the window; a burst-at-the-end
                        # pathology (r03's 13 s TTFT) shows up as itl ≈ 0
                        # with huge ttft
                        itls.append((t_last - t_first) / (n - 1) * 1000)

    t_start = time.perf_counter()
    try:
        await asyncio.gather(*(one(i) for i in range(n_requests)))
    finally:
        if profile_dir:  # finalize the trace even when a request fails
            import jax

            jax.profiler.stop_trace()
    wall = time.perf_counter() - t_start

    await client.close()  # remaining teardown is run_bench's finally

    stages = {}
    critical_path_p50: dict = {}
    slo_attainment = None
    goodput_tok_s = None
    capacity = None
    fleet_health = None
    if scheduler is not None:
        # worker-side spans publish on trace:{id} AFTER job:result resolves
        # the HTTP stream — drain the bus so the tail requests' prefill/
        # decode spans are ingested before we read the timelines
        flush = getattr(scheduler.bus, "flush", None)
        if flush is not None:
            await flush()
        measured = [r for r in scheduler.tracer.ids() if r not in warm_ids]
        stages = _stage_stats(scheduler.tracer, measured)
        critical_path_p50 = _critical_path_stats(scheduler.tracer, measured)
        # SLO/goodput from the obs SLO engine (ISSUE 2): the measured
        # streams are the "interactive" class (the warmup is non-streaming
        # → "batch", so it does not pollute these numbers)
        inter = scheduler.slo.snapshot()["classes"].get("interactive") or {}
        slo_attainment = inter.get("attainment")
        if inter.get("goodputTokens") is not None:
            goodput_tok_s = inter["goodputTokens"] / wall
        # usage + capacity (ISSUE 16): the shard's per-tenant token ledger
        # and the per-model demand/headroom snapshot behind /admin/capacity
        # — lets CI gate that the bench traffic was attributed (non-empty
        # token totals) and that demand tracking saw the measured requests
        capacity = {
            "snapshot": scheduler.capacity.snapshot(),
            "usage_tokens": scheduler.usage.token_totals(),
        }
        # fleet health (ISSUE 19): canary probe summary + per-state worker
        # counts — on a healthy single-worker bench this gates to zero
        # quarantines and (when probing is enabled) a 1.0 pass rate
        fleet_health = {
            "canary": scheduler.prober.summary(),
            "worker_states": scheduler.health.counts(),
        }
    p95 = _p95(ttfts)
    return {
        "tok_s": tokens_out[0] / wall,
        "p50_ttft_ms": statistics.median(ttfts) * 1000,
        "p95_ttft_ms": p95 * 1000 if p95 is not None else None,
        "p50_itl_ms": statistics.median(itls) if itls else None,
        "tokens": tokens_out[0],
        "wall_s": wall,
        "stages": stages,
        "critical_path": critical_path_p50,
        "slo_attainment": slo_attainment,
        "goodput_tok_s": goodput_tok_s,
        "capacity": capacity,
        "fleet_health": fleet_health,
        "perf": _perf_sidecar(),
        "weights": "real-checkpoint" if ckpt else "random-weights synthetic",
    }


async def run_long_context_bench(model: str, n_requests: int,
                                 n_tokens: int, max_slots: int,
                                 prefix_len: int,
                                 long_prompt_len: int) -> dict:
    """Long-context / tiered-KV scenario (ISSUE 11), extending
    --shared-prefix with LRU-overflow pressure: N streams share one long
    system prompt (cold round populates the prefix cache, warm round
    measures the warm TTFT), then a burst of max-capacity long prompts
    overflows the HBM reuse LRU — evicting the shared prefix — and a
    final post-eviction round re-issues the shared prompts. Run twice:
    tier OFF (the long burst destroys the warm TTFT — the regression)
    and tier ON (evicted pages spilled to host RAM page back in on
    match, recovering it). Spill dtype is raw for the A/B so both arms'
    streams are byte-comparable; per-tier hit rates and restore counts
    ride the record."""

    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.worker.main import resolve_checkpoint

    ckpt, tok = resolve_checkpoint(env_raw("GRIDLLM_CHECKPOINT_DIR"), model)
    tiny = model.startswith("tiny")
    ps = 32 if tiny else 64
    n_requests = max(n_requests, 2)
    max_slots = max(max_slots, n_requests)
    # respect the MODEL context: tiny models cap at 256 tokens, and a
    # prompt past the effective context left-truncates (which would
    # silently shrink the long burst below eviction pressure)
    try:
        from gridllm_tpu.models.configs import get_config as _get_config

        model_ctx = _get_config(model).max_seq_len
    except KeyError:
        model_ctx = 8192
    slot_pages = 8 if tiny else 48
    ctx_cap = min(model_ctx, slot_pages * ps)
    prefix_len = min(prefix_len, ctx_cap - 2 * ps)
    long_cap = min(long_prompt_len, ctx_cap - n_tokens - 2)
    # pool sized so the N COLD streams fit but the long burst must evict
    # the reuse LRU: free-after-warm ≈ pool − shared prefix pages, while
    # the burst wants ≈ N × ctx_cap/ps pages. Page math is char≈token
    # exact for the byte tokenizer (tiny CI models); real tokenizers
    # over-estimate, so the record's eviction count is the honesty marker.
    prefix_pages = prefix_len // ps
    num_pages = n_requests * (prefix_pages + 1)

    async def one_arm(host_bytes: int) -> dict:
        engine = InferenceEngine(EngineConfig(
            model=model,
            checkpoint_path=ckpt,
            tokenizer=tok,
            max_slots=max_slots,
            page_size=ps,
            num_pages=num_pages,
            max_pages_per_slot=slot_pages,
            prefill_buckets=(256, 1024),
            prefill_chunk=64 if tiny else 256,
            kv_host_bytes=host_bytes,
            kv_spill_int8=False,  # raw spill: arms stay byte-comparable
        ))
        bus, registry, scheduler, app, worker = await _build_stack(
            engine, model, trace_capacity=n_requests * 8 + 16)
        client = None
        try:
            await worker.start()
            await asyncio.sleep(0.1)
            client = TestClient(TestServer(app))
            await client.start_server()

            shared = ("You are a meticulous assistant. Policy clause %d: "
                      "the quick brown fox jumps over the lazy dog. ")
            system = "".join(shared % i for i in range(100))[:prefix_len]

            # compile warmup: disjoint prefix, issued twice so the warm
            # path's programs (window seed + mid-prompt chunk) compile
            # outside every measured window; then a burst of long-shape
            # prompts that EVICTS the warmup prefix, and one final
            # re-issue so the tier-on arm's restore path (the kv_install
            # program) also compiles before any measured round
            warm_prompts = ["[warmup] " + system, "[warmup] " + system]
            warm_prompts += [("W%d " % j) + "X" * long_cap
                             for j in range(n_requests)]
            warm_prompts += ["[warmup] " + system]
            for ptxt in warm_prompts:
                warm_up = await client.post("/ollama/api/generate", json={
                    "model": model, "prompt": ptxt, "stream": False,
                    "options": {"temperature": 0, "num_predict": 2},
                }, timeout=aiohttp.ClientTimeout(total=240))
                assert warm_up.status == 200, await warm_up.text()

            async def one(i: int, prompt: str, ttfts: list,
                          tokens_out: list, n_pred: int) -> None:
                t0 = time.perf_counter()
                async with client.post("/ollama/api/generate", json={
                    "model": model, "prompt": prompt,
                    "options": {"temperature": 0, "seed": i,
                                "num_predict": n_pred},
                }) as resp:
                    assert resp.status == 200, await resp.text()
                    first = True
                    async for line in resp.content:
                        if not line.strip():
                            continue
                        if first:
                            first = False
                            ttfts.append(time.perf_counter() - t0)
                        frame = json.loads(line)
                        if frame.get("done"):
                            tokens_out[0] += frame.get("eval_count") or 0

            async def round_(prompts: list[str], n_pred: int) -> dict:
                await asyncio.sleep(0.5)  # drain trailing pipeline blocks
                ttfts: list[float] = []
                tokens_out = [0]
                t0 = time.perf_counter()
                await asyncio.gather(*(one(i, p, ttfts, tokens_out, n_pred)
                                       for i, p in enumerate(prompts)))
                wall = time.perf_counter() - t0
                return {"wall_s": wall, "tokens": tokens_out[0],
                        "tok_s": tokens_out[0] / wall,
                        "p50_ttft_ms": statistics.median(ttfts) * 1000}

            shared_prompts = [f"{system}\nUser {i} asks:"
                              for i in range(n_requests)]
            long_prompts = [("L%d " % i) + "X" * long_cap
                            for i in range(n_requests)]

            cold = await round_(shared_prompts, n_tokens)
            warm = await round_(shared_prompts, n_tokens)
            long_r = await round_(long_prompts, n_tokens)
            evict_mark = engine.alloc.evictions
            h0, m0 = engine.alloc.hits, engine.alloc.misses
            tier0 = (engine.host_tier.stats() if engine.host_tier
                     else {"restores": 0, "spills": 0, "misses": 0})
            post = await round_(shared_prompts, n_tokens)
            dh = engine.alloc.hits - h0
            dm = engine.alloc.misses - m0
            tier1 = (engine.host_tier.stats() if engine.host_tier
                     else {"restores": 0, "spills": 0, "misses": 0,
                           "evictions": 0, "pages": 0, "bytes": 0})
            return {
                "cold": cold, "warm": warm, "long": long_r, "post": post,
                "evictions": evict_mark,
                "post_hbm_hit_rate": round(dh / (dh + dm), 4)
                if (dh + dm) else 0.0,
                "post_restores": tier1["restores"] - tier0["restores"],
                "tier": tier1,
                "perf": _perf_sidecar(),
                "weights": ("real-checkpoint" if ckpt
                            else "random-weights synthetic"),
            }
        finally:
            await _teardown_stack(bus, registry, scheduler, worker,
                                  client=client)

    off = await one_arm(0)
    on = await one_arm(256 * 1024 * 1024)
    post_on = on["post"]["p50_ttft_ms"]
    post_off = off["post"]["p50_ttft_ms"]
    return {
        # headline: the tier-on arm's post-eviction round — warm TTFT
        # recovered under LRU-overflow pressure
        "tok_s": on["post"]["tok_s"],
        "tokens": sum(a[r]["tokens"] for a in (off, on)
                      for r in ("cold", "warm", "long", "post")),
        "wall_s": sum(a[r]["wall_s"] for a in (off, on)
                      for r in ("cold", "warm", "long", "post")),
        "p50_ttft_ms_cold": on["cold"]["p50_ttft_ms"],
        "p50_ttft_ms_warm": on["warm"]["p50_ttft_ms"],
        "p50_ttft_ms_post_on": post_on,
        "p50_ttft_ms_post_off": post_off,
        # ≥ 1 when the tier recovers TTFT the eviction storm destroyed
        "ttft_recovery": (post_off / post_on) if post_on else None,
        # the EFFECTIVE prefix actually measured (the model-context clamp
        # above can shrink the requested one) — the metric string must
        # state this, not the requested value
        "prefix_len": prefix_len,
        "restores": on["post_restores"],
        "kv_tier": {
            "on": {"evictions": on["evictions"],
                   "postHbmHitRate": on["post_hbm_hit_rate"],
                   "postRestores": on["post_restores"],
                   "spills": on["tier"]["spills"],
                   "hostPages": on["tier"]["pages"],
                   "hostBytes": on["tier"]["bytes"],
                   "tierMisses": on["tier"]["misses"]},
            "off": {"evictions": off["evictions"],
                    "postHbmHitRate": off["post_hbm_hit_rate"]},
        },
        "perf": on["perf"],
        "weights": on["weights"],
    }


async def run_shared_prefix_bench(model: str, n_requests: int,
                                  n_tokens: int, max_slots: int,
                                  prefix_len: int) -> dict:
    """Shared-prefix scenario (ISSUE 3): N streams share one long system
    prompt. Round 1 (cold) pays full prefill and populates the prefix
    cache; round 2 (warm) re-issues the same prompts and skips the cached
    prefix. Reports cold vs warm p50 TTFT and the warm round's prompt-page
    hit rate — the headline numbers for automatic prefix caching."""

    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.worker.main import resolve_checkpoint

    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    # Chunks sized so BOTH rounds run the chunked-prefill program and the
    # warm round's win is purely the skipped chunk invocations. The tiny
    # CPU models cap context at 256 tokens, so they need page-sized chunks
    # (and a tight page table — the jnp fallback of the prefix-chunk
    # attention gathers the FULL table row, so oversizing it would charge
    # both rounds dense-gather overhead the TPU kernel doesn't pay).
    tiny = model.startswith("tiny")
    # every stream gets a slot: if streams queued behind a full batch, the
    # later "cold" streams would admit AFTER earlier ones completed and
    # registered the shared prefix — silently warming the cold round
    max_slots = max(max_slots, n_requests)
    engine = InferenceEngine(EngineConfig(
        model=model,
        checkpoint_path=ckpt,
        tokenizer=tok,
        max_slots=max_slots,
        page_size=64,
        num_pages=max(384, max_slots * 64),
        max_pages_per_slot=8 if tiny else 48,
        prefill_buckets=(256, 1024),
        prefill_chunk=64 if tiny else 256,
    ))
    bus, registry, scheduler, app, worker = await _build_stack(
        engine, model, trace_capacity=n_requests * 4 + 16)
    client = None
    try:
        await worker.start()
        await asyncio.sleep(0.1)
        client = TestClient(TestServer(app))
        await client.start_server()

        shared = ("You are a meticulous assistant. Policy clause %d: the "
                  "quick brown fox jumps over the lazy dog. " )
        system = "".join(shared % i for i in range(100))[:prefix_len]

        # compile warmup with the same shapes but a DISJOINT prefix so
        # round 1 stays an honest cold measurement. Issued TWICE: the
        # second run matches the first's pages and compiles the warm-path
        # programs (window seed + mid-prompt chunk), so neither round pays
        # first-compile inside its measured window.
        for _ in range(2):
            warm_up = await client.post("/ollama/api/generate", json={
                "model": model, "prompt": "[warmup] " + system,
                "stream": False,
                "options": {"temperature": 0, "num_predict": 2},
            }, timeout=aiohttp.ClientTimeout(total=240))
            assert warm_up.status == 200, await warm_up.text()

        async def one(i: int, ttfts: list, tokens_out: list) -> None:
            t0 = time.perf_counter()
            async with client.post("/ollama/api/generate", json={
                "model": model, "prompt": f"{system}\nUser {i} asks:",
                "options": {"temperature": 0, "seed": i,
                            "num_predict": n_tokens},
            }) as resp:
                assert resp.status == 200, await resp.text()
                first = True
                async for line in resp.content:
                    if not line.strip():
                        continue
                    if first:
                        first = False
                        ttfts.append(time.perf_counter() - t0)
                    frame = json.loads(line)
                    if frame.get("done"):
                        tokens_out[0] += frame.get("eval_count") or 0

        async def round_(ttfts: list[float]) -> dict:
            # drain trailing pipeline blocks from the previous round — the
            # runner keeps dispatching for up to decode_block ×
            # pipeline_depth steps after the last stream resolves, and that
            # tail would otherwise bleed into this round's TTFTs
            await asyncio.sleep(0.5)
            tokens_out = [0]
            t0 = time.perf_counter()
            await asyncio.gather(*(one(i, ttfts, tokens_out)
                                   for i in range(n_requests)))
            wall = time.perf_counter() - t0
            return {"wall_s": wall, "tok_s": tokens_out[0] / wall,
                    "tokens": tokens_out[0]}

        ch0, cm0 = engine.alloc.hits, engine.alloc.misses
        cold_ttfts: list[float] = []
        cold = await round_(cold_ttfts)
        cdh = engine.alloc.hits - ch0
        cdm = engine.alloc.misses - cm0
        hits0, miss0 = engine.alloc.hits, engine.alloc.misses
        # several warm rounds: a single round of n_requests TTFTs is too
        # few samples for a stable p50 on a noisy host
        warm_ttfts: list[float] = []
        warm_rounds = [await round_(warm_ttfts) for _ in range(3)]
        warm = {
            "wall_s": sum(r["wall_s"] for r in warm_rounds),
            "tokens": sum(r["tokens"] for r in warm_rounds),
            "tok_s": statistics.median(r["tok_s"] for r in warm_rounds),
        }
        dh = engine.alloc.hits - hits0
        dm = engine.alloc.misses - miss0
        hit_rate = dh / (dh + dm) if (dh + dm) else 0.0
        # honesty check on the cold round: a nonzero cold hit rate means
        # the rounds are not independent (streams queued past the batch)
        cold_rate = cdh / (cdh + cdm) if (cdh + cdm) else 0.0
        cold["p50_ttft_ms"] = statistics.median(cold_ttfts) * 1000
        warm["p50_ttft_ms"] = statistics.median(warm_ttfts) * 1000
        warm_p95 = _p95(warm_ttfts)
        return {
            "p95_ttft_ms": warm_p95 * 1000 if warm_p95 is not None else None,
            "perf": _perf_sidecar(),
            "tok_s": warm["tok_s"],
            "tokens": cold["tokens"] + warm["tokens"],
            "wall_s": cold["wall_s"] + warm["wall_s"],
            "p50_ttft_ms_cold": cold["p50_ttft_ms"],
            "p50_ttft_ms_warm": warm["p50_ttft_ms"],
            "ttft_speedup": (cold["p50_ttft_ms"] / warm["p50_ttft_ms"]
                             if warm["p50_ttft_ms"] else None),
            "prefix_cache_hit_rate": round(hit_rate, 4),
            "prefix_cache_hit_rate_cold": round(cold_rate, 4),
            "prefix_cache": {"hits": engine.alloc.hits,
                             "misses": engine.alloc.misses,
                             "evictions": engine.alloc.evictions,
                             "cow_copies": engine.alloc.cow_copies},
            "weights": "real-checkpoint" if ckpt
            else "random-weights synthetic",
        }
    finally:
        await _teardown_stack(bus, registry, scheduler, worker,
                              client=client)


async def run_spec_bench(model: str, n_requests: int, n_tokens: int,
                         max_slots: int, spec_k: int) -> dict:
    """Speculative-decoding A/B/C (ISSUE 5 + 18): the SAME
    repetitive-completion workload three ways — speculation off, n-gram
    (prompt-lookup) drafting, and draft-model + token-tree drafting.
    Templated/repetitive output is the n-gram drafter's home turf — the
    workload asks for verbatim repetition and runs greedy with
    repeat_penalty disabled so repetition is not artificially damped.
    Each arm reports tok/s + ITL plus acceptance rate, emitted tokens
    per verify step (> 1 = speculation is paying for its verify
    overhead), and the drafter's own wall overhead per step. The
    draft-model arm uses GRIDLLM_SPEC_DRAFT_MODEL when set, else the
    target config itself (fresh-init tiny targets then draft with
    IDENTICAL weights — the acceptance ceiling, which is the point of
    the harness arm: it isolates tree/verify mechanics from draft-model
    quality)."""

    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.worker.main import resolve_checkpoint

    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    draft_name = env_raw("GRIDLLM_SPEC_DRAFT_MODEL") or model
    # tiny CPU models cap context at 256 byte-tokens — the prompt must
    # leave room for the measured decode or every stream dies at capacity
    reps = 2 if model.startswith("tiny") else 5
    prompt = ("Repeat the policy clause verbatim, forever: the quick brown "
              "fox jumps over the lazy dog; ") * reps
    opts = {"temperature": 0, "repeat_penalty": 1.0,
            "num_predict": n_tokens}

    async def arm(spec_on: bool, draft_model: str = "",
                  last: bool = False) -> dict:
        engine = InferenceEngine(EngineConfig(
            model=model, checkpoint_path=ckpt, tokenizer=tok,
            max_slots=max_slots, page_size=64,
            num_pages=max(256, max_slots * 48), max_pages_per_slot=48,
            prefill_buckets=(256, 1024),
            spec_decode=spec_on, spec_k=spec_k,
            draft_model=draft_model,
        ))
        bus, registry, scheduler, app, worker = await _build_stack(
            engine, model)
        client = None
        try:
            await worker.start()
            await asyncio.sleep(0.1)
            client = TestClient(TestServer(app))
            await client.start_server()
            warm = await client.post("/ollama/api/generate", json={
                "model": model, "prompt": prompt, "stream": False,
                "options": {**opts, "num_predict": 4},
            }, timeout=aiohttp.ClientTimeout(total=240))
            assert warm.status == 200, await warm.text()
            s0 = dict(engine.spec_stats)
            ttfts: list[float] = []
            itls: list[float] = []
            tokens_out = [0]

            async def one(i: int) -> None:
                t0 = time.perf_counter()
                t_first = t_last = None
                async with client.post("/ollama/api/generate", json={
                    "model": model, "prompt": f"[{i}] {prompt}",
                    "options": dict(opts),
                }) as resp:
                    assert resp.status == 200, await resp.text()
                    async for line in resp.content:
                        if not line.strip():
                            continue
                        now = time.perf_counter()
                        if t_first is None:
                            t_first = now
                            ttfts.append(now - t0)
                        t_last = now
                        frame = json.loads(line)
                        if frame.get("done"):
                            n = frame.get("eval_count") or 0
                            tokens_out[0] += n
                            if n > 1 and t_first is not None:
                                itls.append(
                                    (t_last - t_first) / (n - 1) * 1000)

            t0 = time.perf_counter()
            await asyncio.gather(*(one(i) for i in range(n_requests)))
            wall = time.perf_counter() - t0
            st = engine.spec_stats
            d = {k: st[k] - s0[k] for k in st}
            out = {
                "tok_s": tokens_out[0] / wall,
                "p50_ttft_ms": statistics.median(ttfts) * 1000,
                "p50_itl_ms": statistics.median(itls) if itls else None,
                "tokens": tokens_out[0],
                "wall_s": wall,
                "spec": d,
            }
            out["drafter"] = (engine.batch_state().get("specDecode") or
                              {}).get("drafter", "off")
            if last:
                # the final arm is the LAST engine alive — read the perf
                # sidecar (recompiles across ALL arms, peak HBM) here
                out["perf"] = _perf_sidecar()
            return out
        finally:
            await _teardown_stack(bus, registry, scheduler, worker,
                                  client=client)

    def derived(a: dict) -> dict:
        spec = a["spec"]
        steps = spec["steps"]
        return {
            "drafter": a["drafter"],
            "tok_s": round(a["tok_s"], 2),
            "p50_ttft_ms": round(a["p50_ttft_ms"], 2),
            "p50_itl_ms": (round(a["p50_itl_ms"], 2)
                           if a["p50_itl_ms"] is not None else None),
            "acceptance_rate": round(
                spec["accepted"] / spec["proposed"], 4)
            if spec["proposed"] else 0.0,
            "tokens_per_step": round(spec["emitted"] / steps, 4)
            if steps else 0.0,
            "draft_overhead_ms_per_step": round(
                spec.get("draft_ns", 0) / steps / 1e6, 3) if steps else 0.0,
            "steps": steps,
            "proposed": spec["proposed"],
            "accepted": spec["accepted"],
        }

    off = await arm(False)
    ng = await arm(True)
    md = await arm(True, draft_name, last=True)
    arms = {"off": derived(off), "ngram": derived(ng),
            "model": derived(md)}
    return {
        # headline keys = the draft-model tree arm (the ISSUE-18 path);
        # the per-arm breakdown lives under "arms". ITL is reported per
        # arm but deliberately NOT exposed under the gated top-level
        # keys: on tiny CPU runs ITL is scheduler noise — the honest
        # regression gates for speculation are acceptance rate and
        # tokens per verify step.
        "tok_s": md["tok_s"],
        "tok_s_spec_off": off["tok_s"],
        "p50_ttft_ms": md["p50_ttft_ms"],
        "spec_acceptance_rate": arms["model"]["acceptance_rate"],
        "spec_tokens_per_step": arms["model"]["tokens_per_step"],
        "spec_acceptance_rate_ngram": arms["ngram"]["acceptance_rate"],
        "spec_tokens_per_step_ngram": arms["ngram"]["tokens_per_step"],
        "spec_steps": arms["model"]["steps"],
        "spec_proposed": arms["model"]["proposed"],
        "spec_accepted": arms["model"]["accepted"],
        "arms": arms,
        "tokens": off["tokens"] + ng["tokens"] + md["tokens"],
        "wall_s": off["wall_s"] + ng["wall_s"] + md["wall_s"],
        "perf": md.get("perf"),
        "weights": "real-checkpoint" if ckpt
        else "random-weights synthetic",
    }


async def run_mixed_bench(model: str, n_requests: int, n_tokens: int,
                          max_slots: int, long_prompt_len: int) -> dict:
    """Mixed-workload scenario (ISSUE 6): decode-heavy streams running
    CONCURRENTLY with long chunked prefills — the traffic shape the
    unified ragged paged-attention kernel exists for. Half the load is
    short-prompt/long-decode streams (ITL is their number), half is
    long-prompt/short-decode requests arriving while the others are
    mid-generation (TTFT is theirs). Under the ragged engine each prefill
    chunk and the running decodes share one launch, so the decode arm's
    ITL should NOT degrade while prefills churn; `--compare` gates both
    p50 ITL and p50 TTFT (plus tok/s) against a previous record."""

    import aiohttp
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.worker.main import resolve_checkpoint

    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    tiny = model.startswith("tiny")
    engine = InferenceEngine(EngineConfig(
        model=model,
        checkpoint_path=ckpt,
        tokenizer=tok,
        max_slots=max_slots,
        page_size=64,
        num_pages=max(384, max_slots * 64),
        max_pages_per_slot=8 if tiny else 48,
        prefill_buckets=(64, 256, 1024),
        # long prompts MUST take the chunked path — that is the mixed
        # step under test (tiny CPU models cap context at 512 tokens)
        prefill_chunk=64 if tiny else 512,
    ))
    bus, registry, scheduler, app, worker = await _build_stack(
        engine, model, trace_capacity=n_requests * 4 + 16)
    client = None
    try:
        await worker.start()
        await asyncio.sleep(0.1)
        client = TestClient(TestServer(app))
        await client.start_server()

        filler = "the quick brown fox jumps over the lazy dog; "
        long_prompt = (filler * 200)[:long_prompt_len]
        short_prompt = "summarize: " + filler

        # warmup compiles every program both arms need: a long (chunked)
        # prefill AND a short (bucketed) one, plus decode. The warmup
        # prompts use the SAME "[X0] " tag shape as the measured ones so
        # they land in the same prefill buckets — a one-character length
        # difference can cross a bucket edge and put a first-compile
        # inside the measured window
        for p in (f"[W0] {long_prompt}", f"[W0] {short_prompt}"):
            warm = await client.post("/ollama/api/generate", json={
                "model": model, "prompt": p, "stream": False,
                "options": {"temperature": 0, "num_predict": 4},
            }, timeout=aiohttp.ClientTimeout(total=240))
            assert warm.status == 200, await warm.text()

        decode_ttfts: list[float] = []
        decode_itls: list[float] = []
        prefill_ttfts: list[float] = []
        tokens_out = [0]

        async def one(prompt: str, n_predict: int, ttfts: list,
                      itls: list | None, tag: str, i: int) -> None:
            t0 = time.perf_counter()
            t_first = t_last = None
            async with client.post("/ollama/api/generate", json={
                "model": model, "prompt": f"[{tag}{i}] {prompt}",
                "options": {"temperature": 0, "seed": i,
                            "num_predict": n_predict},
            }) as resp:
                assert resp.status == 200, await resp.text()
                async for line in resp.content:
                    if not line.strip():
                        continue
                    now = time.perf_counter()
                    if t_first is None:
                        t_first = now
                        ttfts.append(now - t0)
                    t_last = now
                    frame = json.loads(line)
                    if frame.get("done"):
                        n = frame.get("eval_count") or 0
                        tokens_out[0] += n
                        if itls is not None and n > 1 and t_first is not None:
                            itls.append((t_last - t_first) / (n - 1) * 1000)

        async def long_arm(i: int) -> None:
            # arrive mid-decode: the prefill chunks must share steps with
            # running streams, not an idle engine
            await asyncio.sleep(0.2 * (i + 1))
            await one(long_prompt, 4, prefill_ttfts, None, "L", i)

        # main() clamps --mixed to >= 2 requests, so both arms get >= 1
        # stream and the total matches the record's request count
        n_decode = max(n_requests // 2, 1)
        n_long = max(n_requests - n_decode, 1)
        t0 = time.perf_counter()
        await asyncio.gather(
            *(one(short_prompt, n_tokens, decode_ttfts, decode_itls,
                  "D", i) for i in range(n_decode)),
            *(long_arm(i) for i in range(n_long)),
        )
        wall = time.perf_counter() - t0
        return {
            "tok_s": tokens_out[0] / wall,
            "p50_ttft_ms": (statistics.median(prefill_ttfts) * 1000
                            if prefill_ttfts else None),
            "p50_itl_ms": (statistics.median(decode_itls)
                           if decode_itls else None),
            "p95_ttft_ms": (None if _p95(prefill_ttfts) is None
                            else _p95(prefill_ttfts) * 1000),
            "tokens": tokens_out[0],
            "wall_s": wall,
            "mixed": {
                "decode_streams": n_decode,
                "long_prefills": n_long,
                "long_prompt_chars": len(long_prompt),
                "p50_decode_ttft_ms": (
                    statistics.median(decode_ttfts) * 1000
                    if decode_ttfts else None),
            },
            "perf": _perf_sidecar(),
            "weights": "real-checkpoint" if ckpt
            else "random-weights synthetic",
        }
    finally:
        await _teardown_stack(bus, registry, scheduler, worker,
                              client=client)


async def run_disagg_bench(model: str, n_requests: int, n_tokens: int,
                           max_slots: int, long_prompt_len: int) -> dict:
    """Disaggregated-serving A/B (ISSUE 7): the same mixed workload
    (decode-heavy streams + long prefills arriving mid-generation) served
    by (a) ONE unified worker and (b) a prefill worker + a decode worker
    with KV-page migration between them. The headline: the split arm's
    decode-pool ITL under mixed load — long prefills run on the prefill
    worker, so they stop inflating the decode pool's inter-token latency
    — plus migration volume/latency from the transfer layer's metrics.
    Measured at the scheduler boundary (submit_streaming_job) so both
    arms pay identical harness overhead."""

    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.transfer.migrate import (
        _MIG_BYTES,
        _MIG_SECONDS,
        _MIGRATIONS,
    )
    from gridllm_tpu.utils.config import SchedulerConfig, WorkerConfig
    from gridllm_tpu.utils.types import InferenceRequest
    from gridllm_tpu.worker.main import resolve_checkpoint
    from gridllm_tpu.worker.service import WorkerService

    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    tiny = model.startswith("tiny")

    def make_engine() -> InferenceEngine:
        return InferenceEngine(EngineConfig(
            model=model,
            checkpoint_path=ckpt,
            tokenizer=tok,
            max_slots=max_slots,
            page_size=64,
            num_pages=max(384, max_slots * 64),
            max_pages_per_slot=8 if tiny else 48,
            prefill_buckets=(64, 256, 1024),
            prefill_chunk=64 if tiny else 512,
        ))

    filler = "the quick brown fox jumps over the lazy dog; "
    long_prompt = (filler * 200)[:long_prompt_len]
    # the short prompt must span >1 KV page (64 TOKENS) or there is no
    # full-page prefix to migrate and every decode stream falls back —
    # sized against the engines' ACTUAL tokenizer (byte-level for tiny
    # models, HF for real checkpoints), not in characters
    from gridllm_tpu.engine.tokenizer import get_tokenizer
    from gridllm_tpu.models.configs import get_config

    try:
        vocab = get_config(model).vocab_size
    except KeyError:
        vocab = 32000
    probe_tok = get_tokenizer(tok, vocab)
    short_prompt = "summarize: " + filler
    while len(probe_tok.encode(short_prompt, add_bos=True)) < 80:
        short_prompt += filler

    async def run_arm(roles: list[str]) -> dict:
        bus = InMemoryBus()
        await bus.connect()
        cfg = SchedulerConfig()
        registry = WorkerRegistry(bus, cfg)
        scheduler = JobScheduler(bus, registry, cfg)
        await registry.initialize()
        await scheduler.initialize()
        workers: list[WorkerService] = []
        for i, role in enumerate(roles):
            svc = WorkerService(
                bus, {model: make_engine()},
                WorkerConfig(worker_id=f"bench-{role}-{i}", role=role,
                             heartbeat_interval_ms=250),
                stream_flush_ms=5)
            await svc.start()
            workers.append(svc)
        await asyncio.sleep(0.4)  # first heartbeats (roles/headroom) land
        try:
            tokens_out = [0]

            async def one(prompt: str, n_predict: int, ttfts: list,
                          itls: list | None, tag: str, i: int) -> None:
                t0 = time.perf_counter()
                marks: list[float] = []

                async def on_chunk(_c) -> None:
                    marks.append(time.perf_counter())

                req = InferenceRequest(
                    id=f"bench-{tag}{i}-{uuid.uuid4().hex[:6]}",
                    model=model, prompt=f"[{tag}{i}] {prompt}", stream=True,
                    options={"temperature": 0, "seed": i,
                             "num_predict": n_predict},
                    metadata={"requestType": "inference"})
                res = await scheduler.submit_streaming_job(
                    req, on_chunk, timeout_ms=240_000)
                assert res.success, res.error
                n = int(res.response.eval_count or 0)
                tokens_out[0] += n
                if marks:
                    ttfts.append(marks[0] - t0)
                    if itls is not None and n > 1:
                        itls.append((marks[-1] - marks[0]) / (n - 1) * 1000)

            # warmup compiles every program both arms need — long
            # (chunked) and short (bucketed) prefills, decode, and on the
            # split arm the whole export→wire→import→warm-resume chain —
            # run TWICE so warm-path programs exist before measurement
            for w in range(2):
                await one(long_prompt, 4, [], None, "W", w)
                await one(short_prompt, 4, [], None, "W", w + 10)
            tokens_out[0] = 0  # warmup tokens must not inflate tok/s

            mig0 = _MIGRATIONS.value(side="send", outcome="ok")
            bytes0, secs0 = _MIG_BYTES.sum(), _MIG_SECONDS.sum()
            count0 = _MIG_BYTES.count()
            handoff0 = scheduler._disagg_total.value(event="handoff")
            fallback0 = scheduler._disagg_total.value(event="fallback")

            decode_ttfts: list[float] = []
            decode_itls: list[float] = []
            prefill_ttfts: list[float] = []
            n_decode = max(n_requests // 2, 1)
            n_long = max(n_requests - n_decode, 1)

            async def long_arm(i: int) -> None:
                # arrive mid-decode: prefill load lands while the decode
                # streams are generating — the interference under test
                await asyncio.sleep(0.2 * (i + 1))
                await one(long_prompt, 4, prefill_ttfts, None, "L", i)

            t0 = time.perf_counter()
            await asyncio.gather(
                *(one(short_prompt, n_tokens, decode_ttfts, decode_itls,
                      "D", i) for i in range(n_decode)),
                *(long_arm(i) for i in range(n_long)),
            )
            wall = time.perf_counter() - t0
            n_mig = int(_MIG_BYTES.count() - count0)
            steady = sum(
                p["steadyRecompiles"]
                for svc in workers
                for p in svc.engines[model].perf.state().values())
            return {
                "roles": roles,
                "tok_s": tokens_out[0] / wall,
                "tokens": tokens_out[0],
                "wall_s": wall,
                "p50_itl_ms": (statistics.median(decode_itls)
                               if decode_itls else None),
                "p95_itl_ms": _p95(decode_itls),
                "p50_ttft_ms": (statistics.median(prefill_ttfts) * 1000
                                if prefill_ttfts else None),
                "p95_ttft_ms": (None if _p95(prefill_ttfts) is None
                                else _p95(prefill_ttfts) * 1000),
                "p50_decode_ttft_ms": (
                    statistics.median(decode_ttfts) * 1000
                    if decode_ttfts else None),
                "recompiles_steady": steady,
                "migrations": {
                    "count": n_mig,
                    "ok": int(_MIGRATIONS.value(side="send", outcome="ok")
                              - mig0),
                    "bytes": int(_MIG_BYTES.sum() - bytes0),
                    "avg_ms": (round((_MIG_SECONDS.sum() - secs0)
                                     / n_mig * 1000, 2) if n_mig else None),
                    # deltas over the measured window, like count/bytes
                    # (warmups migrate too and must not skew the record)
                    "handoffs": int(scheduler._disagg_total.value(
                        event="handoff") - handoff0),
                    "fallbacks": int(scheduler._disagg_total.value(
                        event="fallback") - fallback0),
                },
            }
        finally:
            for svc in workers:
                try:
                    await svc.stop(announce=False)
                except Exception:  # noqa: BLE001
                    pass
            try:
                await scheduler.shutdown()
                await registry.shutdown()
                await bus.disconnect()
            except Exception:  # noqa: BLE001
                pass

    unified = await run_arm(["unified"])
    split = await run_arm(["prefill", "decode"])
    return {
        # headline = the split arm (what --compare gates release over
        # release); the unified arm rides in the payload for the A/B read
        "tok_s": split["tok_s"],
        "tokens": split["tokens"],
        "wall_s": unified["wall_s"] + split["wall_s"],
        "p50_itl_ms": split["p50_itl_ms"],
        "p50_ttft_ms": split["p50_ttft_ms"],
        "p95_ttft_ms": split["p95_ttft_ms"],
        "disagg": {"unified": unified, "split": split},
        "perf": _perf_sidecar(),
        "weights": "real-checkpoint" if ckpt
        else "random-weights synthetic",
    }


async def run_fleet_bench(model: str, n_requests: int, n_tokens: int,
                          max_slots: int, prompt_len: int) -> dict:
    """Scaled-control-plane A/B (ISSUE 15): the same mixed stream load
    served by (a) the single-box control plane — one in-process
    scheduler+gateway — and (b) a 2-gateway/2-shard control plane
    (GatewaySubmitter replicas publishing over ctrl:submit to
    SchedulerShard partition owners) on the same bus, one unified worker
    per arm. The headline: control-plane overhead under fan-out — tok/s
    and p50 TTFT through the scaled plane vs the local one — plus the
    shard dispatch split and lease transitions proving both partitions
    actually carried load. Measured at the submit boundary so both arms
    pay identical harness overhead."""

    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.controlplane.client import GatewaySubmitter
    from gridllm_tpu.controlplane.partition import shard_of
    from gridllm_tpu.controlplane.shard import (
        SchedulerShard,
        wait_for_ownership,
    )
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import (
        ControlPlaneConfig,
        SchedulerConfig,
        WorkerConfig,
    )
    from gridllm_tpu.utils.types import InferenceRequest
    from gridllm_tpu.worker.main import resolve_checkpoint
    from gridllm_tpu.worker.service import WorkerService

    ckpt, tok = resolve_checkpoint(
        env_raw("GRIDLLM_CHECKPOINT_DIR"), model
    )
    tiny = model.startswith("tiny")

    def make_engine() -> InferenceEngine:
        return InferenceEngine(EngineConfig(
            model=model,
            checkpoint_path=ckpt,
            tokenizer=tok,
            max_slots=max_slots,
            page_size=64,
            num_pages=max(384, max_slots * 64),
            max_pages_per_slot=8 if tiny else 48,
            prefill_buckets=(64, 256, 1024),
        ))

    prompt = ("the quick brown fox jumps over the lazy dog; "
              * (prompt_len // 10 + 1))[:max(prompt_len, 40)]
    num_shards = 2

    def id_for_shard(tag: str, i: int, idx: int) -> str:
        # deterministic spread: both partitions must carry real load or
        # the scaled arm silently degrades to a 1-shard measurement
        while True:
            jid = f"bench-{tag}{i}-{uuid.uuid4().hex[:6]}"
            if shard_of(jid, num_shards) == idx:
                return jid

    async def run_arm(scaled: bool) -> dict:
        bus = InMemoryBus()
        await bus.connect()
        cfg = SchedulerConfig()
        shards: list[SchedulerShard] = []
        registries: list[WorkerRegistry] = []
        submitters: list = []
        local_sched: JobScheduler | None = None
        if scaled:
            for i in range(num_shards):
                reg = WorkerRegistry(bus, cfg)
                sh = SchedulerShard(
                    bus, reg, cfg,
                    ControlPlaneConfig(num_shards=num_shards, shard_id=i,
                                       lease_ttl_ms=2000,
                                       renew_interval_ms=300),
                    member_id=f"bench-shard-{i}", settle_s=0.01)
                await reg.initialize()
                await sh.start()
                registries.append(reg)
                shards.append(sh)
            assert await wait_for_ownership(shards, num_shards)
            for i in range(2):
                reg = WorkerRegistry(bus, cfg, observer=True)
                gw = GatewaySubmitter(bus, reg, cfg,
                                      member_id=f"bench-gw-{i}")
                await reg.initialize()
                await gw.initialize()
                registries.append(reg)
                submitters.append(gw)
        else:
            reg = WorkerRegistry(bus, cfg)
            local_sched = JobScheduler(bus, reg, cfg)
            await reg.initialize()
            await local_sched.initialize()
            registries.append(reg)
            submitters.append(local_sched)
        svc = WorkerService(bus, {model: make_engine()},
                            WorkerConfig(worker_id="bench-fleet-w0",
                                         heartbeat_interval_ms=250),
                            stream_flush_ms=5)
        await svc.start()
        await asyncio.sleep(0.4)  # registrations land on every registry
        try:
            tokens_out = [0]

            async def one(i: int, jid: str, ttfts: list,
                          itls: list | None) -> None:
                sub = submitters[i % len(submitters)]
                t0 = time.perf_counter()
                marks: list[float] = []

                async def on_chunk(_c) -> None:
                    marks.append(time.perf_counter())

                req = InferenceRequest(
                    id=jid, model=model, prompt=f"[{i}] {prompt}",
                    stream=True,
                    options={"temperature": 0, "seed": i,
                             "num_predict": n_tokens},
                    metadata={"requestType": "inference"})
                res = await sub.submit_streaming_job(req, on_chunk,
                                                     timeout_ms=240_000)
                assert res.success, res.error
                n = int(res.response.eval_count or 0)
                tokens_out[0] += n
                if marks:
                    ttfts.append(marks[0] - t0)
                    if itls is not None and n > 1:
                        itls.append((marks[-1] - marks[0]) / (n - 1) * 1000)

            for w in range(2):  # warmup compiles; spread over partitions
                await one(w, id_for_shard("W", w, w % num_shards), [],
                          None)
            tokens_out[0] = 0

            ttfts: list[float] = []
            itls: list[float] = []
            jids = [id_for_shard("R", i, i % num_shards)
                    for i in range(n_requests)]
            t0 = time.perf_counter()
            await asyncio.gather(*(one(i, jid, ttfts, itls)
                                   for i, jid in enumerate(jids)))
            wall = time.perf_counter() - t0
            steady = sum(
                p["steadyRecompiles"]
                for p in svc.engines[model].perf.state().values())
            arm = {
                "plane": "2x2" if scaled else "1x1",
                "tok_s": tokens_out[0] / wall,
                "tokens": tokens_out[0],
                "wall_s": wall,
                "p50_ttft_ms": (statistics.median(ttfts) * 1000
                                if ttfts else None),
                "p95_ttft_ms": (None if _p95(ttfts) is None
                                else _p95(ttfts) * 1000),
                "p50_itl_ms": (statistics.median(itls)
                               if itls else None),
                "recompiles_steady": steady,
            }
            if scaled:
                arm["shard_dispatched"] = [
                    int(sh.scheduler._jobs_total.value(event="dispatched"))
                    for sh in shards]
                arm["lease_transitions"] = {
                    ev: int(sum(sh.lease._transitions.value(event=ev)
                                for sh in shards))
                    for ev in ("acquired", "adopted", "deposed",
                               "expired")}
                arm["fenced_ops"] = int(sum(
                    sh.scheduler._shard_fenced.value(op=op)
                    for sh in shards
                    for op in ("assign", "timeout", "orphan", "failure",
                               "cancel", "drain", "preempt")))
            return arm
        finally:
            try:
                await svc.stop(announce=False)
            except Exception:  # noqa: BLE001
                pass
            for gw in (s for s in submitters if s is not local_sched):
                try:
                    await gw.shutdown()
                except Exception:  # noqa: BLE001
                    pass
            for sh in shards:
                try:
                    await sh.stop()
                except Exception:  # noqa: BLE001
                    pass
            try:
                if local_sched is not None:
                    await local_sched.shutdown()
                for reg in registries:
                    await reg.shutdown()
                await bus.disconnect()
            except Exception:  # noqa: BLE001
                pass

    local = await run_arm(scaled=False)
    scaled = await run_arm(scaled=True)
    return {
        # headline = the scaled plane (what --compare gates); the local
        # arm rides in the payload for the A/B read
        "tok_s": scaled["tok_s"],
        "tokens": scaled["tokens"],
        "wall_s": local["wall_s"] + scaled["wall_s"],
        "p50_ttft_ms": scaled["p50_ttft_ms"],
        "p95_ttft_ms": scaled["p95_ttft_ms"],
        "p50_itl_ms": scaled["p50_itl_ms"],
        "fleet": {"local": local, "scaled": scaled},
        "perf": _perf_sidecar(),
        "weights": "real-checkpoint" if ckpt
        else "random-weights synthetic",
    }


async def run_swap_bench(model: str, n_requests: int, n_tokens: int,
                         max_slots: int) -> dict:
    """Elastic-serving scenario (ISSUE 20), two parts.

    Part A — cold-start TTFT, three arms at the engine boundary (wall
    time from construction start to a first greedy token):

    - ``cold``: fresh persistent compile-cache dir, no weight snapshot —
      the full price (XLA compiles + weight materialization);
    - ``compile_warm``: same cache dir (now populated), weights still
      re-materialized from disk/init — what a NEW checkpoint pays on a
      warmed host;
    - ``snapshot_warm``: compile cache AND host-RAM weight snapshot hit
      — the swap-in hot path. The headline gate: snapshot-warm must be
      ≥ 3× faster than fully cold.

    Runs FIRST in the process so the cold arm's compiles are honest.

    Part B — bursty two-model traffic through the full stack: bursts of
    model A, then B, then A again, with idle gaps past the idle TTL. The
    elastic arm (placement controller on, one worker with an engine
    factory) must serve every request — A scales to zero while idle, B
    is swapped in on demand, queued-not-rejected. The static arm (model
    A pinned, no elasticity) cannot serve B: those submissions time out,
    the counter-factual the acceptance criterion names."""

    import os as _os
    import shutil

    import jax

    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine import loader
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import (
        SchedulerConfig,
        WorkerConfig,
        compile_cache_dir,
    )
    from gridllm_tpu.utils.types import InferenceRequest
    from gridllm_tpu.worker.main import resolve_checkpoint
    from gridllm_tpu.worker.service import WorkerService

    tiny = model.startswith("tiny")
    model_b = "tiny-qwen2" if tiny else "llama3.2:1b"

    # the cold arm wants an EMPTY cache at a path that does not move (the
    # path is part of the cache key): one sub-directory of the cache root
    # in force, emptied here, before this process's first compile
    cache_dir = _os.path.join(compile_cache_dir(), "swap-bench")
    shutil.rmtree(cache_dir, ignore_errors=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    _os.environ["GRIDLLM_WEIGHT_SNAPSHOT_BYTES"] = str(4 << 30)
    loader.reset_weight_snapshot_tier()  # fresh read of the knob

    def make_engine(name: str) -> InferenceEngine:
        ckpt, tok = resolve_checkpoint(env_raw("GRIDLLM_CHECKPOINT_DIR"),
                                       name)
        return InferenceEngine(EngineConfig(
            model=name,
            checkpoint_path=ckpt,
            tokenizer=tok,
            max_slots=max_slots,
            page_size=64,
            num_pages=max(384, max_slots * 64),
            max_pages_per_slot=8 if tiny else 48,
            prefill_buckets=(64, 256, 1024),
        ))

    # ---- Part A: cold-start TTFT arms --------------------------------
    from gridllm_tpu.engine.engine import GenerationRequest

    def cold_start_arm() -> tuple[float, str, InferenceEngine]:
        """(seconds to first greedy token from construction, text,
        engine) — the cold-start unit every arm measures identically."""
        marks: list[float] = []

        def on_chunk(_d: str, _done: bool, _r) -> None:
            if not marks:
                marks.append(time.perf_counter())

        t0 = time.perf_counter()
        eng = make_engine(model)
        res = eng.generate(GenerationRequest(
            id=f"swapbench-{uuid.uuid4().hex[:6]}",
            prompt="the quick brown fox",
            options={"temperature": 0, "seed": 0,
                     "num_predict": max(n_tokens, 4)},
            on_chunk=on_chunk,
        ))
        ttft = (marks[0] if marks else time.perf_counter()) - t0
        return ttft, res.text, eng

    cold_s, cold_text, eng1 = cold_start_arm()
    assert eng1.load_source in ("checkpoint", "init"), eng1.load_source
    eng1.params = None  # release before the next arm materializes
    warm_s, warm_text, eng2 = cold_start_arm()
    eng2.park_weights()
    snap_s, snap_text, eng3 = cold_start_arm()
    snapshot_hit = eng3.load_source == "snapshot"
    eng3.params = None
    tier_stats = loader.weight_snapshot_tier().stats()

    # ---- Part B: bursty two-model elastic vs static ------------------
    idle_ttl_ms = 500

    async def run_arm(elastic: bool) -> dict:
        _os.environ["GRIDLLM_PLACEMENT_INTERVAL_MS"] = (
            "100" if elastic else "0")
        _os.environ["GRIDLLM_MODEL_IDLE_TTL_MS"] = str(idle_ttl_ms)
        _os.environ["GRIDLLM_SWAP_COOLDOWN_MS"] = "100"
        # short demand half-life so the arrival-rate EWMA decays below
        # the idle epsilon within the bench's idle gap (default 60s
        # would hold models "busy" for minutes after a burst)
        _os.environ["GRIDLLM_CAPACITY_EWMA_HALFLIFE_S"] = "0.2"
        bus = InMemoryBus()
        await bus.connect()
        cfg = SchedulerConfig()
        reg = WorkerRegistry(bus, cfg)
        sched = JobScheduler(bus, reg, cfg)
        await reg.initialize()
        await sched.initialize()
        svc = WorkerService(
            bus, {model: make_engine(model)},
            WorkerConfig(worker_id=f"bench-swap-{'el' if elastic else 'st'}",
                         heartbeat_interval_ms=150),
            stream_flush_ms=5,
            engine_factory=(make_engine if elastic else None))
        await svc.start()
        await asyncio.sleep(0.4)
        served = [0]
        rejected = [0]
        b_ttfts: list[float] = []

        async def one(name: str, i: int, timeout_ms: int) -> None:
            t0 = time.perf_counter()
            marks: list[float] = []

            async def on_chunk(_c) -> None:
                marks.append(time.perf_counter())

            try:
                res = await sched.submit_streaming_job(InferenceRequest(
                    id=f"swap-{'el' if elastic else 'st'}-{name}-{i}-"
                       f"{uuid.uuid4().hex[:6]}",
                    model=name, prompt=f"[{i}] the quick brown fox",
                    stream=True,
                    options={"temperature": 0, "seed": i,
                             "num_predict": n_tokens},
                    metadata={"requestType": "inference"},
                ), on_chunk, timeout_ms=timeout_ms)
            except Exception:  # noqa: BLE001 — timeout = rejected (the
                rejected[0] += 1  # static arm's expected counter-factual)
                return
            if res.success:
                served[0] += 1
                if name == model_b and marks:
                    b_ttfts.append(marks[0] - t0)
            else:
                rejected[0] += 1

        arm: dict = {"mode": "elastic" if elastic else "static"}
        try:
            # burst 1: model A (resident everywhere)
            await asyncio.gather(*(one(model, i, 240_000)
                                   for i in range(n_requests)))
            # idle past the TTL; the elastic arm scales A to zero
            a_zero = False
            if elastic:
                deadline = time.perf_counter() + (idle_ttl_ms / 1000.0 + 8.0)
                while time.perf_counter() < deadline:
                    await asyncio.sleep(0.1)
                    if not reg.get_workers_with_model(model):
                        a_zero = True
                        break
            else:
                await asyncio.sleep(idle_ttl_ms / 1000.0 + 0.5)
            arm["a_scaled_to_zero"] = a_zero
            # burst 2: model B — swap-in on demand (elastic) / timeout
            # (static: nothing can ever serve it, 25s cap per request)
            await asyncio.gather(*(one(model_b, i,
                                       240_000 if elastic else 25_000)
                                   for i in range(n_requests)))
            # burst 3: model A again — reload from the weight snapshot
            await asyncio.gather(*(one(model, i,
                                       240_000 if elastic else 25_000)
                                   for i in range(n_requests)))
            arm["served"] = served[0]
            arm["rejected"] = rejected[0]
            arm["p50_b_swapin_ttft_ms"] = (
                statistics.median(b_ttfts) * 1000 if b_ttfts else None)
            if elastic:
                p = sched.placement
                arm["swaps"] = {
                    f"{op}_{oc}": int(p._swaps.value(op=op, outcome=oc))
                    for op in ("load", "unload")
                    for oc in ("ok", "declined", "error", "timeout")
                    if p._swaps.value(op=op, outcome=oc)}
            return arm
        finally:
            try:
                await svc.stop(announce=False)
            except Exception:  # noqa: BLE001
                pass
            try:
                await sched.shutdown()
                await reg.shutdown()
                await bus.disconnect()
            except Exception:  # noqa: BLE001
                pass
            _os.environ["GRIDLLM_PLACEMENT_INTERVAL_MS"] = "0"

    t0 = time.perf_counter()
    elastic = await run_arm(elastic=True)
    static = await run_arm(elastic=False)
    wall = time.perf_counter() - t0

    return {
        "cold_ttft_ms": cold_s * 1000,
        "compile_warm_ttft_ms": warm_s * 1000,
        "snapshot_warm_ttft_ms": snap_s * 1000,
        "cold_start_speedup": cold_s / snap_s if snap_s > 0 else None,
        "snapshot_hit": snapshot_hit,
        "cold_texts_identical": cold_text == warm_text == snap_text,
        "snapshot_tier": tier_stats,
        "compile_cache_dir_entries": sum(
            len(files) for _, _, files in _os.walk(cache_dir)),
        "bursty": {"elastic": elastic, "static": static,
                   "model_a": model, "model_b": model_b,
                   "requests_per_burst": n_requests},
        "wall_s": wall,
        "perf": _perf_sidecar(),
        "weights": "random-weights synthetic" if tiny
        else "checkpoint-or-init",
    }


async def run_embed_bench(model: str, n_requests: int,
                          batch: int = 64, rounds: int = 8) -> dict:
    """Embeddings QPS through the full stack (BASELINE config #5):
    n_requests concurrent /ollama/api/embed calls, each carrying `batch`
    texts, repeated `rounds` times after a warmup."""
    from aiohttp.test_utils import TestClient, TestServer

    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    engine = InferenceEngine(EngineConfig(
        model=model, max_slots=1, prefill_buckets=(64, 256),
    ))
    bus, registry, scheduler, app, worker = await _build_stack(
        engine, model, stream_flush_ms=20)
    client = None
    try:
        await worker.start()
        await asyncio.sleep(0.1)
        client = TestClient(TestServer(app))
        await client.start_server()

        texts = [f"document {i}: the quick brown fox jumps over the lazy "
                 f"dog " * (1 + i % 4) for i in range(batch)]
        warm = await client.post("/ollama/api/embed",
                                 json={"model": model, "input": texts})
        assert warm.status == 200, await warm.text()

        done = [0]

        async def one() -> None:
            for _ in range(rounds):
                resp = await client.post(
                    "/ollama/api/embed", json={"model": model, "input": texts})
                assert resp.status == 200, await resp.text()
                body = await resp.json()
                done[0] += len(body.get("embeddings") or [])

        t0 = time.perf_counter()
        await asyncio.gather(*(one() for _ in range(n_requests)))
        wall = time.perf_counter() - t0
        return {"qps": done[0] / wall, "texts": done[0], "wall_s": wall,
                "perf": _perf_sidecar()}
    finally:
        await _teardown_stack(bus, registry, scheduler, worker,
                              client=client)


BENCH_SCHEMA = "gridllm-bench/v1"

# regression direction per metric: the compare gate flags a >threshold
# move the WRONG way; metrics absent from either record are skipped
# spec gating (ISSUE 18): tokens/step and acceptance — NOT ITL, which is
# scheduler noise at tiny-CPU scale (itl_speedup left the gate set when
# the spec bench went three-arm)
HIGHER_BETTER = ("tok_s", "qps", "goodput_tok_s", "slo_attainment",
                 "ttft_speedup", "prefix_cache_hit_rate",
                 "spec_acceptance_rate", "spec_tokens_per_step",
                 "spec_acceptance_rate_ngram",
                 "spec_tokens_per_step_ngram", "ttft_recovery",
                 "cold_start_speedup")
LOWER_BETTER = ("p50_ttft_ms", "p95_ttft_ms", "p50_itl_ms",
                "peak_hbm_bytes", "cold_ttft_ms", "compile_warm_ttft_ms",
                "snapshot_warm_ttft_ms")


def build_record(scenario: str, args, payload: dict, r: dict) -> dict:
    """The standardized machine-readable bench result (--emit): one stable
    schema so BENCH_rNN.json files form a comparable perf trajectory."""
    metrics: dict = {}
    for key in HIGHER_BETTER + LOWER_BETTER:
        val = payload.get(key, r.get(key))
        if isinstance(val, (int, float)):
            metrics[key] = round(float(val), 4)
    perf = r.get("perf") or {}
    metrics["recompiles_steady"] = int(perf.get("recompiles_steady", 0))
    if perf.get("peak_hbm_bytes"):
        metrics["peak_hbm_bytes"] = int(perf["peak_hbm_bytes"])
    return {
        "peak_hbm_source": perf.get("peak_hbm_source", "none"),
        "schema": BENCH_SCHEMA,
        "createdAt": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "scenario": scenario,
        "model": args.model,
        "platform": payload.get("platform"),
        "config": {"requests": args.requests, "tokens": args.tokens,
                   "slots": args.slots, "prompt_len": args.prompt_len},
        "metrics": metrics,
        "recompiles_by_fn": perf.get("recompiles_by_fn") or {},
        "payload": payload,
    }


def compare_records(old: dict, new: dict,
                    threshold: float = 0.10) -> tuple[list[str], list[str]]:
    """(regressions, notes) between two bench records. Apples-to-apples
    only: scenario/model/platform mismatches skip the comparison with a
    note instead of flagging nonsense regressions (a --tiny CPU run must
    not 'regress' a TPU baseline)."""
    notes: list[str] = []
    for field in ("scenario", "model", "platform"):
        if old.get(field) != new.get(field):
            notes.append(
                f"baseline {field} mismatch ({old.get(field)!r} vs "
                f"{new.get(field)!r}) — comparison skipped")
            return [], notes
    if old.get("schema") != new.get("schema"):
        notes.append(f"schema drift: {old.get('schema')} vs "
                     f"{new.get('schema')} — comparing shared metrics only")
    regressions: list[str] = []
    om, nm = old.get("metrics") or {}, new.get("metrics") or {}
    for key in HIGHER_BETTER:
        if key in om and key in nm and om[key] > 0:
            if nm[key] < om[key] * (1 - threshold):
                regressions.append(
                    f"{key}: {om[key]:g} -> {nm[key]:g} "
                    f"({(nm[key] / om[key] - 1) * 100:+.1f}%)")
    for key in LOWER_BETTER:
        if key in om and key in nm and om[key] > 0:
            if nm[key] > om[key] * (1 + threshold):
                regressions.append(
                    f"{key}: {om[key]:g} -> {nm[key]:g} "
                    f"({(nm[key] / om[key] - 1) * 100:+.1f}%)")
    old_rc = om.get("recompiles_steady")
    new_rc = nm.get("recompiles_steady")
    if old_rc is not None and new_rc is not None and new_rc > old_rc:
        # any NEW steady-state recompile is a regression — there is no
        # 10% grace for a signal whose healthy value is zero
        regressions.append(f"recompiles_steady: {old_rc} -> {new_rc}")
    return regressions, notes


def probe_backend(timeout_s: float = 120.0) -> tuple[str, str]:
    """(platform, detail) of jax's default backend, asked of a child
    process: one chip belongs to one process, and the child has exited
    before this one imports jax; an in-process init that hung would take
    the one JSON line down with it. Never pins a platform — a machine
    with no TPU is the caller's error, not a CPU run."""
    import subprocess

    code = ("import jax; d = jax.devices(); print('PLATFORM=' + "
            "d[0].platform + ' kind=' + d[0].device_kind.replace(' ', '_') "
            "+ ' devices=%d' % len(d))")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return "none", f"backend init timed out after {timeout_s}s"
    for line in out.stdout.splitlines():
        if line.startswith("PLATFORM="):
            return line.split("=", 1)[1].split()[0], line[9:]
    tail = (out.stderr or out.stdout).strip().splitlines()[-3:]
    return "none", f"rc={out.returncode} {' | '.join(tail)}"


def emit(payload: dict) -> None:
    """The driver contract: exactly ONE JSON line on stdout."""
    print(json.dumps(payload), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama3.2:3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=120)
    ap.add_argument("--embed", action="store_true",
                    help="embeddings QPS bench (BASELINE config #5)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="prefix-cache scenario: N streams share one long "
                         "system prompt; reports cold vs warm p50 TTFT and "
                         "the prefix-cache hit rate (ISSUE 3)")
    ap.add_argument("--prefix-len", type=int, default=1200,
                    help="shared system-prompt length in characters "
                         "(--shared-prefix only)")
    ap.add_argument("--long-context", action="store_true",
                    help="tiered-KV scenario: shared-prefix streams, then "
                         "long prompts overflow the HBM reuse LRU; A/B "
                         "host tier off vs on (post-eviction warm TTFT "
                         "recovery, per-tier hit rates, restores)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding A/B/C: the same repetitive-"
                         "completion workload spec-off, n-gram, and "
                         "draft-model + token-tree; reports per-arm "
                         "tok/s, ITL, acceptance rate, tokens per verify "
                         "step, and drafter overhead (ISSUE 5 + 18)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculation depth K for the --spec scenario")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-workload scenario: decode-heavy streams "
                         "concurrent with long chunked prefills; reports "
                         "the decode arm's p50 ITL and the prefill arm's "
                         "p50 TTFT — the ragged paged-attention gate "
                         "(ISSUE 6)")
    ap.add_argument("--long-prompt-len", type=int, default=2400,
                    help="long-prefill prompt length in characters "
                         "(--mixed/--disagg only)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated-serving A/B: the mixed workload "
                         "served by one unified worker vs a prefill+decode "
                         "split fleet with KV-page migration; reports both "
                         "arms' decode ITL and prefill TTFT plus migration "
                         "bytes/latency (ISSUE 7)")
    ap.add_argument("--fleet", action="store_true",
                    help="scaled-control-plane A/B: the same stream load "
                         "through the single-box scheduler vs a "
                         "2-gateway/2-shard control plane on one bus; "
                         "reports both arms' tok/s and p50 TTFT plus the "
                         "shard dispatch split (ISSUE 15)")
    ap.add_argument("--swap", action="store_true",
                    help="elastic-serving scenario: cold-start TTFT arms "
                         "(fully cold vs compile-cache-warm vs weight-"
                         "snapshot-warm) plus a bursty two-model A/B — "
                         "demand-driven swapping vs a static single-model "
                         "pin (ISSUE 20)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny-llama CPU smoke test")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the measured "
                         "window into DIR (SURVEY §5.1)")
    ap.add_argument("--emit", metavar="PATH", default=None,
                    help="write the standardized bench record "
                         "(gridllm-bench/v1) to PATH, e.g. BENCH_r06.json "
                         "— the machine-readable perf trajectory (ISSUE 4)")
    ap.add_argument("--compare", metavar="PATH", default=None,
                    help="compare this run against a previous --emit "
                         "record; exit nonzero on a >10%% regression")
    ap.add_argument("--regression-threshold", type=float, default=0.10,
                    help="fractional regression tolerance for --compare")
    args = ap.parse_args()
    if args.embed and args.model == ap.get_default("model"):
        args.model = "all-minilm"
    if args.profile and args.embed:
        # only the generate path threads profile_dir through; failing fast
        # beats silently never writing the trace
        ap.error("--profile is only supported on the generate bench")
    if args.embed and args.shared_prefix:
        ap.error("--shared-prefix is a generate scenario; drop --embed")
    if args.spec and (args.embed or args.shared_prefix):
        ap.error("--spec is its own generate scenario; drop "
                 "--embed/--shared-prefix")
    if args.mixed and (args.embed or args.shared_prefix or args.spec):
        ap.error("--mixed is its own generate scenario; drop "
                 "--embed/--shared-prefix/--spec")
    if args.long_context and (args.embed or args.shared_prefix or args.spec
                              or args.mixed or args.disagg):
        ap.error("--long-context is its own generate scenario; drop "
                 "--embed/--shared-prefix/--spec/--mixed/--disagg")
    if args.disagg and (args.embed or args.shared_prefix or args.spec
                        or args.mixed):
        ap.error("--disagg is its own generate scenario; drop "
                 "--embed/--shared-prefix/--spec/--mixed")
    if args.fleet and (args.embed or args.shared_prefix or args.spec
                       or args.mixed or args.disagg or args.long_context):
        ap.error("--fleet is its own generate scenario; drop "
                 "--embed/--shared-prefix/--spec/--mixed/--disagg/"
                 "--long-context")
    if args.swap and (args.embed or args.shared_prefix or args.spec
                      or args.mixed or args.disagg or args.long_context
                      or args.fleet):
        ap.error("--swap is its own generate scenario; drop "
                 "--embed/--shared-prefix/--spec/--mixed/--disagg/"
                 "--long-context/--fleet")
    if args.swap:
        # every burst needs at least one stream; keep the CPU arms short
        args.requests = max(args.requests, 1)
    if args.fleet:
        # both partitions must carry at least one measured stream each
        args.requests = max(args.requests, 2)
    if args.disagg:
        # at least one stream per class, same clamp rationale as --mixed
        args.requests = max(args.requests, 2)
    if args.mixed:
        # the scenario needs at least one stream per arm — clamp HERE so
        # the emitted record's request count matches the load actually run
        args.requests = max(args.requests, 2)

    if args.tiny:
        # the explicit CPU run (CI's dry run of the same command): a tiny
        # model, sizes cut to match
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        platform = "cpu"
        args.model = "tiny-bert" if args.embed else "tiny-llama"
        # the spec scenario needs enough decode steps for the output to
        # enter its repetitive regime before acceptance can show
        args.tokens = min(args.tokens,
                          48 if (args.spec or args.mixed or args.disagg)
                          else 16)
        if args.fleet:
            args.tokens = min(args.tokens, 16)
        args.prompt_len = 20
        # the shared prefix must still span several KV pages (64-token
        # pages, byte tokenizer) or there is nothing to cache
        args.prefix_len = min(args.prefix_len, 800)
        # tiny models cap context at 512 tokens (byte tokenizer): the
        # long arm must still span several 64-token chunks
        args.long_prompt_len = min(args.long_prompt_len, 320)
        args.requests = min(args.requests, 4)
        if args.long_context:
            # tiny slot cap is 8×64 = 512 tokens: the shared prefix must
            # leave room for the query + generation, and the long burst
            # must still exceed the post-warm free pool
            args.prefix_len = min(args.prefix_len, 320)
            args.long_prompt_len = min(args.long_prompt_len, 448)
            args.tokens = min(args.tokens, 16)
            args.requests = max(min(args.requests, 3), 2)
    else:
        platform, detail = probe_backend()
        if platform != "tpu":
            emit({"metric": f"bench ({args.model})", "value": 0.0,
                  "unit": "embeddings/s" if args.embed else "tok/s",
                  "vs_baseline": 0.0, "platform": platform,
                  "error": f"no TPU ({detail}); bench.py measures a TPU or "
                           "nothing — --tiny is the explicit CPU run"})
            return 2

    metric_name = (  # provisional — refined with weights provenance below
        f"embeddings/sec via /ollama/api/embed ({args.model})" if args.embed
        else f"output tokens/sec via /ollama/api/generate ({args.model}, "
             f"{args.requests} concurrent streams)"
    )
    try:
        if args.embed:
            r = asyncio.run(run_embed_bench(args.model, args.requests))
            baseline = EMBED_BASELINE_QPS.get(args.model, 0.0)
            value, unit = r["qps"], "embeddings/s"
        elif args.shared_prefix:
            r = asyncio.run(run_shared_prefix_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.prefix_len,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"warm-cache output tokens/sec via /ollama/api/generate "
                f"({args.model}, shared-prefix scenario, {args.requests} "
                f"streams × {args.prefix_len}-char system prompt, "
                f"{r['weights']})"
            )
        elif args.long_context:
            r = asyncio.run(run_long_context_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.prefix_len, args.long_prompt_len,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"post-eviction warm output tokens/sec via /ollama/api/"
                f"generate ({args.model}, tiered-KV long-context A/B, "
                f"{args.requests} streams × {r['prefix_len']}-char shared "
                f"prefix under LRU-overflow pressure, {r['weights']})"
            )
        elif args.spec:
            r = asyncio.run(run_spec_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.spec_k,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"spec-on output tokens/sec via /ollama/api/generate "
                f"({args.model}, speculative-decoding off/n-gram/"
                f"draft-model-tree A/B/C, K={args.spec_k}, "
                f"{args.requests} streams, repetitive workload, "
                f"{r['weights']})"
            )
        elif args.disagg:
            r = asyncio.run(run_disagg_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.long_prompt_len,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"split-fleet output tokens/sec via scheduler submit "
                f"({args.model}, disaggregated prefill/decode A/B with "
                f"KV-page migration, {args.requests} streams, "
                f"{r['weights']})"
            )
        elif args.fleet:
            r = asyncio.run(run_fleet_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.prompt_len,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"scaled-control-plane output tokens/sec via gateway-"
                f"replica submit ({args.model}, 2 gateways / 2 scheduler "
                f"shards vs single-box, {args.requests} streams, "
                f"{r['weights']})"
            )
        elif args.swap:
            r = asyncio.run(run_swap_bench(
                args.model, args.requests, args.tokens, args.slots,
            ))
            baseline = 0.0
            value = r.get("cold_start_speedup") or 0.0
            unit = "x"
            metric_name = (
                f"snapshot-warm vs fully-cold cold-start TTFT speedup "
                f"({args.model}, elastic-serving scenario: compile-cache "
                f"+ weight-snapshot swap-in, plus bursty two-model "
                f"elastic-vs-static A/B, {r['weights']})"
            )
        elif args.mixed:
            r = asyncio.run(run_mixed_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.long_prompt_len,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            metric_name = (
                f"mixed-workload output tokens/sec via /ollama/api/"
                f"generate ({args.model}, decode streams concurrent with "
                f"long chunked prefills, {args.requests} streams, "
                f"{r['weights']})"
            )
        else:
            r = asyncio.run(run_bench(
                args.model, args.requests, args.tokens, args.slots,
                args.prompt_len, profile_dir=args.profile,
            ))
            baseline = A100_OLLAMA_TOK_S.get(args.model, 0.0)
            value, unit = r["tok_s"], "tok/s"
            # the weights provenance lives IN the metric string so a
            # synthetic number can never be misread as a real-model one
            # (VERDICT r03 weak #4)
            metric_name = (
                f"output tokens/sec via /ollama/api/generate ({args.model}, "
                f"{args.requests} concurrent streams, {r['weights']})"
            )
    except Exception as e:  # noqa: BLE001 — report on the JSON line, then fail
        import traceback

        err_payload = {
            "metric": metric_name, "value": 0.0,
            "unit": "embeddings/s" if args.embed else "tok/s",
            "vs_baseline": 0.0, "platform": platform,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc().strip().splitlines()[-3:],
        }
        if args.emit:
            # the perf gate reads the record file — a crashed run must
            # leave one (with the error and no metrics) rather than
            # silently skipping the emit
            try:
                with open(args.emit, "w") as f:
                    json.dump({
                        "schema": BENCH_SCHEMA, "scenario": "error",
                        "model": args.model, "error": err_payload["error"],
                        "metrics": {}, "payload": err_payload,
                    }, f, indent=2, sort_keys=True)
                    f.write("\n")
            except OSError:
                pass
        emit(err_payload)
        return 1
    payload = {
        "metric": metric_name,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else None,
        "platform": platform,
        "wall_s": round(r["wall_s"], 2),
    }
    if args.spec:
        # the speculation headline (ISSUE 18, three arms): acceptance
        # rate and tokens per verify step for BOTH drafting backends —
        # the numbers --compare gates on (a collapse to acceptance ≈ 0
        # means drafting is pure verify overhead). ITL stays per-arm
        # inside "arms" (informational; tiny-CPU ITL is noise).
        payload["tok_s_spec_off"] = round(r["tok_s_spec_off"], 2)
        payload["spec_acceptance_rate"] = r["spec_acceptance_rate"]
        payload["spec_tokens_per_step"] = r["spec_tokens_per_step"]
        payload["spec_acceptance_rate_ngram"] = (
            r["spec_acceptance_rate_ngram"])
        payload["spec_tokens_per_step_ngram"] = (
            r["spec_tokens_per_step_ngram"])
        payload["spec_steps"] = r["spec_steps"]
        payload["spec_proposed"] = r["spec_proposed"]
        payload["spec_accepted"] = r["spec_accepted"]
        payload["arms"] = r["arms"]
        payload["tokens"] = r["tokens"]
    elif args.long_context:
        # the tiered-KV headline: the post-eviction round's warm TTFT
        # with the host tier on vs off (the recovery ratio), plus the
        # per-tier hit rates and restore counts that prove the tier —
        # not luck — did the work
        payload["p50_ttft_ms_cold"] = round(r["p50_ttft_ms_cold"], 1)
        payload["p50_ttft_ms_warm"] = round(r["p50_ttft_ms_warm"], 1)
        payload["p50_ttft_ms_post_on"] = round(r["p50_ttft_ms_post_on"], 1)
        payload["p50_ttft_ms_post_off"] = round(r["p50_ttft_ms_post_off"], 1)
        if r.get("ttft_recovery") is not None:
            payload["ttft_recovery"] = round(r["ttft_recovery"], 3)
        payload["restores"] = r["restores"]
        payload["kv_tier"] = r["kv_tier"]
        payload["tokens"] = r["tokens"]
    elif args.shared_prefix:
        # the prefix-cache headline: warm TTFT must beat cold, and the
        # warm round's prompt-page hit rate proves the cache did the work
        payload["p50_ttft_ms_cold"] = round(r["p50_ttft_ms_cold"], 1)
        payload["p50_ttft_ms_warm"] = round(r["p50_ttft_ms_warm"], 1)
        if r.get("ttft_speedup") is not None:
            payload["ttft_speedup"] = round(r["ttft_speedup"], 2)
        payload["prefix_cache_hit_rate"] = r["prefix_cache_hit_rate"]
        payload["prefix_cache_hit_rate_cold"] = r["prefix_cache_hit_rate_cold"]
        payload["prefix_cache"] = r["prefix_cache"]
        payload["tokens"] = r["tokens"]
    elif args.disagg:
        # the disaggregation headline: the split arm's decode-pool ITL
        # under mixed load (long prefills no longer inflate it) against
        # the unified arm's, plus migration volume/latency — both arms
        # ride the record so --compare gates the split numbers
        if r.get("p50_itl_ms") is not None:
            payload["p50_itl_ms"] = round(r["p50_itl_ms"], 2)
        if r.get("p50_ttft_ms") is not None:
            payload["p50_ttft_ms"] = round(r["p50_ttft_ms"], 1)
        payload["disagg"] = r["disagg"]
        payload["tokens"] = r["tokens"]
    elif args.fleet:
        # the control-plane headline: the scaled plane's TTFT/tok_s vs
        # the single-box arm (control-plane overhead under fan-out), and
        # the shard dispatch split proving both partitions carried load
        if r.get("p50_ttft_ms") is not None:
            payload["p50_ttft_ms"] = round(r["p50_ttft_ms"], 1)
        if r.get("p50_itl_ms") is not None:
            payload["p50_itl_ms"] = round(r["p50_itl_ms"], 2)
        payload["fleet"] = r["fleet"]
        payload["tokens"] = r["tokens"]
    elif args.swap:
        # the elastic-serving headline: the three cold-start arms (the
        # ≥3× snapshot-vs-cold gate), proof the snapshot tier — not luck
        # — did the work, and the bursty A/B where only the elastic arm
        # serves both models
        payload["cold_ttft_ms"] = round(r["cold_ttft_ms"], 1)
        payload["compile_warm_ttft_ms"] = round(r["compile_warm_ttft_ms"], 1)
        payload["snapshot_warm_ttft_ms"] = round(
            r["snapshot_warm_ttft_ms"], 1)
        if r.get("cold_start_speedup") is not None:
            payload["cold_start_speedup"] = round(r["cold_start_speedup"], 2)
        payload["snapshot_hit"] = r["snapshot_hit"]
        payload["cold_texts_identical"] = r["cold_texts_identical"]
        payload["snapshot_tier"] = r["snapshot_tier"]
        payload["compile_cache_dir_entries"] = r["compile_cache_dir_entries"]
        payload["bursty"] = r["bursty"]
    elif args.mixed:
        # the mixed-workload headline: the decode arm's ITL must survive
        # concurrent long prefills (single-launch mixed steps), and the
        # prefill arm's TTFT shows the chunked path's pace under load
        if r.get("p50_ttft_ms") is not None:
            payload["p50_ttft_ms"] = round(r["p50_ttft_ms"], 1)
        if r.get("p50_itl_ms") is not None:
            payload["p50_itl_ms"] = round(r["p50_itl_ms"], 2)
        payload["mixed"] = r["mixed"]
        payload["tokens"] = r["tokens"]
    elif not args.embed:
        payload["p50_ttft_ms"] = round(r["p50_ttft_ms"], 1)
        if r.get("p50_itl_ms") is not None:
            payload["p50_itl_ms"] = round(r["p50_itl_ms"], 1)
        payload["tokens"] = r["tokens"]
        if r.get("stages"):
            # per-stage breakdown from the obs tracer (queue-wait/prefill/
            # decode p50s) — explains the end-to-end numbers above
            payload["stages"] = r["stages"]
        if r.get("critical_path"):
            # additive per-segment p50 decomposition (ISSUE 17): unlike
            # the raw stage durations these sum to the traced e2e
            payload["critical_path"] = r["critical_path"]
        if r.get("slo_attainment") is not None:
            payload["slo_attainment"] = round(r["slo_attainment"], 4)
        if r.get("goodput_tok_s") is not None:
            payload["goodput_tok_s"] = round(r["goodput_tok_s"], 2)
        if r.get("capacity") is not None:
            # per-model demand/headroom snapshot + per-tenant token ledger
            # (ISSUE 16) — the capacity-smoke CI gate asserts the bench
            # traffic was attributed and the demand tracker saw it
            payload["capacity"] = r["capacity"]
        if r.get("fleet_health") is not None:
            # canary probe summary + worker health-state counts (ISSUE
            # 19) — a healthy bench run records zero quarantines
            payload["fleet_health"] = r["fleet_health"]
    else:
        payload["texts"] = r["texts"]
    # perf introspection always rides the driver line when measured —
    # steady-state recompiles and peak HBM are headline health signals
    perf_side = r.get("perf")
    if perf_side:
        payload["recompiles_steady"] = perf_side["recompiles_steady"]
        if perf_side.get("peak_hbm_bytes"):
            payload["peak_hbm_bytes"] = perf_side["peak_hbm_bytes"]
    scenario = ("embed" if args.embed
                else "shared-prefix" if args.shared_prefix
                else "long-context" if args.long_context
                else "spec" if args.spec
                else "mixed" if args.mixed
                else "disagg" if args.disagg
                else "fleet" if args.fleet
                else "swap" if args.swap else "generate")
    record = build_record(scenario, args, payload, r)
    regressions: list = []
    if args.compare:
        # a missing/corrupt baseline (first run of a CI gate, truncated
        # artifact) is a note, never a crash — the one-JSON-line driver
        # contract holds and the gate passes until a real baseline exists
        try:
            with open(args.compare) as f:
                baseline = json.load(f)
        except (OSError, ValueError) as e:
            baseline = None
            notes = [f"baseline unreadable ({type(e).__name__}: {e}) — "
                     "comparison skipped"]
        if baseline is not None:
            regressions, notes = compare_records(
                baseline, record, threshold=args.regression_threshold)
        payload["compare"] = {"baseline": args.compare,
                              "regressions": regressions, "notes": notes}
        record["compare"] = payload["compare"]
    if args.emit:
        with open(args.emit, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    emit(payload)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
