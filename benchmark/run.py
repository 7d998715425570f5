#!/usr/bin/env python3
"""One run of one cell of the benchmark, against the deployed path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Parent (this process, which never imports jax: the chip belongs to the
worker) -> broker, gateway and ``launch_worker.py`` as child processes ->
wait until the worker has registered with its model -> warm-up of this
cell's shapes -> read counters -> a window of ``--seconds`` of open-loop
traffic over HTTP -> drain -> read counters -> stop the processes ->
reference check (and, with ``--trace 1``, the trace's reduction) in
children -> ONE JSON line, the last of standard output (its last key,
``compared``, has each number `correct` compares beside its limit; the
same numbers are the last lines of standard error). Everything else goes
on earlier lines or under ``benchmark/out/``.

A cell is found by name: ``BENCHMARK.json`` gives its configuration and
traffic mix, ``workloads/<cell>.json`` its rate, ``configs/<config>.json``
the model and the deployment's settings, ``traffic/<mix>.json`` the mix,
``layer_metrics/<metric>.py`` each per-layer reader. See README.md.

``--rehearse`` runs the same code on the CPU at a tiny size (stamped
``"platform": "cpu"``, ``"rehearsal": true``); without it a machine with
no TPU, or fewer chips than the cell asks for, is a non-zero exit and no
result. ``--sweep r1,r2,...`` finds the knee instead of measuring (one
stack, one window a rate, a table, no result line).
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import launch_worker  # noqa: E402
import loadgen  # noqa: E402
import stack as st  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import trafficgen  # noqa: E402
from stack import Failed, say  # noqa: E402

DRAIN_S = 10.0
PROFILE_S = 5.0
SAMPLE_EVERY_S = 0.5
REFERENCE_RECORDS = 2
REFERENCE_MAX_PROMPT = 2048


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """Everything a cell's name leads to, found by name under `bench_dir`
    (and ``BENCHMARK.json`` beside it)."""

    def __init__(self, workload: str, bench_dir: str = HERE):
        self.bench_dir = bench_dir
        root = os.path.dirname(bench_dir)
        self.manifest = load_json(root, "BENCHMARK.json")
        entry = next((w for w in self.manifest["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.name, self.chips = workload, entry["chips"]
        cfg = next(c for c in self.manifest["configs"]
                   if c["name"] == entry["config"])
        self.config_name = cfg["name"]
        self.config_file = os.path.join(root, cfg["file"])
        self.config = load_json(self.config_file)
        launch_worker.check_deployment(self.config, self.config_name)
        self.mix = load_json(bench_dir, "traffic", entry["traffic"] + ".json")
        self.params = load_json(bench_dir, "workloads", workload + ".json")
        self.rate = float(self.params["rate"])

    def metric_names(self, group: str) -> list[str]:
        return [m["name"] for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, name: str):
        path = os.path.join(self.bench_dir, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def warmup_requests(schedule: list, slots: int, chunk: int,
                    buckets: list[int], seed: int) -> list[list]:
    """Two phases of requests that touch every shape this cell's window
    will use, and no other: (1) a burst of `slots` short streams (the
    verify step at full batch) beside one prompt for every prefill bucket
    its prompts pad to, its longest chunked prompt and one shared
    document; (2) the last two of those again, now prefix-cache hits, and
    a second question of the document."""
    import random

    rng = random.Random(f"warmup/{seed}")
    mk = trafficgen.text
    lens = [len(r.prompt) + 1 for r in schedule]       # + BOS
    reached = sorted({next((b for b in buckets if b >= t), buckets[-1])
                      for t in lens if t <= chunk})
    cold = [(mk(min(b, chunk) - 8, f"(b{seed}.{b}) ", rng), 8) for b in reached]
    if any(t > chunk for t in lens):
        cold.append((mk(max(lens) - 1, f"(c{seed}) ", rng), 8))
    second = list(cold[-2:])
    shared = [r.shared_bytes for r in schedule if r.shared_bytes]
    if shared:
        doc = mk(max(shared), f"(d{seed}) ", rng)
        cold.append((doc + mk(64, "(q1) ", rng), 8))
        second.append((doc + mk(64, "(q2) ", rng), 8))
    # never more at once than the worker has slots: it refuses the rest,
    # and the scheduler then retries them seconds later
    first = [(mk(48, f"(w{seed}.{i}) ", rng), 24)
             for i in range(max(1, slots - len(cold)))]
    return [[trafficgen.Request(i, 0.0, "warm", -1, p, 0, n)
             for i, (p, n) in enumerate(phase)]
            for phase in (first + cold, second)]


def reference_sample(requests: list, rule: dict | None = None) -> set[int]:
    """The requests whose served tokens the reference checks, by the
    cell's own `rule` (``workloads/<cell>.json`` ``reference``; absent:
    ``max_prompt`` REFERENCE_MAX_PROMPT, the shortest). The shortest
    prompt, or with ``"prefer": "longest"`` the longest (where a window, a
    ring or a selection does the most), none over ``max_prompt``; and,
    where prompts share a document, a later question of the same document
    (one cold admission, one from the prefix cache); else a prompt near the
    median length. No prompt fits: no record, and `correct` is false."""
    rule = rule or {}
    limit = rule.get("max_prompt", REFERENCE_MAX_PROMPT)
    prefer = rule.get("prefer", "shortest")
    if prefer not in ("shortest", "longest"):
        raise ValueError(f"reference.prefer {prefer!r}: shortest or longest")
    sign = -1 if prefer == "longest" else 1
    fits = sorted((r for r in requests if len(r.prompt) <= limit),
                  key=lambda r: (sign * len(r.prompt), r.index))
    if not fits:
        return set()
    a = fits[0]
    mates = [r for r in fits if r.shared_bytes and r.group == a.group
             and r.stream == a.stream and r.index != a.index]
    b = mates[0] if mates else fits[len(fits) // 2]
    return {a.index, b.index}


def deployment_env(cfg: dict, rehearse: bool) -> dict:
    """A configuration's own part of its children's environment: its
    ``env`` (in a rehearsal the CPU and ``rehearse_env`` over it) and the
    mesh. ``mesh`` is the one source of ``GRIDLLM_MESH_SHAPE``, for the
    worker and the reference child alike, whatever the caller's
    environment holds (``check_deployment`` refuses an ``env`` that differs)."""
    env = dict(cfg.get("env", {}))
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env.update(cfg.get("rehearse_env", {}))
    env["GRIDLLM_MESH_SHAPE"] = cfg.get("mesh") or ""
    return env


class Run:
    def __init__(self, args: argparse.Namespace):
        self.args = args
        self.cell = Cell(args.workload)
        self.rehearse = args.rehearse
        self.out_dir = args.out_dir or os.path.join(
            HERE, "out", f"{args.workload}-s{args.seed}-t{args.trace}")
        os.makedirs(self.out_dir, exist_ok=True)
        self.model = self.cell.config_name
        cfg = self.cell.config
        bp, gp, wp = st.free_port(), st.free_port(), st.free_port()
        self.gw = f"http://127.0.0.1:{gp}"
        self.wk = f"http://127.0.0.1:{wp}"
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        env.update({
            "PYTHONPATH": ROOT + os.pathsep + HERE,
            "GRIDLLM_BUS_URL": f"resp://127.0.0.1:{bp}",
            "GRIDLLM_MODELS": self.model, "PORT": str(gp),
            "WORKER_PORT": str(wp), "WORKER_ID": "bench-worker",
            "LOG_LEVEL": "info",
            "GRIDLLM_PROFILE_DIR": os.path.join(self.out_dir, "profile"),
        })
        env.update(deployment_env(cfg, self.rehearse))
        self.env, self.broker_port = env, bp
        self.stack = st.Stack(self.out_dir, env, ROOT)
        self.slots = int(env.get("GRIDLLM_MAX_BATCH_SLOTS", "8"))
        self.buckets = [int(b) for b in env.get(
            "GRIDLLM_PREFILL_BUCKETS", "512,1024,2048,4096,8192").split(",")]
        self.chunk = 128 if self.rehearse else 1024
        self.mix = self.rehearse_mix() if self.rehearse else self.cell.mix

    def rehearse_mix(self) -> dict:
        """The cell's mix with every length divided by 24, or by as much
        more as brings its longest prompt under 180: the tiny presets
        hold 256 positions."""
        mix = json.loads(json.dumps(self.cell.mix))
        def top(d: dict | None) -> int:
            return (d or {}).get("max", (d or {}).get("value", 0))

        longest = max(top(s.get("shared_tokens")) + top(s.get("own_tokens"))
                      for s in mix["streams"])
        by = max(24, -(-longest // 180))
        for s in mix["streams"]:
            for key in ("shared_tokens", "own_tokens", "output_tokens"):
                d = s.get(key)
                if not d:
                    continue
                for k in ("value", "median", "min", "max"):
                    if k in d:
                        d[k] = max(4, d[k] // by)
        return mix

    # -- start-up ------------------------------------------------------
    def start(self) -> dict:
        if not self.rehearse and os.environ.get("JAX_PLATFORMS", "") == "cpu":
            say("JAX_PLATFORMS=cpu: no accelerator here. The benchmark "
                "measures nothing on a CPU; --rehearse is the explicit rehearsal")
            raise SystemExit(3)
        s = self.stack
        s.spawn("broker", "-m", "gridllm_tpu.bus.broker", "--host",
                "127.0.0.1", "--port", str(self.broker_port))
        time.sleep(0.3)
        s.spawn("gateway", "-m", "gridllm_tpu.gateway.main")
        argv = [os.path.join(HERE, "launch_worker.py"), "--config",
                self.cell.config_file]
        s.spawn("worker", *argv, *(["--rehearse"] if self.rehearse else []))
        want = "cpu" if self.rehearse else "tpu"
        end = time.monotonic() + (600 if self.rehearse else 1100)
        seen_backend = False
        while True:
            s.check_alive()
            if not seen_backend:
                lines = s.grep("worker", "jax backend")
                if lines:
                    seen_backend = True
                    say("worker: " + lines[-1][-200:])
                    if f'"platform": "{want}"' not in lines[-1] and (
                            f"platform={want}" not in lines[-1]):
                        say(f"the worker's jax backend is not {want}: no "
                            "result is printed")
                        raise SystemExit(3)
            try:
                ws = st.http_json(f"{self.gw}/health/workers", timeout=5)["workers"]
                mine = [w for w in ws if self.model in w["models"] and w["topology"]]
                if mine:
                    break
            except (OSError, ValueError, KeyError):
                pass
            if time.monotonic() > end:
                raise Failed("worker not registered in time")
            time.sleep(0.25)
        topo = mine[0]["topology"]
        device = {"platform": topo["platform"], "kind": topo["deviceKind"],
                  "count": topo["numDevices"]}
        if device["platform"] != want or (
                not self.rehearse and device["count"] < self.cell.chips):
            say(f"worker runs on {device}, the cell needs {want} x"
                f"{self.cell.chips}: no result is printed")
            raise SystemExit(3)
        for needle in ("kv pool sized", "engine ready"):
            for line in s.grep("worker", needle)[-1:]:
                say("worker: " + line[line.index("["):][:300] if "[" in line else line[:300])
        return device

    def pool(self) -> dict:
        lines = self.stack.grep("worker", "kv pool sized")
        try:
            return json.loads(lines[-1][lines[-1].index("{"):])
        except (IndexError, ValueError):
            return {}

    def scrape(self) -> tuple[str, str]:
        return (st.http_text(f"{self.wk}/metrics", 20),
                st.http_text(f"{self.gw}/metrics", 20))

    # -- traffic -------------------------------------------------------
    def play(self, requests, t0, end_by, keep=lambda r: False, background=()):
        return asyncio.run(loadgen.play(
            f"{self.gw}/ollama/api/generate", self.model, requests, t0,
            end_by, keep, background))

    def warm_up(self, schedule) -> None:
        for i, phase in enumerate(warmup_requests(
                schedule, self.slots, self.chunk, self.buckets, self.args.seed)):
            t = time.monotonic()
            outs = self.play(phase, t, t + 600)
            bad = [o for o in outs if stats.failed(o)]
            if bad:
                raise Failed(f"warm-up request failed: {bad[0].error or 'unfinished'}")
            say(f"warm-up phase {i + 1}: {len(outs)} requests (prompt bytes "
                f"{sorted(len(r.prompt) for r in phase)[-4:]} longest) in "
                f"{time.monotonic() - t:.1f}s")

    async def sampler(self, samples: list) -> None:
        import aiohttp

        async with aiohttp.ClientSession() as s:
            while True:
                await asyncio.sleep(SAMPLE_EVERY_S)
                try:
                    async with s.get(f"{self.wk}/metrics") as r:
                        samples.append((time.monotonic(), await r.text()))
                except aiohttp.ClientError:
                    pass

    async def profiler(self, at: float, got: dict) -> None:
        import aiohttp

        await asyncio.sleep(max(0.0, at - time.monotonic()))
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{self.wk}/metrics") as r:
                before = await r.text()
            async with s.post(
                    f"{self.wk}/admin/profile?seconds={PROFILE_S:g}") as r:
                got["capture"] = await r.json()
            await asyncio.sleep(PROFILE_S)
            async with s.get(f"{self.wk}/metrics") as r:
                got["counters"] = (before, await r.text())

    # -- after the window ------------------------------------------------
    def ring_events(self) -> tuple[list, list]:
        dump = st.http_json(f"{self.wk}/admin/dump", timeout=20)
        bad = [e for e in dump["flightRecorder"]["rings"].get("engine", [])
               if e["event"] in ("step_failure", "runner_dead")]
        gdump = st.http_json(f"{self.gw}/admin/dump", timeout=20)
        hangs = [e for e in gdump["flightRecorder"]["rings"].get("scheduler", [])
                 if e["event"] == "hang"]
        return bad, hangs

    def child(self, name: str, argv: list[str], env: dict) -> subprocess.Popen:
        with open(os.path.join(self.out_dir, f"{name}.log"), "wb") as out:
            return subprocess.Popen([sys.executable, *argv], env=env, cwd=ROOT,
                                    stdout=out, stderr=subprocess.STDOUT,
                                    start_new_session=True)

    def child_result(self, name: str, proc: subprocess.Popen, prefix: str,
                     timeout: float):
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            say(f"{name} did not end within {timeout:.0f}s")
            return None
        with open(os.path.join(self.out_dir, f"{name}.log"), errors="replace") as f:
            lines = f.read().splitlines()
        for line in reversed(lines):
            if line.startswith(prefix):
                return json.loads(line[len(prefix):])
        say(f"{name} exited {proc.returncode} without a result:\n"
            + "\n".join(lines[-15:]))
        return None


def window(run: Run, requests, seconds: float, traced: bool, keep) -> dict:
    """One window plus its drain; returns what the readers need."""
    samples, got, background = [], {}, []
    worker_before, gateway_before = run.scrape()
    t0 = time.monotonic() + 0.25
    if traced:
        background = [run.sampler(samples),
                      run.profiler(t0 + seconds / 3.0, got)]
    outcomes = run.play(requests, t0, t0 + seconds + DRAIN_S, keep, background)
    worker_after, gateway_after = run.scrape()
    return {"requests": requests, "outcomes": outcomes, "seconds": seconds,
            "drain_s": DRAIN_S, "t0": t0, "worker_before": worker_before,
            "worker_after": worker_after, "gateway_before": gateway_before,
            "gateway_after": gateway_after, "samples": samples,
            "trace_counters": got.get("counters"), "capture": got.get("capture")}


def sweep(run: Run, rates: list[float], seconds: float) -> None:
    """The knee: the highest rate at which >= 97 % of the requests due in
    the window finish within window + drain, the last quarter's median
    TTFT is under 1.5x the first quarter's, and TTFT p95 is under 2x its
    value at the first (lowest) rate swept."""
    rows = []
    for rate in rates:
        requests = trafficgen.generate(run.mix, rate, seconds, run.args.seed)
        w = window(run, requests, seconds, False, lambda r: False)
        outs = w["outcomes"]
        done = [o for o in outs if not stats.failed(o)]
        ttft = stats.ttfts_ms(outs, (seconds + DRAIN_S) * 1e3)
        q = max(1, len(outs) // 4)
        first, last = stats.percentile(ttft[:q], 0.5), stats.percentile(ttft[-q:], 0.5)
        e2e = stats.end_to_end(outs, w["t0"], seconds, DRAIN_S)
        row = {"rate": rate, "due": len(outs), "finished_pct":
               100.0 * len(done) / len(outs), "ttft_first_q_ms": first,
               "ttft_last_q_ms": last, **e2e,
               "sustained": len(done) >= 0.97 * len(outs) and last < 1.5 * first}
        row["sustained"] = row["sustained"] and (
            row["ttft_p95_ms"] < 2.0 * (rows[0] if rows else row)["ttft_p95_ms"])
        rows.append(row)
        say("SWEEP " + json.dumps(row))
        time.sleep(2.0)
    with open(os.path.join(run.out_dir, "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny preset, lengths / 16; never a result")
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates: find the knee, print no result")
    ap.add_argument("--no-reference", action="store_true",
                    help="skip the reference check (correct is then false)")
    ap.add_argument("--out-dir", default="")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "gridllm_tpu")):
        say(f"{ROOT} holds no gridllm_tpu package: the benchmark runs the "
            "program from the root of a checkout")
        return 2
    run = Run(args)
    seconds = args.seconds or float(run.cell.manifest["run_seconds"])
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    result = None
    try:
        device = run.start()
        t_registered = time.monotonic()
        requests = trafficgen.generate(run.mix, run.cell.rate, seconds, args.seed)
        run.warm_up(requests)
        t_warm = time.monotonic()
        if args.sweep:
            sweep(run, [float(r) for r in args.sweep.split(",")], seconds)
            return 0
        keep_set = reference_sample(requests,
                                    run.cell.params.get("reference"))
        w = window(run, requests, seconds, bool(args.trace),
                   lambda r: r.index in keep_set)
        setup_s = w["t0"] - T_START
        path = (w.get("capture") or {}).get("path")
        end = time.monotonic() + 90
        while path and time.monotonic() < end and not _trace_file(path):
            run.stack.check_alive()     # the worker writes the trace
        say(f"set-up {setup_s:.1f}s: start to registered "
            f"{t_registered - T_START:.1f}s, warm-up {t_warm - t_registered:.1f}s")
        memory = st.http_json(f"{run.wk}/admin/memory", timeout=30)
        bad, hangs = run.ring_events()
        pool = run.pool()
        result, checks, e2e = finish(run, w, device, memory, bad, hangs,
                                     pool, setup_s)
    except Failed as e:
        say(f"FAILED: {e}")
        if e.child in run.stack.procs:
            say(f"--- tail of {e.child}.log ---")
            print(run.stack.tail(e.child), flush=True)
        return 1
    finally:
        run.stack.stop()
    if result is None:
        return 1
    print(json.dumps(after_stop(run, w, result, checks, e2e)), flush=True)
    return 0


def finish(run: Run, w: dict, device: dict, memory: dict, bad: list,
           hangs: list, pool: dict, setup_s: float) -> tuple[dict, dict, dict]:
    """What can be said while the serving processes live: the result's
    frame, the checks behind `correct` so far, the end-to-end metrics."""
    outs, reqs, seconds = w["outcomes"], w["requests"], w["seconds"]
    e2e = stats.end_to_end(outs, w["t0"], seconds, DRAIN_S)
    e2e["setup_s"] = setup_s
    n_failed = sum(stats.failed(o) for o in outs)
    malformed = [m for m in (stats.malformed(o, r.num_predict)
                             for o, r in zip(outs, reqs)) if m]
    jnp_built = st.metric_sum(w["worker_after"],
                              "gridllm_kernel_dispatch_total", path="jnp")
    recompiled = (st.metric_sum(w["worker_after"], "gridllm_recompiles_total")
                  - st.metric_sum(w["worker_before"], "gridllm_recompiles_total"))
    live_hangs = [h for h in hangs if h.get("phase") in ("prefill", "decode-step")]
    checks = {
        "platform": device["platform"] == ("cpu" if run.rehearse else "tpu")
        and (run.rehearse or device["count"] >= run.cell.chips),
        "no_jnp_kernel_path": jnp_built == 0,
        "no_recompile_in_window": recompiled == 0,
        "no_engine_failure": not bad, "no_hang_requeue": not live_hangs,
        "streams_well_formed": not malformed,
    }
    # each number `correct` compares, beside its limit
    w["compared"] = {"jnp_kernel_paths": [jnp_built, 0],
                     "recompiles_in_window": [recompiled, 0],
                     "engine_failures": [len(bad), 0],
                     "hang_requeues": [len(live_hangs), 0],
                     "malformed_streams": [len(malformed), 0]}
    for text in malformed[:3]:
        say("malformed: " + text)
    for o in [o for o in outs if stats.failed(o)][:3]:
        say(f"failed: request {o.index} ({len(reqs[o.index].prompt)} prompt "
            f"bytes): {o.error or 'unfinished at the end of the drain'}")
    peaks = [d.get("peakBytesInUse") or 0 for d in memory.get("devices", {}).values()]
    device = {**device, "memory_peak_bytes": max(peaks, default=0)}
    spec_prop = (st.metric_sum(w["worker_after"], "gridllm_spec_proposed_tokens_total")
                 - st.metric_sum(w["worker_before"], "gridllm_spec_proposed_tokens_total"))
    spec_acc = (st.metric_sum(w["worker_after"], "gridllm_spec_accepted_tokens_total")
                - st.metric_sum(w["worker_before"], "gridllm_spec_accepted_tokens_total"))
    n95 = stats.samples_beyond(len(outs), 0.95)
    say(f"window: {len(outs)} requests due, {n_failed} failed or unfinished, "
        f"{n95} samples beyond the 95th percentile; prompt tokens "
        f"{sum(len(r.prompt) for r in reqs)}, output tokens asked "
        f"{sum(r.num_predict for r in reqs)}; speculation accepted "
        f"{spec_acc:.0f} of {spec_prop:.0f} proposed")
    with open(os.path.join(run.out_dir, "outcomes.json"), "w") as f:
        json.dump([{"index": r.index, "stream": r.stream, "group": r.group,
                    "due_s": r.due_s, "prompt_bytes": len(r.prompt),
                    "shared_bytes": r.shared_bytes, "num_predict": r.num_predict,
                    "failed": stats.failed(o), "eval_count": o.eval_count,
                    "late_ms": None if o.sent is None else (o.sent - o.due) * 1e3,
                    "frames_ms": [(t - o.due) * 1e3 for t, _ in o.frames]}
                   for r, o in zip(reqs, outs)], f)
    w.update({"memory": memory, "pool": pool, "config": run.cell.config,
              "device": device, "prefill_buckets": run.buckets,
              "prefill_chunk": run.chunk})
    return ({"correct": False, "attempted": len(outs), "failed": int(n_failed),
             "metrics": {}, "device": device}, checks, e2e)


def after_stop(run: Run, w: dict, result: dict, checks: dict,
               e2e: dict) -> dict:
    """The chip is free: the reference check takes it, the trace's
    reduction runs beside it on the CPU; then the metrics and the line."""
    args, manifest = run.args, run.cell.manifest
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in manifest[g]}
    ref_proc = trace_proc = None
    records = [{"index": o.index, "context": o.context,
                "n_prompt": len(o.context) - o.eval_count}
               for o in w["outcomes"]
               if o.context and o.eval_count and not stats.failed(o)]
    if not args.no_reference:
        path = os.path.join(run.out_dir, "records.json")
        with open(path, "w") as f:
            json.dump(records[:REFERENCE_RECORDS], f)
        ref_proc = run.child("reference", [
            os.path.join(HERE, "reference_check.py"), "--config",
            run.cell.config_file, "--records", path,
            *(["--rehearse"] if run.rehearse else [])], run.env)
    capture = w.get("capture") or {}
    if args.trace and capture.get("path"):
        env = {**run.env, "JAX_PLATFORMS": "cpu"}
        trace_proc = run.child("trace_reduce", [
            os.path.join(HERE, "trace_reduce.py"), capture["path"]], env)
    w["trace"] = {}
    if trace_proc is not None:
        w["trace"] = run.child_result("trace_reduce", trace_proc, "TRACE=", 200) or {}
        if w["trace"]:
            with open(os.path.join(run.out_dir, "trace.json"), "w") as f:
                json.dump(w["trace"], f)
    if ref_proc is not None:
        ref = run.child_result(
            "reference", ref_proc, "REFERENCE=",
            run.cell.config["reference"].get("timeout_s", 300)) or {}
        say("reference: " + json.dumps(ref)[:1500])
        checks["reference_agrees"] = bool(ref.get("agrees"))
        for rec in ref.get("records", []):
            w["compared"][f"shortfall_r{rec['index']}"] = [
                rec["worst_shortfall"], rec["allowed_there"]]
        if "mean_shortfall" in ref:
            w["compared"]["mean_shortfall"] = [ref["mean_shortfall"],
                                               ref["mean_allowed"]]
    else:
        checks["reference_agrees"] = False
    result["correct"] = all(checks.values())
    say("checks: " + json.dumps(checks))
    if args.trace:
        sources = {m["name"]: m["source"] for m in manifest["per_layer"]}
        for name in run.cell.metric_names("per_layer"):
            value = run.cell.reader(name).compute(w)
            if value is None:
                continue
            if run.rehearse and sources[name] == "device_trace":
                # a CPU number never goes under a device metric's name
                say(f"rehearsal only, not a device number: {name} reader "
                    f"returned {value}")
                continue
            result["metrics"][name] = {"value": value, "unit": units[name]}
        tr = w["trace"]
        if tr and not run.rehearse:
            result["device"]["busy_s"] = tr["busy_s"]
            result["device"]["window_s"] = tr["window_s"]
            result["breakdown"] = tr["breakdown"]
        if tr:
            say("programs: " + json.dumps(tr["programs"])[:1500])
        say("end to end in the traced run: " + json.dumps(e2e))
    else:
        for name in run.cell.metric_names("end_to_end"):
            result["metrics"][name] = {"value": e2e[name], "unit": units[name]}
    if run.rehearse:
        result["rehearsal"] = True
    # last in the line and last on standard error: number, then limit
    result["compared"] = w["compared"]
    for name, (value, limit) in w["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    print(f"correct: {result['correct']} checks: {json.dumps(checks)}",
          file=sys.stderr, flush=True)
    return result


def _trace_file(path: str) -> bool:
    """The capture's .xplane.pb is there and has stopped growing."""
    f = trace_reduce.find_xplane(path)
    if not f:
        return False
    size = os.path.getsize(f)
    time.sleep(0.5)
    return size > 0 and os.path.getsize(f) == size


if __name__ == "__main__":
    sys.exit(main())
