#!/usr/bin/env python
"""Start the system on the chip, the way a user would, and check it serves.

The quickest proof that GridLLM-TPU still starts on a TPU: the three
processes the README and the compose files describe — broker, gateway,
worker — wired over ``resp://``, one model at its registered widths
(``llama3.2:3b``, bf16, random weights from the fixed seed, byte tokenizer),
every engine default as shipped (ragged attention, speculative decoding,
prefix cache), and a handful of real HTTP requests through the gateway.
``deploy/smoke_local.py`` is its CPU counterpart for API shapes.

    python chip_smoke.py          # on a machine with a TPU
    python chip_smoke.py --cpu    # rehearsal: tiny-llama, kernels interpreted

Without ``--cpu`` a machine with no TPU is a failure, never a CPU run. This
process never imports jax — a chip belongs to one process, and that process
is the worker. It checks, from what the processes themselves report:

- the worker registered on ``platform == "tpu"`` (``/health/workers``);
- two non-streaming generates (a prompt of a few hundred bytes, which
  pads to a bucket and runs flash prefill; one longer than a prefill
  chunk, which runs the chunk program twice), a streaming chat
  completion and eight concurrent streams of mixed prompt lengths (two
  longer than a chunk, so chunked prefill and mixed steps run while the
  others decode) all finish with HTTP 200, ``done`` and
  ``eval_count == num_predict``; streams deliver frames before they end;
- both generates again, twice each — prefix-cache hits, on slots and
  pages the streams have used since. The two hits must answer byte for
  byte alike: same program, same inputs, whatever the mesh. The long
  prompt's hit must also equal its cold answer: it is sized so that the
  cache covers exactly its first chunk, and the hit then runs the cold
  admission's last chunk again — the same tokens in the same rows of the
  same program, so a difference is state that leaked, not arithmetic.
  (Rows matter under a mesh: the chip's cross-chip sum rounds a row by
  its place in the buffer, and a hit that moved the tail tokens to other
  rows parted from its cold answer at token 47 under tp:4.) The short
  prompt cold (flash prefill) and cached (the chunk program) is two
  programs; their last-token logits are 0.06-0.08 apart at these widths,
  all 28 layers, with the kernels on or off, and a random-weight model's
  top two candidates 0.005-0.03, so their greedy tokens part (my chip
  runs, PR 21; PERF.md findings 6 and 9). Where they part is printed, not
  asserted; the reference check below holds the two programs' logits to
  the jnp model's instead;
- every kernel dispatch on the path was built on the Pallas kernel and
  none on the jnp reference (worker ``/metrics``), the engine recorded no
  ``step_failure`` or ``runner_dead`` (worker ``/admin/dump``) and the hang
  watchdog requeued nothing (gateway ``/admin/dump``); with
  ``GRIDLLM_SANITIZE=1 GRIDLLM_NUMCHECK_SAMPLE=1`` in the environment the
  worker shadows every kernel launch with its jnp oracle inside the real
  programs, and its ``numcheck`` ring must then be empty too;
- device memory is a real figure and, under a mesh (``GRIDLLM_MESH_SHAPE``
  is passed through), spread over every device (``/admin/memory``);
- the persistent compile cache holds entries afterwards;
- with the three processes gone, ``deploy/tpu_kernel_bisect.py paths`` in
  a child of its own: the short prompt's two programs against the all-jnp
  model at the same widths, depth cut to two layers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only if every check passed. Exit codes: 0 passed; 1 a check failed
or timed out (the failing child's log tail is printed); 2 not a checkout;
3 no TPU.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from importlib import metadata

REPO = os.path.dirname(os.path.abspath(__file__))

# The whole run must end inside the chip check's 1200 s, compiles included.
DEADLINE_S = 1100.0


class Failed(Exception):
    """A check failed; `child` names the process whose log explains it."""

    def __init__(self, msg: str, child: str = "worker"):
        super().__init__(msg)
        self.child = child


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 10.0):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if r.status != 200:
            raise Failed(f"{url}: HTTP {r.status}", "gateway")
        return json.load(r)


def http_text(url: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def http_stream(url: str, body: dict, timeout: float) -> list[tuple[float, str]]:
    """POST and read the response line by line: (arrival time, line)."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    lines: list[tuple[float, str]] = []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if r.status != 200:
            raise Failed(f"{url}: HTTP {r.status}", "gateway")
        for raw in r:
            line = raw.decode().strip()
            if line:
                lines.append((time.monotonic(), line))
    return lines


class Stack:
    """The three child processes, their logs, and their end."""

    def __init__(self, log_dir: str, env: dict[str, str]):
        self.log_dir = log_dir
        self.env = env
        self.procs: dict[str, subprocess.Popen] = {}
        os.makedirs(log_dir, exist_ok=True)

    def log_path(self, name: str) -> str:
        return os.path.join(self.log_dir, f"{name}.log")

    def spawn(self, name: str, *argv: str) -> None:
        with open(self.log_path(name), "wb") as out:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], env=self.env, cwd=REPO, stdout=out,
                stderr=subprocess.STDOUT, start_new_session=True)

    def check_alive(self) -> None:
        for name, p in self.procs.items():
            if p.poll() is not None:
                raise Failed(f"{name} exited (rc={p.returncode})", name)

    def tail(self, name: str, n: int = 40) -> str:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"(no log: {e})"

    def grep(self, name: str, needle: str) -> list[str]:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return [ln.rstrip() for ln in f if needle in ln]
        except OSError:
            return []

    def stop(self) -> None:
        for sig, grace in ((signal.SIGTERM, 10.0), (signal.SIGKILL, 5.0)):
            for p in self.procs.values():
                if p.poll() is None:
                    try:
                        os.killpg(p.pid, sig)
                    except ProcessLookupError:
                        pass
            end = time.monotonic() + grace
            for p in self.procs.values():
                try:
                    p.wait(timeout=max(0.1, end - time.monotonic()))
                except subprocess.TimeoutExpired:
                    pass


def probe_platform(env: dict[str, str]) -> dict:
    """What jax finds, asked of a child that exits before the worker
    starts (a parent that touched jax would hold the chip)."""
    code = ("import jax, json; d = jax.devices(); print('DEVICE=' + "
            "json.dumps({'platform': d[0].platform, 'kind': "
            "d[0].device_kind, 'count': len(d)}))")
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=180)
    except subprocess.TimeoutExpired:
        raise Failed("jax did not list its devices within 180 s", "probe")
    for line in out.stdout.splitlines():
        if line.startswith("DEVICE="):
            return json.loads(line[len("DEVICE="):])
    raise Failed("jax could not initialise a backend:\n"
                 + (out.stderr or out.stdout)[-2000:], "probe")


def metric_values(text: str, name: str) -> dict[tuple[tuple[str, str], ...], float]:
    """Samples of one Prometheus series: {sorted label pairs: value}."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name)] not in "{ ":
            continue
        head, _, value = line.rpartition(" ")
        labels = ()
        if "{" in head:
            inner = head[head.index("{") + 1:head.rindex("}")]
            labels = tuple(sorted(
                (k, v.strip('"')) for k, v in
                (pair.split("=", 1) for pair in inner.split(",") if pair)))
        out[labels] = float(value)
    return out


def prompt_of(n_bytes: int, tag: str) -> str:
    """A deterministic prompt of exactly n_bytes bytes (one token each
    under the byte tokenizer) that shares no leading page with another
    tag's."""
    head = f"[{tag}] "
    body = "The quick brown fox jumps over the lazy dog. "
    return (head + body * (n_bytes // len(body) + 1))[:n_bytes]


def run(args: argparse.Namespace) -> dict:
    t_start = time.monotonic()
    cpu = args.cpu
    model = "tiny-llama" if cpu else "llama3.2:3b"
    if not os.path.isdir(os.path.join(REPO, "gridllm_tpu")):
        say(f"{REPO} holds no gridllm_tpu package — chip_smoke.py runs from "
            "the root of a checkout")
        raise SystemExit(2)
    from gridllm_tpu.utils.config import compile_cache_dir  # jax-free

    broker_port, gw_port, worker_port = free_port(), free_port(), free_port()
    env = {
        **os.environ,
        "PYTHONPATH": REPO,
        "GRIDLLM_BUS_URL": f"resp://127.0.0.1:{broker_port}",
        "GRIDLLM_MODELS": model,
        "PORT": str(gw_port),
        "WORKER_PORT": str(worker_port),
        "WORKER_ID": "chip-smoke-worker",
        "LOG_LEVEL": "info",
    }
    if cpu:
        # the one rehearsal switch: everything below it is the same code
        env.update({
            "JAX_PLATFORMS": "cpu", "GRIDLLM_PALLAS": "interpret",
            "GRIDLLM_KV_PAGE_SIZE": "16", "GRIDLLM_PREFILL_BUCKETS": "32,128",
        })
        want_platform = "cpu"
        # tiny-llama's whole context (256) is one chunk: "long" only
        # pads to a larger bucket here
        sizes = {"first": 40, "long": 150, "predict": 16, "chat_predict": 12,
                 "streams": [8, 40, 90, 150, 20, 200, 60, 120],
                 "stream_predict": 12}
    else:
        want_platform = "tpu"
        try:
            found = probe_platform(env)
        except Failed as e:
            say(f"FAILED: {e}")
            raise SystemExit(1)
        if found["platform"] != "tpu":
            say(f"no TPU: jax found platform={found['platform']} "
                f"({found['kind']} x{found['count']}). chip_smoke.py serves "
                "nothing on a CPU; --cpu is the explicit rehearsal")
            raise SystemExit(3)
        say(f"probe: {found}")
        # "long" and two of the streams are longer than one 1024-token
        # prefill chunk; the cache (whole 128-token pages, never the last
        # token) covers exactly the first chunk of "long"
        sizes = {"first": 300, "long": 1100, "predict": 64,
                 "chat_predict": 48,
                 "streams": [40, 200, 600, 1500, 90, 300, 2500, 700],
                 "stream_predict": 48}

    cache_dir = compile_cache_dir()
    mesh = env.get("GRIDLLM_MESH_SHAPE", "")
    say(f"model={model} mesh={mesh or '(none)'} compile cache={cache_dir} "
        f"logs={args.log_dir}")
    stack = Stack(args.log_dir, env)
    gw = f"http://127.0.0.1:{gw_port}"
    wk = f"http://127.0.0.1:{worker_port}"
    tripped: list[dict] = []
    stop_watch = threading.Event()

    def left() -> float:
        return DEADLINE_S - (time.monotonic() - t_start)

    def engine_events() -> list[dict]:
        dump = http_json(f"{wk}/admin/dump", timeout=10)
        ring = dump["flightRecorder"]["rings"].get("engine", [])
        return [e for e in ring
                if e["event"] in ("step_failure", "runner_dead")]

    def watch() -> None:
        # a raised step (a Mosaic compile error in a first request is one)
        # is retried by the scheduler for minutes: stop at the first
        while not stop_watch.wait(2.0):
            try:
                bad = engine_events()
            except Exception:  # noqa: BLE001 — worker busy or gone
                continue
            if bad:
                tripped.extend(bad)
                return

    def wait_for(what: str, ok, timeout: float, child: str) -> None:
        end = time.monotonic() + min(timeout, left())
        while time.monotonic() < end:
            stack.check_alive()
            try:
                if ok():
                    return
            except (OSError, urllib.error.URLError, ValueError, KeyError):
                pass
            time.sleep(0.5)
        raise Failed(f"{what} not reached within {timeout:.0f}s", child)

    def phase(name: str, fn, timeout: float):
        """Run one request phase in a thread so that a dead child, an
        engine failure or the deadline ends it instead of a hung read."""
        say(f"phase {name} ...")
        t0 = time.monotonic()
        with cf.ThreadPoolExecutor(1) as pool:
            fut = pool.submit(fn)
            end = t0 + min(timeout, left())
            while True:
                try:
                    out = fut.result(timeout=0.5)
                    break
                except cf.TimeoutError:
                    pass
                if tripped:
                    raise Failed(f"engine failed during {name}: "
                                 f"{json.dumps(tripped[0])}")
                stack.check_alive()
                if time.monotonic() > end:
                    raise Failed(f"phase {name} timed out after "
                                 f"{timeout:.0f}s")
        say(f"phase {name} ok in {time.monotonic() - t0:.1f}s")
        return out

    try:
        stack.spawn("broker", "-m", "gridllm_tpu.bus.broker",
                    "--host", "127.0.0.1", "--port", str(broker_port))
        time.sleep(0.5)
        stack.spawn("gateway", "-m", "gridllm_tpu.gateway.main")
        t_worker = time.monotonic()
        stack.spawn("worker", "-m", "gridllm_tpu.worker.main")
        wait_for("gateway health", lambda: http_json(f"{gw}/health"),
                 90, "gateway")

        def registered() -> bool:
            ws = http_json(f"{gw}/health/workers")["workers"]
            return any(model in w["models"] and w["topology"] for w in ws)

        # weights, the KV pool and every start-up program's compile
        wait_for("worker registered with its model", registered,
                 240 if cpu else 780, "worker")
        t_ready = time.monotonic() - t_worker
        worker = next(w for w in http_json(f"{gw}/health/workers")["workers"]
                      if model in w["models"])
        topo = worker["topology"]
        device = {"platform": topo["platform"], "kind": topo["deviceKind"],
                  "count": topo["numDevices"]}
        say(f"worker reports platform={device['platform']} "
            f"device_kind={device['kind']!r} devices={device['count']} "
            f"jax={metadata.version('jax')} "
            f"jaxlib={metadata.version('jaxlib')}")
        if device["platform"] != want_platform:
            raise Failed(f"worker runs on {device['platform']}, "
                         f"not {want_platform}")
        pool_line = stack.grep("worker", "kv pool sized")[-1]
        for line in ([pool_line] + stack.grep("worker", "prewarm step")
                     + stack.grep("worker", "engine ready")):
            say("worker: " + line[line.index("["):][:400])
        threading.Thread(target=watch, daemon=True).start()

        # -- 1. two non-streaming generates ----------------------------
        def generate(tag: str) -> dict:
            r = http_json(f"{gw}/ollama/api/generate", {
                "model": model, "prompt": prompt_of(sizes[tag], tag),
                "raw": True, "stream": False,
                "options": {"num_predict": sizes["predict"],
                            "temperature": 0, "seed": 7},
            }, timeout=600)
            if not r.get("done") or r.get("eval_count") != sizes["predict"]:
                raise Failed(f"generate {tag}: {json.dumps(r)[:600]}")
            return r

        cold = {"first": phase("generate", lambda: generate("first"), 420)}
        t_first_answer = time.monotonic() - t_worker
        cold["long"] = phase("generate-long", lambda: generate("long"), 420)
        for tag, r in cold.items():
            say(f"generate {tag}: eval_count={r['eval_count']} "
                f"prompt_eval_count={r['prompt_eval_count']} "
                f"response={r['response'][:48]!r}")

        # -- 2. one streaming chat completion ---------------------------
        def chat_stream() -> int:
            lines = http_stream(f"{gw}/v1/chat/completions", {
                "model": model, "stream": True, "temperature": 0, "seed": 7,
                "max_tokens": sizes["chat_predict"],
                "messages": [{"role": "user",
                              "content": prompt_of(80, "chat")}],
            }, timeout=600)
            data = [(t, ln[5:].strip()) for t, ln in lines
                    if ln.startswith("data:")]
            if not data or data[-1][1] != "[DONE]":
                raise Failed(f"chat stream did not end in [DONE]: "
                             f"{data[-2:]}")
            frames = [(t, json.loads(d)) for t, d in data[:-1]]
            text = [(t, f) for t, f in frames
                    if f["choices"] and f["choices"][0]["delta"].get("content")]
            if len(text) < 2 or not text[0][0] < data[-1][0]:
                raise Failed(f"chat stream delivered {len(text)} content "
                             "frame(s); frames must arrive before the end")
            if not any(f["choices"] and f["choices"][0].get("finish_reason")
                       for _, f in frames):
                raise Failed("chat stream carried no finish_reason")
            return len(text)

        n = phase("chat-stream", chat_stream, 300)
        say(f"chat stream: {n} content frames before [DONE]")

        # -- 3. eight concurrent streams, mixed prompt lengths ----------
        def one_stream(i: int, n_bytes: int) -> tuple[int, int]:
            lines = http_stream(f"{gw}/ollama/api/generate", {
                "model": model, "prompt": prompt_of(n_bytes, f"s{i}"),
                "raw": True, "stream": True,
                "options": {"num_predict": sizes["stream_predict"],
                            "temperature": 0, "seed": 100 + i},
            }, timeout=600)
            frames = [(t, json.loads(ln)) for t, ln in lines]
            last = frames[-1][1]
            if last.get("error") or not last.get("done") or (
                    last.get("eval_count") != sizes["stream_predict"]):
                raise Failed(f"stream {i} ({n_bytes} B): "
                             f"{json.dumps(last)[:600]}")
            early = [t for t, f in frames[:-1] if f.get("response")]
            if not early or not early[0] < frames[-1][0]:
                raise Failed(f"stream {i} ({n_bytes} B) delivered no text "
                             "frame before its end")
            return n_bytes, len(early)

        def eight() -> list[tuple[int, int]]:
            with cf.ThreadPoolExecutor(len(sizes["streams"])) as pool:
                futs = [pool.submit(one_stream, i, nb)
                        for i, nb in enumerate(sizes["streams"])]
                return [f.result() for f in futs]

        got = phase("eight-streams", eight, 420)
        say("eight streams (prompt bytes, text frames before the end): "
            + " ".join(f"{b}:{f}" for b, f in got))

        # -- 4. both generates again, twice: prefix-cache hits ----------
        def answer(r: dict) -> tuple:
            return r["response"], r["context"]

        def parted(x: dict, y: dict) -> int:
            n = x["prompt_eval_count"]
            xs, ys = x["context"][n:], y["context"][n:]
            return next((i for i, (p, q) in enumerate(zip(xs, ys)) if p != q),
                        min(len(xs), len(ys)))

        for tag in ("first", "long"):
            hit1 = phase(f"repeat-{tag}", lambda: generate(tag), 300)
            hit2 = phase(f"repeat-{tag}-again", lambda: generate(tag), 300)
            if answer(hit1) != answer(hit2):
                raise Failed(
                    f"{tag}: two prefix-cache hits of one request answered "
                    f"differently from generated token {parted(hit1, hit2)}: "
                    f"{hit1['response'][:80]!r} then {hit2['response'][:80]!r}")
            same = answer(cold[tag]) == answer(hit1)
            # the long prompt's hit re-runs the cold admission's last chunk
            # (module docstring); tiny-llama's context holds no second chunk
            if tag == "long" and not cpu and not same:
                raise Failed(
                    "long: the prefix-cache hit answered differently from "
                    "the cold request from generated token "
                    f"{parted(cold[tag], hit1)}: "
                    f"{cold[tag]['response'][:80]!r} then "
                    f"{hit1['response'][:80]!r}")
            say(f"repeat {tag}: hits byte-identical "
                f"({len(hit1['context'])} context ids); cold answer "
                + ("byte-identical too" if same else
                   f"parts from them at generated token "
                   f"{parted(cold[tag], hit1)} of {sizes['predict']}"))
        stop_watch.set()

        # -- what the processes report about the run --------------------
        metrics = http_text(f"{wk}/metrics")
        dispatch = metric_values(metrics, "gridllm_kernel_dispatch_total")
        by_op: dict[str, dict[str, float]] = {}
        for labels, v in dispatch.items():
            d = dict(labels)
            by_op.setdefault(d["op"], {})[d["path"]] = v
        say("kernel dispatch (programs built, by op and path): "
            + json.dumps(by_op, sort_keys=True))
        for op in ("attention_ragged", "write_decode", "write_multi",
                   "write_prefill"):
            if by_op.get(op, {}).get("pallas", 0) < 1:
                raise Failed(f"no program was built on the {op} kernel")
        on_jnp = {op: p["jnp"] for op, p in by_op.items() if p.get("jnp")}
        if on_jnp:
            raise Failed(f"programs built on the jnp reference path: {on_jnp}")
        hits = sum(metric_values(
            metrics, "gridllm_prefix_cache_hits_total").values())
        page = json.loads(pool_line[pool_line.index("{"):])["pageSize"]
        want_hits = 2 * sum((sizes[t] - 1) // page for t in ("first", "long"))
        if hits < want_hits:
            raise Failed(f"{hits:.0f} prompt pages came from the prefix "
                         f"cache; the four repeats alone make {want_hits}")
        spec = sum(metric_values(
            metrics, "gridllm_spec_proposed_tokens_total").values())
        say(f"prefix-cache page hits={hits:.0f} "
            f"speculative drafts proposed={spec:.0f}")

        bad = engine_events()
        if bad:
            raise Failed(f"engine ring: {json.dumps(bad[0])}")
        # GRIDLLM_SANITIZE=1 in the caller's environment arms the numerics
        # sanitizer in the worker: every kernel launch inside the real
        # programs is shadowed by its jnp oracle at the registry tolerance
        shadow = http_json(f"{wk}/admin/dump", timeout=10)[
            "flightRecorder"]["rings"].get("numcheck", [])
        if env.get("GRIDLLM_SANITIZE"):
            say(f"numerics sanitizer armed: {len(shadow)} violation(s)")
        if shadow:
            raise Failed(f"numerics sanitizer: {json.dumps(shadow[0])}")
        gdump = http_json(f"{gw}/admin/dump", timeout=20)
        hangs = [e for e in gdump["flightRecorder"]["rings"].get(
            "scheduler", []) if e["event"] == "hang"]
        say(f"hang watchdog events: {json.dumps(hangs) if hangs else 'none'}")
        if any(h.get("phase") in ("prefill", "decode-step") for h in hangs):
            raise Failed("the hang watchdog requeued a request", "gateway")

        mem = http_json(f"{wk}/admin/memory", timeout=30)
        devs = {k: v for k, v in mem["devices"].items()
                if v["totalLiveBytes"]}
        for label, d in sorted(devs.items()):
            say(f"memory {label}: weights={d['weightsBytes']} "
                f"kv_pool={d['kvPoolBytes']} workspace={d['workspaceBytes']} "
                f"in_use={d.get('bytesInUse')} limit={d.get('bytesLimit')} "
                f"peak={d.get('peakBytesInUse')}")
            if not cpu and not (d.get("bytesLimit") or 0) > 8 << 30:
                raise Failed(f"{label}: bytesLimit={d.get('bytesLimit')} is "
                             "not a device-memory figure")
        if mesh:
            total = sum(d["weightsBytes"] + d["kvPoolBytes"]
                        for d in devs.values())
            if len(devs) != device["count"] or len(devs) < 2:
                raise Failed(f"mesh {mesh}: {len(devs)} of "
                             f"{device['count']} devices hold arrays")
            for label, d in devs.items():
                held = d["weightsBytes"] + d["kvPoolBytes"]
                if not (d["weightsBytes"] and d["kvPoolBytes"]
                        and held <= total / 2):
                    raise Failed(f"mesh {mesh}: {label} holds {held} of "
                                 f"{total} weight+KV bytes")
            say(f"mesh {mesh}: weights and KV on all {len(devs)} devices, "
                "none holds more than half")

        entries = sum(len(files) for _, _, files in os.walk(cache_dir))
        say(f"compile cache {cache_dir}: {entries} entries")
        if entries < 1:
            raise Failed("the persistent compile cache is empty")
        say(f"set-up times: worker start to registered {t_ready:.1f}s, "
            f"to first answer {t_first_answer:.1f}s")
        result = {"ok": True, "device": device}
        if cpu:
            result["rehearsal"] = True
    except Failed as e:
        say(f"FAILED: {e}")
        if tripped:
            say(f"first engine failure: {json.dumps(tripped[0])}")
        if e.child in stack.procs:
            say(f"--- tail of {e.child}.log ---")
            print(stack.tail(e.child), flush=True)
        raise SystemExit(1)
    finally:
        stop_watch.set()
        stack.stop()

    # -- the reference check: the chip is free again, one child takes it --
    say("phase reference (deploy/tpu_kernel_bisect.py paths) ...")
    argv = [sys.executable, os.path.join(REPO, "deploy",
                                         "tpu_kernel_bisect.py")]
    try:
        ref = subprocess.run(
            argv + (["--model", model] if cpu else []) + ["paths"], env=env,
            cwd=REPO, capture_output=True, text=True,
            timeout=max(min(420.0, left()), 1.0))
    except subprocess.TimeoutExpired:
        say("FAILED: the reference check did not end in time")
        raise SystemExit(1)
    for line in ref.stdout.splitlines():
        say("reference: " + line)
    if ref.returncode != 0:
        say(f"FAILED: the reference check exited {ref.returncode}")
        print(ref.stderr[-3000:], flush=True)
        raise SystemExit(1)
    say(f"whole run {time.monotonic() - t_start:.1f}s")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU: tiny-llama, JAX_PLATFORMS=cpu, "
                         "GRIDLLM_PALLAS=interpret")
    ap.add_argument("--log-dir", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke", time.strftime("%Y%m%d-%H%M%S")),
        help="where the children's logs go (default: chiprun_out/, which "
             "git ignores and the chip tool copies back)")
    args = ap.parse_args()
    # children die with this process on every path: SIGTERM and SIGINT
    # unwind through run()'s finally like any exception
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    result = run(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
