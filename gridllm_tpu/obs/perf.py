"""Performance introspection layer (ISSUE 4).

The obs stack through ISSUE 2 says *whether* requests meet their SLOs;
this module instruments the three dominant TPU-side reasons they don't:

1. **Recompile tripwire** (:class:`RecompileTripwire` / :class:`JitProbe`)
   — wraps the engine's jitted entry points and fingerprints every call's
   abstract signature (array shapes/dtypes — the shape-bucket and
   donated-arg-layout proxy jit keys on — plus static args). A signature
   never seen before means XLA compiled a new program. Compiles while the
   probe is *unarmed* are expected warmup (bucket compiles, first block);
   once armed (the engine arms itself after its first completed request),
   every new signature is a **steady-state recompile**: counted in
   ``gridllm_recompiles_total{fn,reason}``, logged to the flight recorder
   with the offending shapes, and — past a per-window budget — escalated
   to a watchdog-style *recompile storm* diagnosis.
2. **Device-memory accounting** (:func:`memory_snapshot`) — splits each
   device's live HBM into weights / KV pool / workspace from
   ``jax.live_arrays()`` classified against engine-registered memory
   probes, plus allocator-derived KV math (cold vs cached pages,
   lane-padding overhead, reserved-capacity fragmentation). Served at
   ``GET /admin/memory`` and exported as
   ``gridllm_device_memory_bytes{device,kind}`` gauges via a registry
   collector, with headroom/limit gauges where the backend reports
   allocator stats (TPU; CPU reports live bytes only).
3. **On-demand profiler capture** (:class:`ProfilerCapture`) —
   ``POST /admin/profile?seconds=N`` starts a ``jax.profiler`` trace
   (Python tracer off unless ``&python=1``: safe under load) into
   a bounded artifact directory (``GRIDLLM_PROFILE_DIR``, oldest captures
   pruned past ``GRIDLLM_PROFILE_KEEP``) and returns the path; the hang
   watchdog auto-triggers a short capture on decode-step hangs so the
   trace covers the wedge, not its aftermath.

4. **Phase clock** (:class:`PhaseClock`) — the engine runner's wall time
   partitioned into named phases (``gridllm_engine_phase_seconds``), the
   thread's own CPU time beside it
   (``gridllm_engine_phase_cpu_seconds_total``: wall less CPU is what a
   phase spent blocked), and the same boundaries as
   ``jax.profiler.TraceAnnotation`` spans while a capture runs, so a
   trace shows what the host did in every device gap. A phase divides
   into **stages** (``PhaseClock.stage``:
   ``gridllm_engine_stage_seconds``, flat ``gridllm.<phase>.<stage>``
   spans), and the clock keeps the time the runner was busy with nothing
   in flight (``gridllm_engine_unfed_seconds_total``).
   Driven by the engine's runner loop — see engine/engine.py.
5. **Stall witnesses** — the cyclic collector's pauses
   (:func:`install_gc_witness`: ``gridllm_process_gc_pause_seconds``, a
   ``gridllm.gc`` span while a capture runs) and an event loop's lag
   (:class:`LoopLagTimer`: ``gridllm_worker_loop_lag_seconds``): what a
   long gap under a busy phase may turn out to be.

jax is imported lazily (function-level): importing this module — and
therefore ``gridllm_tpu.obs`` — must stay cheap for control-plane-only
processes. Pure stdlib otherwise.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import gc
import math
import os
import shutil
import threading
import time
from collections import deque
from typing import Any, Callable

from gridllm_tpu.obs.flightrec import default_flight_recorder
from gridllm_tpu.obs.metrics import default_registry
from gridllm_tpu.utils.config import ENV_VARS, env_float, env_int, env_raw
from gridllm_tpu.utils.logging import get_logger

log = get_logger("obs.perf")

_OBS = default_registry()

# -- recompile tripwire instruments -----------------------------------------

RECOMPILES_TOTAL = _OBS.counter(
    "gridllm_recompiles_total",
    "XLA compiles observed by the jit tripwire, by wrapped fn and reason "
    "(warmup = before the engine's first completed request; new_shape / "
    "new_static / new_signature = steady-state recompiles — each one is "
    "also a flight-recorder event carrying the offending shapes).",
    ("fn", "reason"),
)
RECOMPILE_STORMS_TOTAL = _OBS.counter(
    "gridllm_recompile_storms_total",
    "Recompile-storm diagnoses: steady-state recompiles exceeded the "
    "per-window budget (GRIDLLM_RECOMPILE_BUDGET per "
    "GRIDLLM_RECOMPILE_WINDOW seconds).",
)

# -- step-time decomposition (engine runner drives these) -------------------
# Sub-ms-focused buckets: decode steps on a healthy TPU are 1-50 ms; the
# long tail is exactly what these histograms exist to catch.
STEP_PHASE_BUCKETS = (
    0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0,
)
# Every instant of the runner thread is in exactly one of these (no
# "other"): the clock runs mark to mark. What each covers is in the
# histogram's help text below and in PERF.md section 3.
PHASES = ("idle_wait", "ctl", "admit", "dispatch_prefill", "draft",
          "dispatch_verify", "fetch", "ingest")
PHASE_SECONDS = _OBS.histogram(
    "gridllm_engine_phase_seconds",
    "The engine runner thread's wall time, partitioned: one observation "
    "per contiguous stretch in a phase (several stretches of one phase "
    "inside one runner iteration are observed at their mean). idle_wait = "
    "waiting for work; ctl = cancel/suspend drain and the loop's own "
    "bookkeeping; admit = a popped request up to its prefill dispatch "
    "(tokenize, prefix lookup, page allocation; _count = admissions); "
    "dispatch_prefill = the prefill / mixed-chunk jitted calls returning; "
    "draft = speculative drafting; dispatch_verify = the verify / decode "
    "block jitted call returning (_count = launches); fetch = blocked on "
    "the device for a block's tokens; ingest = stop checks, detokenize, "
    "stream callbacks. Sum over phases = the runner's wall time; host "
    "phases growing against fetch is a host stall, not a device problem. "
    "A phase's wall sum less gridllm_engine_phase_cpu_seconds_total is its "
    "blocked time: the runner off the CPU, waiting.",
    ("model", "phase"), buckets=STEP_PHASE_BUCKETS,
)
PHASE_CPU_SECONDS_TOTAL = _OBS.counter(
    "gridllm_engine_phase_cpu_seconds_total",
    "The engine runner thread's own CPU time (CLOCK_THREAD_CPUTIME_ID), by "
    "the phases of gridllm_engine_phase_seconds and read at the same marks. "
    "That series' _sum less this one is the phase's blocked time: the "
    "runner off the CPU, waiting for the interpreter lock (another thread "
    "of the worker holding it), a threading lock (dispatch_lock, the "
    "allocator's), a runtime call that waits on another thread, or the "
    "scheduler. In fetch and idle_wait blocked is nearly all of the wall "
    "time by design; in ctl, admit, dispatch_prefill, draft, "
    "dispatch_verify and ingest it is time the chip waits for work that "
    "nobody is doing for it. Not served where the host's kernel keeps a "
    "thread's CPU time in ticks of a millisecond or more (gVisor: 10 ms).",
    ("model", "phase"),
)
STAGE_SECONDS = _OBS.histogram(
    "gridllm_engine_stage_seconds",
    "Stages inside a phase of gridllm_engine_phase_seconds, on the same "
    "clock and by the same flush rule (one observation a stretch, several "
    "stretches of one stage inside one runner iteration at their mean). "
    "admit: tokenize (the prompt's ids, image expansion, truncation), match "
    "(prefix lookup, page allocation, the state plan, under the "
    "allocator's lock); dispatch_prefill: seed (the sampler row, a state "
    "restore and the window-seed launches), chunk (one stretch for each "
    "prefill / chunk / mixed-chunk jitted call returning), book (plan "
    "record, counters and gauges after the last call); fetch: wait (until "
    "the oldest launch's outputs are ready), copy (device finished to "
    "tokens on the host); ingest: emit (inside a stream's on_chunk). The "
    "sum over a phase's stages is at most the phase; the difference is the "
    "phase's unstaged time.",
    ("model", "phase", "stage"), buckets=STEP_PHASE_BUCKETS,
)
UNFED_SECONDS_TOTAL = _OBS.counter(
    "gridllm_engine_unfed_seconds_total",
    "Runner-thread wall time outside idle_wait during which no launch was "
    "in flight: from the moment the oldest launch's outputs were ready "
    "with nothing queued behind it to the return of the next jitted launch "
    "call. In series that is copy + ingest + admit + draft + ctl + the "
    "launch call; where the runner keeps a launch in flight, near zero. "
    "Over the sum of gridllm_engine_phase_seconds it is the share of the "
    "runner's wall in which the host starved the chip; idle_wait over the "
    "same sum is the share in which nobody asked.",
    ("model",),
)
GC_PAUSE_SECONDS = _OBS.histogram(
    "gridllm_process_gc_pause_seconds",
    "Pauses of the cyclic garbage collector in this process, one "
    "observation a collection, by generation (gc.callbacks' start / stop "
    "pair, on whichever thread collected). A full collection walks every "
    "container alive in jax's caches: a long gap under a busy phase of the "
    "runner is one of these or it is not.",
    ("generation",), buckets=STEP_PHASE_BUCKETS,
)
# Observed from inside a collection, which can begin on a thread that
# holds this very lock (a scrape's render allocates under it): reentrant,
# or that thread waits for itself.
GC_PAUSE_SECONDS._lock = threading.RLock()
LOOP_LAG_SECONDS = _OBS.histogram(
    "gridllm_worker_loop_lag_seconds",
    "How late a timer re-armed every 50 ms fired in the worker's event "
    "loop: the time a callback due now waits behind whatever the loop is "
    "running (or behind the interpreter lock). One observation a firing.",
    buckets=STEP_PHASE_BUCKETS,
)
ADMIT_WAIT_SECONDS = _OBS.histogram(
    "gridllm_engine_admit_wait_seconds",
    "submit() to popped for admission: a request's wait in the engine's "
    "pending queue (behind admit_per_block, a full batch or an exhausted "
    "pool), by model.",
    ("model",), buckets=STEP_PHASE_BUCKETS,
)
VERIFY_CTX_TOKENS_TOTAL = _OBS.counter(
    "gridllm_engine_verify_ctx_tokens_total",
    "Sum over live slots of context length at each verify / decode block "
    "dispatch — the KV positions the ragged kernel reads. Over "
    "gridllm_engine_phase_seconds_count{phase=\"dispatch_verify\"} it is the "
    "mean live context a launch.",
    ("model",),
)

VERIFY_WINDOW_TOKENS_TOTAL = _OBS.counter(
    "gridllm_engine_verify_window_tokens_total",
    "As gridllm_engine_verify_ctx_tokens_total, with every layer's sliding "
    "window applied: sum over live slots of the mean over layers of "
    "min(context, that layer's window), the KV positions a launch's ragged "
    "kernel has to read. Equal to the context counter for a family with no "
    "window; their ratio is the share of the context that window layers "
    "leave to be read.",
    ("model",),
)
MOE_EXPERT_ROWS_TOTAL = _OBS.counter(
    "gridllm_moe_expert_rows_total",
    "Routed families: live token rows routed by the verify / decode block "
    "launches, summed over layers (rows of inactive slots are not live). "
    "Read from the launch's own fetch; nothing for a dense family.",
    ("model",),
)
MOE_FORM_ROWS_TOTAL = _OBS.counter(
    "gridllm_moe_form_rows_total",
    "Routed families: token rows a launch put through each expert layer "
    "(its padded rows: every slot's rows of a verify or decode launch, a "
    "mixed launch's chunk width and slots), by the form the expert layer "
    "took there (models/mixtral.py expert_form: all_experts, sorted, "
    "grouped, the touched experts alone by one kernel under 240 rows, or "
    "grouped_sorted, that kernel's regime from 240 rows: each expert "
    "against the rows that picked it) and "
    "the launch's kind (verify, decode, chunk).",
    ("model", "form", "launch"),
)
MOE_EXPERTS_TOUCHED_TOTAL = _OBS.counter(
    "gridllm_moe_experts_touched_total",
    "Routed families: experts with at least one live row, summed over "
    "layers and launches. Over num_experts x layers x "
    "gridllm_engine_phase_seconds_count{phase=\"dispatch_verify\"} it is "
    "the share of the held experts a launch has to read. Where a chip "
    "holds a share of each layer's experts (ModelConfig.experts_held) only "
    "HELD experts are counted, and the divisor is the held count.",
    ("model",),
)
MOE_PICKS_TOTAL = _OBS.counter(
    "gridllm_moe_picks_total",
    "A share of the experts (ModelConfig.experts_held): router picks of "
    "live rows in the verify / decode block launches, summed over layers, "
    "by where the picked expert lives: held (computed here), absent (on "
    "another chip of the expert-parallel group: it adds nothing here) or "
    "zero (a zero-compute expert, ModelConfig.zero_experts: the token "
    "itself times the weight, no product). Nothing for a family that "
    "holds every expert and has no zero-compute ones.",
    ("model", "where"),
)

# -- XLA compilations, as jax itself reports them ----------------------------
# The recompile tripwire above sees Python-level signatures only; under a
# mesh the first program compiles a second time once the state's layouts
# have settled, with no new signature. jax.monitoring's backend-compile
# event fires for every executable jax builds or loads from its persistent
# cache (eager one-primitive programs included), so "nothing compiled in
# this window" is the change of _count over it being 0.
XLA_COMPILE_SECONDS = _OBS.histogram(
    "gridllm_xla_compile_seconds",
    "Executables jax built (XLA compilation, or a load from the persistent "
    "compile cache) and the seconds each took, from jax.monitoring's "
    "backend-compile duration event, by the model whose engine the "
    "compiling thread was working for (\"\" = none: another thread). "
    "_count not rising over a stretch of serving means nothing compiled "
    "in it, under a mesh or not.",
    ("model",),
    buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0, 120.0, 300.0),
)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_owner = threading.local()
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def _on_jax_duration(event: str, seconds: float, **_: Any) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        XLA_COMPILE_SECONDS.observe(
            seconds, model=getattr(_compile_owner, "model", ""))


@contextlib.contextmanager
def compile_owner(model: str):
    """Book what this thread compiles inside the block to `model` in
    ``gridllm_xla_compile_seconds``; the first use registers the listener
    with ``jax.monitoring`` (once a process: jax offers no way to take one
    listener off again)."""
    global _compile_listener_on
    with _compile_listener_lock:
        if not _compile_listener_on:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            _compile_listener_on = True
    before = getattr(_compile_owner, "model", "")
    _compile_owner.model = model
    try:
        yield
    finally:
        _compile_owner.model = before


# -- device-memory gauges ----------------------------------------------------

DEVICE_MEMORY_BYTES = _OBS.gauge(
    "gridllm_device_memory_bytes",
    "Live device memory by kind: weights (model params), kv_pool (paged "
    "KV cache + tables), workspace (all other live arrays — activations, "
    "sampler state, staging buffers). Classified per jax.live_arrays() "
    "against engine memory probes at scrape time.",
    ("device", "kind"),
)
DEVICE_MEMORY_HEADROOM = _OBS.gauge(
    "gridllm_device_memory_headroom_bytes",
    "Allocator-reported free device memory (bytes_limit - bytes_in_use); "
    "only present on backends exposing memory_stats (TPU/GPU).",
    ("device",),
)
DEVICE_MEMORY_LIMIT = _OBS.gauge(
    "gridllm_device_memory_limit_bytes",
    "Allocator-reported device memory limit; only present on backends "
    "exposing memory_stats (TPU/GPU).",
    ("device",),
)


# Deliberately laxer than utils/config._env: these are read lazily on
# telemetry paths (per steady-state recompile, per capture), where a
# malformed env var must degrade to the default, never raise — config
# load's fail-fast SystemExit semantics would turn a typo'd budget into
# an outage of the thing doing the diagnosing.
def jax_loaded() -> bool:
    """Whether this process already imported jax. Every perf path that
    would otherwise import jax checks this first: in an engine-less
    control-plane process (split-deployment gateway) a surprise backend
    init is seconds of stall at best and, on a TPU host whose worker
    holds the exclusive libtpu claim, a hang — scrapes, snapshots, and
    captures must refuse or no-op instead."""
    import sys

    return "jax" in sys.modules


# ---------------------------------------------------------------------------
# recompile tripwire
# ---------------------------------------------------------------------------


def _leaf_signature(leaves: list[Any]) -> tuple[tuple[Any, ...], tuple[str, ...]]:
    """(array avals, static reprs) for one call's flattened args. Arrays
    contribute (shape, dtype) — the jit cache key's shape-bucket /
    donated-layout proxy; everything else (python ints, bools, static
    kwargs) contributes its repr."""
    avals: list[Any] = []
    statics: list[str] = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            avals.append((tuple(shape), str(dtype)))
        else:
            statics.append(repr(leaf))
    return tuple(avals), tuple(statics)


class JitProbe:
    """One wrapped jitted callable. Transparent pass-through plus
    signature bookkeeping; the owning :class:`RecompileTripwire` gets told
    about every first-seen signature."""

    def __init__(self, name: str, fn: Callable, tripwire: "RecompileTripwire",
                 armable: bool = True):
        self.name = name
        self._fn = fn
        self._tripwire = tripwire
        # armable=False: probes whose whole compile surface is explicitly
        # bucket-bounded and demand-driven (embed batch/len buckets,
        # vision image counts) — their first-use compiles can land long
        # after the generation path warms, so flagging them would page on
        # healthy behavior. They still count under reason="warmup".
        self.armable = armable
        self.armed = False
        # signature bookkeeping is guarded: the embed probe is called
        # from concurrent asyncio.to_thread workers while the runner
        # thread drives decode — an unguarded check-then-add would
        # double-count the same first-seen signature
        self._sig_lock = threading.Lock()
        # full signature → first-seen; plus the two projections used to
        # classify WHAT changed when a new signature appears
        self._seen: set[tuple] = set()
        self._seen_avals: set[tuple] = set()
        self._seen_statics: set[tuple] = set()
        # identity-memo for the first positional arg: every engine entry
        # point passes the (large, shape-stable) params tree first, and
        # re-flattening its hundreds of leaves per decode-block dispatch
        # would tax the hot path (the dispatch_verify phase). One
        # (obj, sig) tuple so cross-thread reads are never torn; the
        # strong ref makes the `is` check immune to id reuse.
        self._memo: tuple[Any, tuple] | None = None
        self.compiles = 0
        self.steady_recompiles = 0

    def arm(self) -> None:
        """Enter steady state: every new signature from here on is a
        flagged recompile, not expected warmup."""
        self.armed = True

    def __getattr__(self, name):
        # transparent wrapper: jit-object introspection (_cache_size,
        # lower, ...) must keep working through the probe
        fn = self.__dict__.get("_fn")
        if fn is None:  # mid-__init__ / copy protocols
            raise AttributeError(name)
        return getattr(fn, name)

    def _signature(self, args, kwargs) -> tuple[tuple, tuple]:
        """(avals, statics) for this call. Always computed as arg0's
        leaves followed by the rest's, so memo hits and misses produce
        identical keys for identical calls."""
        import jax

        flatten = jax.tree_util.tree_flatten
        if not args:
            return _leaf_signature(flatten(kwargs)[0])
        memo = self._memo
        if memo is not None and memo[0] is args[0]:
            avals0, statics0 = memo[1]
        else:
            avals0, statics0 = _leaf_signature(flatten(args[0])[0])
            self._memo = (args[0], (avals0, statics0))
        avals_r, statics_r = _leaf_signature(flatten((args[1:], kwargs))[0])
        return avals0 + avals_r, statics0 + statics_r

    def __call__(self, *args, **kwargs):
        avals, statics = self._signature(args, kwargs)
        key = (avals, statics)
        with self._sig_lock:
            new = key not in self._seen
            if new:
                reason = self._note_compile(avals, statics, key)
        if new and reason != "warmup":
            self._tripwire._on_steady_recompile(self, reason, avals, statics)
        return self._fn(*args, **kwargs)

    def _note_compile(self, avals, statics, key) -> str:
        """Record a first-seen signature (caller holds _sig_lock).

        A probe's very FIRST signature is always ``warmup`` even when
        armed: a program must compile once to exist, and some entry
        points legitimately run for the first time only after the engine
        warms (state_restore needs a prefix-cache hit, which requires a
        COMPLETED request — the very event that arms the tripwire;
        chunked prefill needs the first long prompt). Only a SECOND
        signature on an armed probe is evidence of shape leakage."""
        self.compiles += 1
        if not self.armed or not self._seen:
            reason = "warmup"
        elif statics in self._seen_statics and avals not in self._seen_avals:
            reason = "new_shape"
        elif avals in self._seen_avals and statics not in self._seen_statics:
            reason = "new_static"
        else:
            reason = "new_signature"
        self._seen.add(key)
        self._seen_avals.add(avals)
        self._seen_statics.add(statics)
        RECOMPILES_TOTAL.inc(fn=self.name, reason=reason)
        if reason != "warmup":
            self.steady_recompiles += 1
        return reason


class RecompileTripwire:
    """Per-engine probe set + process-wide storm detection. Engines build
    one (``InferenceEngine._build_fns``), wrap each jitted entry point,
    and arm it after their first completed request; storms are judged
    across ALL tripwires in the process (a per-engine budget would let N
    co-hosted engines each storm just under it)."""

    # shared across instances: storms are a process-level pathology
    _storm_lock = threading.Lock()
    _storm_events: deque[float] = deque(maxlen=256)
    _last_storm_ts = 0.0

    def __init__(self, context: str = ""):
        self.context = context  # e.g. the model name, for events/logs
        self._probes: dict[str, JitProbe] = {}

    def wrap(self, name: str, fn: Callable, armable: bool = True) -> JitProbe:
        probe = JitProbe(name, fn, self, armable=armable)
        self._probes[name] = probe
        return probe

    def arm(self) -> None:
        for probe in self._probes.values():
            if probe.armable:
                probe.arm()

    @property
    def armed(self) -> bool:
        return any(p.armed for p in self._probes.values())

    def state(self) -> dict[str, Any]:
        return {
            name: {"compiles": p.compiles,
                   "steadyRecompiles": p.steady_recompiles,
                   "armed": p.armed,
                   "signatures": len(p._seen)}
            for name, p in self._probes.items()
        }

    def _on_steady_recompile(self, probe: JitProbe, reason: str,
                             avals, statics) -> None:
        # compact shape string: enough to identify the offending program
        # without dumping a 300-leaf params tree into the ring
        shapes = ",".join(f"{s}/{d}" for s, d in avals[:12])
        if len(avals) > 12:
            shapes += f",…+{len(avals) - 12}"
        default_flight_recorder().record(
            "engine", "recompile", fn=probe.name, reason=reason,
            context=self.context, nArrays=len(avals), shapes=shapes,
            statics=";".join(statics[:8]),
        )
        log.warning("steady-state recompile", fn=probe.name, reason=reason,
                    context=self.context, shapes=shapes)
        try:
            budget = env_int("GRIDLLM_RECOMPILE_BUDGET")
            window = env_float("GRIDLLM_RECOMPILE_WINDOW")
        except ValueError:
            # this runs on the engine step path mid-incident: a malformed
            # telemetry knob must degrade to the registry default, not crash
            budget = int(ENV_VARS["GRIDLLM_RECOMPILE_BUDGET"].default)
            window = float(ENV_VARS["GRIDLLM_RECOMPILE_WINDOW"].default)
        now = time.monotonic()
        with RecompileTripwire._storm_lock:
            ev = RecompileTripwire._storm_events
            ev.append(now)
            while ev and now - ev[0] > window:
                ev.popleft()
            storm = (len(ev) > budget
                     and now - RecompileTripwire._last_storm_ts > window / 2)
            if storm:
                RecompileTripwire._last_storm_ts = now
        if storm:
            RECOMPILE_STORMS_TOTAL.inc()
            diagnosis = {"windowS": window, "budget": budget,
                         "recompilesInWindow": len(ev),
                         "lastFn": probe.name, "lastReason": reason,
                         "lastShapes": shapes}
            default_flight_recorder().record(
                "engine", "recompile_storm", **diagnosis)
            log.error("recompile storm: steady-state recompiles exceed "
                      "budget — shape bucketing is broken or inputs are "
                      "unbucketed", **diagnosis)


def recompile_totals() -> dict[str, Any]:
    """Process-wide compile counts from the tripwire counter, split into
    warmup vs steady-state (bench --emit reads this; the CI perf-smoke
    gate asserts steady == 0)."""
    out = {"total": 0, "warmup": 0, "steady": 0, "byFn": {}}
    for labels, count in RECOMPILES_TOTAL.items():
        fn, reason = labels["fn"], labels["reason"]
        count = int(count)
        out["total"] += count
        if reason == "warmup":
            out["warmup"] += count
        else:
            out["steady"] += count
        per = out["byFn"].setdefault(fn, {"warmup": 0, "steady": 0})
        per["warmup" if reason == "warmup" else "steady"] += count
    return out


# ---------------------------------------------------------------------------
# device-memory accounting
# ---------------------------------------------------------------------------
# Engines register a *memory probe* (worker/service.py, one per service)
# returning, per model, the live weight/KV arrays plus allocator math —
# mirroring the flight recorder's engine probes so the snapshot path never
# imports or locks engine internals.

_memory_probes: dict[str, Callable[[], dict[str, Any]]] = {}
_memory_probes_lock = threading.Lock()


def register_memory_probe(name: str, fn: Callable[[], dict[str, Any]]) -> None:
    with _memory_probes_lock:
        _memory_probes[name] = fn


def unregister_memory_probe(name: str) -> None:
    with _memory_probes_lock:
        _memory_probes.pop(name, None)


def _device_label(device: Any) -> str:
    return f"{device.platform}:{device.id}"


def memory_snapshot() -> dict[str, Any]:
    """Point-in-time device-memory breakdown (``GET /admin/memory``).

    Walks ``jax.live_arrays()`` once, attributing each array's per-shard
    bytes to its device as weights / kv_pool / workspace by identity
    against the registered memory probes; workspace is everything not
    claimed, so the three kinds sum to the measured live total exactly.
    Adds allocator-reported in-use/limit/headroom where the backend
    exposes memory_stats (TPU/GPU; CPU has none) and per-model KV math
    from the page allocator (cold vs cached pages, lane-padding overhead,
    reserved-capacity fragmentation).

    In a process that never imported jax this returns an empty snapshot
    with a note instead of initializing a backend (see jax_loaded)."""
    if not jax_loaded():
        return {"generatedAt": time.time(), "devices": {}, "models": {},
                "note": "jax not initialized in this process — query the "
                        "worker health port for the engine-side view"}
    import jax

    with _memory_probes_lock:
        probes = dict(_memory_probes)
    models: dict[str, Any] = {}
    weight_ids: set[int] = set()
    kv_ids: set[int] = set()
    # shape+dtype fallback for KV attribution: the decode block DONATES
    # and rebinds engine.cache, so under load the live pool arrays can be
    # successors of the ones the probe captured (same shapes, new ids) —
    # id-only matching would misread the whole pool as workspace exactly
    # when the server is busy. Weights are never donated; ids suffice.
    kv_shapes: set[tuple] = set()
    for probe_name, fn in probes.items():
        try:
            for model, info in fn().items():
                weights = info.get("weights") or []
                kv = info.get("kv") or []
                weight_ids.update(id(a) for a in weights)
                kv_ids.update(id(a) for a in kv)
                # only the rank≥4 pool arrays (k/v: [L,P,ps,KVH,D]) —
                # they carry ~all the bytes and their shape is
                # unambiguous; low-rank tables/lengths share shapes with
                # sampler state and stay id-matched
                kv_shapes.update(
                    (tuple(a.shape), str(a.dtype)) for a in kv
                    if hasattr(a, "shape") and len(a.shape) >= 4)
                entry = dict(info.get("alloc") or {})
                entry["weightsBytes"] = sum(
                    getattr(a, "nbytes", 0) for a in weights)
                entry["kvPoolBytes"] = sum(
                    getattr(a, "nbytes", 0) for a in kv)
                entry["probe"] = probe_name
                models[model] = entry
        except Exception as e:  # noqa: BLE001 — snapshots must assemble
            models[f"{probe_name}:error"] = {"error": str(e)}

    devices: dict[str, dict[str, Any]] = {}

    def dev_entry(label: str) -> dict[str, Any]:
        return devices.setdefault(label, {
            "weightsBytes": 0, "kvPoolBytes": 0, "workspaceBytes": 0,
            "totalLiveBytes": 0,
        })

    for arr in jax.live_arrays():
        try:
            if id(arr) in weight_ids:
                kind = "weightsBytes"
            elif id(arr) in kv_ids or (
                    (tuple(arr.shape), str(arr.dtype)) in kv_shapes):
                kind = "kvPoolBytes"
            else:
                kind = "workspaceBytes"
            # per-device bytes from the sharding's metadata. Walking
            # arr.addressable_shards instead materialises one Array per
            # shard, which the NEXT walk finds in live_arrays() and counts
            # again — every figure doubled from the second snapshot on
            # (seen on the chip, PR 21: /metrics scrapes take one too)
            nbytes = math.prod(
                arr.sharding.shard_shape(arr.shape)) * arr.dtype.itemsize
            for device in arr.sharding.addressable_devices:
                entry = dev_entry(_device_label(device))
                entry[kind] += nbytes
                entry["totalLiveBytes"] += nbytes
        except Exception:  # noqa: BLE001 — deleted mid-walk (donation race)
            continue

    for device in jax.local_devices():
        entry = dev_entry(_device_label(device))
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — backend without allocator stats
            stats = None
        if stats:
            in_use = stats.get("bytes_in_use")
            limit = (stats.get("bytes_limit")
                     or stats.get("bytes_reservable_limit"))
            entry["bytesInUse"] = in_use
            entry["bytesLimit"] = limit
            entry["peakBytesInUse"] = stats.get("peak_bytes_in_use")
            if in_use is not None and limit:
                entry["headroomBytes"] = max(limit - in_use, 0)
                largest = stats.get("largest_free_block_bytes")
                free = limit - in_use
                if largest is not None and free > 0:
                    # external fragmentation: how much of the free HBM is
                    # NOT reachable as one contiguous block
                    entry["fragmentation"] = round(1 - largest / free, 4)
        else:
            entry["bytesInUse"] = None
            entry["bytesLimit"] = None
            entry["headroomBytes"] = None
    return {
        "generatedAt": time.time(),
        "devices": devices,
        "models": models,
    }


def _memory_collector() -> None:
    """Registry collector: refresh the device-memory gauges from a fresh
    snapshot at scrape time (point-in-time-correct, like the scheduler's
    queue-depth collectors). Skips entirely in processes that never
    imported jax — a scrape must not initialize a backend."""
    if not jax_loaded():
        return
    snap = memory_snapshot()
    for label, entry in snap["devices"].items():
        DEVICE_MEMORY_BYTES.set(entry["weightsBytes"],
                                device=label, kind="weights")
        DEVICE_MEMORY_BYTES.set(entry["kvPoolBytes"],
                                device=label, kind="kv_pool")
        DEVICE_MEMORY_BYTES.set(entry["workspaceBytes"],
                                device=label, kind="workspace")
        if entry.get("headroomBytes") is not None:
            DEVICE_MEMORY_HEADROOM.set(entry["headroomBytes"], device=label)
        if entry.get("bytesLimit"):
            DEVICE_MEMORY_LIMIT.set(entry["bytesLimit"], device=label)


# Registered once at import: scrapes of any process importing the engine
# get the gauges; processes with no live arrays pay one cheap walk.
_OBS.add_collector("perf.device_memory", _memory_collector)


# ---------------------------------------------------------------------------
# on-demand profiler capture
# ---------------------------------------------------------------------------


def _tree_bytes(path: str) -> int:
    total = 0
    for root, _dirs, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


class CaptureBusy(RuntimeError):
    """A profiler capture is already running (jax allows one trace at a
    time per process)."""


class ProfilerCapture:
    """Bounded on-demand ``jax.profiler`` captures.

    ``capture(seconds)`` starts a trace into a fresh subdirectory of the
    artifact root (``GRIDLLM_PROFILE_DIR``, default
    ``/tmp/gridllm-profiles``), spawns a daemon timer that stops it after
    ``seconds``, prunes the oldest captures past ``GRIDLLM_PROFILE_KEEP``
    (default 4), and returns the path immediately — the caller (an HTTP
    handler or the hang watchdog) never blocks for the capture window.
    Open the result with TensorBoard (``tensorboard --logdir <path>``,
    profile plugin) or Perfetto (``xprof``/trace viewer); see README
    "Profiling & performance introspection"."""

    MAX_SECONDS = 120.0

    def __init__(self, base_dir: str | None = None, keep: int | None = None):
        self._base_dir = base_dir
        self._keep = keep
        self._lock = threading.Lock()
        self._active: dict[str, Any] | None = None
        # True from start_trace to stop_trace: the one plain attribute the
        # hot paths (PhaseClock.mark, capture_span) read to decide whether
        # a TraceAnnotation is worth constructing
        self.tracing = False
        self.captures: list[dict[str, Any]] = []  # bounded history

    @property
    def base_dir(self) -> str:
        return (self._base_dir
                or env_raw("GRIDLLM_PROFILE_DIR")
                or "/tmp/gridllm-profiles")

    @property
    def keep(self) -> int:
        if self._keep is not None:
            return self._keep
        try:
            return env_int("GRIDLLM_PROFILE_KEEP")
        except ValueError:
            # read during artifact rotation (watchdog auto-capture thread
            # included) — degrade to the registry default, not an exception
            return int(ENV_VARS["GRIDLLM_PROFILE_KEEP"].default)

    @property
    def active(self) -> dict[str, Any] | None:
        with self._lock:
            return dict(self._active) if self._active else None

    def _prune(self) -> None:
        base = self.base_dir
        try:
            # only the module's own trace-* capture dirs are prunable —
            # GRIDLLM_PROFILE_DIR may point at a shared directory, and
            # deleting unrelated entries there would be catastrophic
            entries = sorted(
                e for e in os.listdir(base)
                if e.startswith("trace-")
                and os.path.isdir(os.path.join(base, e))
            )
        except OSError:
            return
        for stale in entries[:max(0, len(entries) - self.keep)]:
            shutil.rmtree(os.path.join(base, stale), ignore_errors=True)

    def capture(self, seconds: float, reason: str = "on_demand",
                python: bool = False) -> dict[str, Any]:
        """Start a capture; returns {path, seconds, reason, python,
        startedAt}. Raises :class:`CaptureBusy` when one is already
        running.

        The Python tracer is OFF unless ``python=True``: with it on, every
        Python call of every thread is an event (970 k in 5 s on a serving
        worker, ``stop_trace`` 3.4 s) and requests slowed 5-15x while it
        ran (PERF.md, PR 23 finding 6). The host tracer stays on, so the
        trace still holds the runtime's own spans and this module's
        ``gridllm.*`` annotations beside the device's lines. A wedge is
        read from its Python stack: the hang watchdog asks for it."""
        seconds = min(max(float(seconds), 0.05), self.MAX_SECONDS)
        safe_reason = "".join(
            c if c.isalnum() or c in "-_" else "-" for c in reason)[:48]
        path = os.path.join(
            self.base_dir, f"trace-{int(time.time() * 1000)}-{safe_reason}")
        with self._lock:
            if self._active is not None:
                raise CaptureBusy(
                    f"capture already running: {self._active['path']}")
            import jax

            os.makedirs(path, exist_ok=True)
            self._prune()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if python else 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(path, profiler_options=options)
            info = {"path": path, "seconds": seconds, "reason": reason,
                    "python": bool(python), "startedAt": time.time()}
            self._active = info
            self.tracing = True
        default_flight_recorder().record("engine", "profile_capture",
                                         path=path, seconds=seconds,
                                         reason=reason)
        threading.Thread(target=self._finish_after, args=(seconds,),
                         name="profiler-capture", daemon=True).start()
        return dict(info)

    def _finish_after(self, seconds: float) -> None:
        time.sleep(seconds)
        self.stop()

    def stop(self) -> dict[str, Any] | None:
        """Stop the active capture (idempotent; also the timer's path).
        The trace flush runs OUTSIDE the lock: writing a large trace can
        take seconds, and a concurrent capture() on the event loop must
        get an immediate CaptureBusy/answer, not block on the flush.
        Claiming ``_active`` under the lock first keeps stop idempotent
        and leaves exactly one thread responsible for the flush; a
        capture() arriving mid-flush correctly sees "busy" until the
        post-flush bookkeeping clears it."""
        with self._lock:
            info = self._active
            if info is None or info.get("stopping"):
                return None  # no capture, or another thread owns the flush
            info["stopping"] = True
            self.tracing = False
        t0 = time.perf_counter()
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — a failed stop must not
            info["error"] = str(e)  # wedge the endpoint forever
        with self._lock:
            self._active = None
            info.pop("stopping", None)
            info["endedAt"] = time.time()
            # what the capture cost to write out (the flush runs on the
            # timer thread, beside the serving threads)
            info["stopTraceS"] = round(time.perf_counter() - t0, 3)
            self.captures.append(dict(info))
            del self.captures[:-16]
        log.info("profiler capture written", path=info["path"],
                 reason=info["reason"], python=info["python"],
                 stopTraceS=info["stopTraceS"], bytes=_tree_bytes(info["path"]),
                 error=info.get("error"))
        return dict(info)


_PROFILER = ProfilerCapture()


def default_profiler() -> ProfilerCapture:
    """The process-global capture manager (HTTP endpoints + watchdog)."""
    return _PROFILER


def capture_span(name: str, **meta: Any):
    """A ``jax.profiler.TraceAnnotation`` while the process-global capture
    runs, else a no-op context: for code off the runner thread that a
    trace should name (the worker's ``/metrics`` render). One attribute
    read when nothing is being captured."""
    if not _PROFILER.tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name, **meta)


@functools.cache
def thread_cpu_clock() -> Callable[[], float] | None:
    """``time.thread_time`` where the kernel keeps a thread's CPU time
    finer than a millisecond, else None. Linux proper steps it by under a
    microsecond and a read costs 0.3 us. A sandboxed kernel (gVisor, which
    the benchmark's machines run under) charges a thread whole 10 ms
    ticks, so a stretch of a phase reads 0 or a tick, and a read costs
    6 us: such a clock says nothing of a sub-ms stretch and is not worth
    its read (PERF.md section 6, PR 38). Probed once a process, by at most
    2 ms of spinning: a property of the kernel, not a setting."""
    clock = time.thread_time
    t0, end = clock(), time.perf_counter() + 2e-3
    while time.perf_counter() < end:
        if 0.0 < clock() - t0 < 1e-3:
            return clock
    return None


class PhaseClock:
    """One clock for the engine's runner thread: its wall time partitioned
    into :data:`PHASES`, mark to mark.

    ``mark(phase)`` closes the phase the thread was in and opens `phase`:
    one ``perf_counter``, one ``thread_time`` (the calling thread's CPU
    time) where :func:`thread_cpu_clock` finds one worth reading, two
    float adds into a local dict. ``flush()`` (once a runner iteration,
    never per mark) moves what was closed into
    ``gridllm_engine_phase_seconds{model,phase}`` and
    ``gridllm_engine_phase_cpu_seconds_total{model,phase}``. ``pause()``
    closes the open phase without opening another (the runner stopping, or
    the end of a synchronous ``step()``): time until the next mark is
    nobody's. Every ``step()`` and the runner end in ``pause()``, so a
    stretch opens and closes on one thread.

    A phase's wall seconds less its CPU seconds is its **blocked** time:
    the thread off the CPU, waiting for the interpreter lock, a
    ``threading`` lock, the runtime or the scheduler. On a host whose
    kernel keeps no fine thread clock nothing is read, ``cpu_seconds``
    stays empty and the CPU series is not served: no reading, not a
    reading of zero.

    ``stage(name)`` divides the open phase one level down: it closes the
    stretch of the phase that was running (a stage, or the phase's own
    unstaged time) and opens ``<phase>.<name>``; ``stage(None)`` goes back
    to the phase's own time, and the next ``mark`` closes the open stage
    with its phase. One ``perf_counter`` and two float adds, no CPU clock.
    The phase's own accounting does not see stages: ``mark`` still returns
    the whole closed phase's seconds, and
    ``gridllm_engine_stage_seconds{model,phase,stage}`` sums to at most the
    phase. With no phase open (a multi-host follower replaying a dispatch
    off the runner) ``stage`` does nothing.

    The clock also keeps the **unfed** time: the engine says ``starve(t)``
    when the oldest launch's outputs were ready at `t` with nothing queued
    behind it and ``fed()`` when the next launch call has returned; what
    lies between, ``idle_wait`` and paused time left out, goes to
    ``gridllm_engine_unfed_seconds_total{model}``. A clock starts starved:
    nothing is in flight before the first launch.

    While the profiler captures, each phase is also entered as a
    ``TraceAnnotation("gridllm.<phase>", **meta)``, so the ``.xplane.pb``
    holds the runner's phases on the same clock as the device's ``XLA
    Ops`` line, and a stage as ``gridllm.<phase>.<stage>`` IN PLACE of its
    phase's span (closed, not nested under: the benchmark's reduction
    names an idle gap by the shortest span that covers it, and a parent
    left open would own every gap that straddles two stages); with no
    capture no annotation is constructed. Owned by one thread: no lock."""

    def __init__(self, model: str, profiler: ProfilerCapture | None = None):
        self.model = model
        self._profiler = profiler or _PROFILER
        self._cpu_clock = thread_cpu_clock()
        self._phase: str | None = None
        self._t = self._cpu = 0.0
        self._span: Any = None
        self._meta: dict[str, Any] = {}     # the open phase's span meta
        # the open stage (None: the phase's own time) and when it opened
        self._stage: str | None = None
        self._ts = 0.0
        # closed and not yet flushed: phase -> [seconds, stretches, cpu s]
        self._acc: dict[str, list] = {p: [0.0, 0, 0.0] for p in PHASES}
        # the same of stages: (phase, stage) -> [seconds, stretches]
        self._stage_acc: dict[tuple[str, str], list] = {}
        # cumulative, flushed: what tests and batch_state read
        self.seconds: dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self.cpu_seconds: dict[str, float] = (
            dict.fromkeys(PHASES, 0.0) if self._cpu_clock else {})
        self.counts: dict[str, int] = dict.fromkeys(PHASES, 0)
        self.stage_seconds: dict[tuple[str, str], float] = {}
        self.stage_counts: dict[tuple[str, str], int] = {}
        # nothing in flight, as the engine last said, and since when that
        # has been costing the runner's time (None: idle, paused or fed)
        self._starved = True
        self._unfed_t: float | None = None
        self._unfed_acc = 0.0
        self.unfed_seconds = 0.0
        UNFED_SECONDS_TOTAL.inc(0.0, model=model)   # served from the start

    def _span_open(self, name: str, meta: dict[str, Any]) -> None:
        import jax

        self._span = jax.profiler.TraceAnnotation(name, **meta)
        self._span.__enter__()

    def _span_close(self) -> None:
        span = self._span
        if span is not None:
            self._span = None
            span.__exit__(None, None, None)

    def _close_stage(self, now: float) -> None:
        key = (self._phase, self._stage)
        cell = self._stage_acc.get(key)
        if cell is None:
            cell = self._stage_acc[key] = [0.0, 0]
        cell[0] += now - self._ts
        cell[1] += 1
        self._stage = None

    def _close(self, now: float, cpu: float) -> float:
        self._span_close()
        if self._phase is None:
            return 0.0
        if self._stage is not None:
            self._close_stage(now)
        dt = now - self._t
        cell = self._acc[self._phase]
        cell[0] += dt
        cell[1] += 1
        cell[2] += cpu - self._cpu
        return dt

    def mark(self, phase: str, stage: str | None = None,
             **meta: Any) -> float:
        """Enter `phase` (in its stage `stage`, if given: one clock read
        for both); returns the seconds the closed phase lasted."""
        now, clock = time.perf_counter(), self._cpu_clock
        cpu = clock() if clock else 0.0
        dt = self._close(now, cpu)
        self._phase, self._t, self._cpu = phase, now, cpu
        self._stage, self._ts = stage, now
        if self._starved:
            if phase == "idle_wait":
                self._unfed_stop(now)
            elif self._unfed_t is None:     # back from idle or a pause
                self._unfed_t = now
        self._meta = meta
        if self._profiler.tracing:
            self._span_open(
                f"gridllm.{phase}.{stage}" if stage else "gridllm." + phase,
                meta)
        return dt

    def stage(self, name: str | None, of: str | None = None,
              **meta: Any) -> float:
        """Enter stage `name` of the open phase (None: the phase's own
        time again), if that phase is `of` where `of` is given; returns
        the clock's reading, 0.0 where it did nothing (no phase open)."""
        phase = self._phase
        if phase is None or (of is not None and phase != of):
            return 0.0
        now = time.perf_counter()
        if self._stage is not None:
            self._close_stage(now)
        self._stage, self._ts = name, now
        if self._span is not None or self._profiler.tracing:
            self._span_close()
            if self._profiler.tracing:
                if name is None:
                    self._span_open("gridllm." + phase, self._meta)
                else:
                    self._span_open(f"gridllm.{phase}.{name}", meta)
        return now

    def annotate(self, **meta: Any) -> None:
        """Metadata known only once the phase (or its open stage) is under
        way (the tokens an ingest emitted). Free when nothing is being
        captured."""
        if self._span is not None:
            self._span.set_metadata(**meta)

    def starve(self, now: float) -> None:
        """Nothing is in flight as of `now` (a reading of this clock: the
        end of ``fetch.wait``)."""
        self._starved, self._unfed_t = True, now

    def fed(self) -> None:
        """A launch call returned: something is in flight again. A clock
        read only where the runner was starved."""
        if self._starved:
            self._starved = False
            self._unfed_stop(time.perf_counter())

    def _unfed_stop(self, now: float) -> None:
        if self._unfed_t is not None:
            self._unfed_acc += now - self._unfed_t
            self._unfed_t = None

    def spent(self, *phases: str) -> float:
        """Seconds closed in `phases` so far, flushed or not; the open
        phase's running stretch is not in it."""
        return sum(self.seconds[p] + self._acc[p][0] for p in phases)

    def pause(self) -> None:
        clock = self._cpu_clock
        now = time.perf_counter()
        self._close(now, clock() if clock else 0.0)
        self._phase = None
        self._unfed_stop(now)
        self.flush()

    def flush(self) -> None:
        for phase, cell in self._acc.items():
            secs, n, cpu = cell
            if not n:
                continue
            cell[0], cell[1], cell[2] = 0.0, 0, 0.0
            self.seconds[phase] += secs
            self.counts[phase] += n
            PHASE_SECONDS.observe_many(secs / n, n, model=self.model,
                                       phase=phase)
            if self._cpu_clock:
                self.cpu_seconds[phase] += cpu
                PHASE_CPU_SECONDS_TOTAL.inc(cpu, model=self.model, phase=phase)
        for key, cell in self._stage_acc.items():
            secs, n = cell
            if not n:
                continue
            cell[0], cell[1] = 0.0, 0
            self.stage_seconds[key] = self.stage_seconds.get(key, 0.0) + secs
            self.stage_counts[key] = self.stage_counts.get(key, 0) + n
            STAGE_SECONDS.observe_many(secs / n, n, model=self.model,
                                       phase=key[0], stage=key[1])
        if self._unfed_acc:
            self.unfed_seconds += self._unfed_acc
            UNFED_SECONDS_TOTAL.inc(self._unfed_acc, model=self.model)
            self._unfed_acc = 0.0


# ---------------------------------------------------------------------------
# stall witnesses: the collector's pauses, an event loop's lag
# ---------------------------------------------------------------------------

_gc_witness_lock = threading.Lock()
_gc_witness_on = False
# one collection runs at a time in a process (the collector does not
# nest), so its start time and span are plain globals
_gc_t0: float | None = None
_gc_span: Any = None


def _on_gc(phase: str, info: dict[str, int]) -> None:
    """``gc.callbacks`` entry: runs on the thread that collects, before
    and after every collection of every generation. A clock read each and
    one observe; while a capture runs, a ``gridllm.gc`` span over the
    collection, inside whatever span the thread had open (so it is the
    shortest cover of a gap it spans)."""
    global _gc_t0, _gc_span
    if phase == "start":
        _gc_t0 = time.perf_counter()
        if _PROFILER.tracing:
            import jax

            _gc_span = jax.profiler.TraceAnnotation(
                "gridllm.gc", generation=info["generation"])
            _gc_span.__enter__()
        return
    t0, _gc_t0 = _gc_t0, None
    if t0 is None:      # registered between a collection's two callbacks
        return
    GC_PAUSE_SECONDS.observe(time.perf_counter() - t0,
                             generation=str(info["generation"]))
    span, _gc_span = _gc_span, None
    if span is not None:
        span.set_metadata(collected=info["collected"])
        span.__exit__(None, None, None)


def install_gc_witness() -> None:
    """Time every collection of this process from here on
    (``gridllm_process_gc_pause_seconds{generation}``). Idempotent: a
    worker process calls it once at start, and no thread is added."""
    global _gc_witness_on
    with _gc_witness_lock:
        if not _gc_witness_on:
            gc.callbacks.append(_on_gc)
            _gc_witness_on = True


class LoopLagTimer:
    """A timer re-armed every ``INTERVAL`` seconds in the running event
    loop, observing how late each firing came
    (``gridllm_worker_loop_lag_seconds``): no thread, one ``call_at``
    and one observe a firing. ``start()`` inside the loop, ``stop()``
    cancels the pending firing."""

    INTERVAL = 0.05

    def __init__(self) -> None:
        self._loop: asyncio.AbstractEventLoop | None = None
        self._handle: asyncio.TimerHandle | None = None
        self._due = 0.0

    def start(self) -> "LoopLagTimer":
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
            self._arm()
        return self

    def _arm(self) -> None:
        self._due = self._loop.time() + self.INTERVAL
        self._handle = self._loop.call_at(self._due, self._fire)

    def _fire(self) -> None:
        LOOP_LAG_SECONDS.observe(max(self._loop.time() - self._due, 0.0))
        self._arm()

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
        self._loop = self._handle = None


def handle_profile_request(seconds_raw: str | None,
                           python_raw: str | None = None,
                           ) -> tuple[int, dict[str, Any]]:
    """Transport-agnostic body of ``POST /admin/profile?seconds=N[&python=1]``:
    (http_status, json_payload). ``python=1`` turns the Python tracer on
    (slows the process severalfold while it runs: see
    :meth:`ProfilerCapture.capture`). Shared by the gateway admin surface and
    the worker health port so neither re-implements validation, the
    busy conflict, or the no-jax guard (which refuses rather than
    synchronously initializing a backend in a control-plane process).
    Does blocking work (dir pruning, start_trace) — async HTTP handlers
    must call it via ``asyncio.to_thread``."""
    if not jax_loaded():
        return 501, {"error": "no jax runtime in this process — POST the "
                              "worker health port's /admin/profile for an "
                              "engine-side capture",
                     "code": "NO_JAX_RUNTIME"}
    raw = seconds_raw if seconds_raw is not None else "5"
    try:
        seconds = float(raw)
    except ValueError:
        return 400, {"error": f"seconds must be a number, got {raw!r}",
                     "code": "BAD_REQUEST"}
    if not 0 < seconds <= ProfilerCapture.MAX_SECONDS:
        return 400, {"error": f"seconds must be in "
                              f"(0, {ProfilerCapture.MAX_SECONDS:g}]",
                     "code": "BAD_REQUEST"}
    python = (python_raw or "").strip().lower() in ("1", "true", "yes", "on")
    try:
        return 200, default_profiler().capture(seconds, reason="on_demand",
                                               python=python)
    except CaptureBusy as e:
        return 409, {"error": str(e), "code": "CAPTURE_BUSY"}
