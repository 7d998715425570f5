"""Env-driven configuration, validated fail-fast at load.

Reference analogue: dotenv + Joi schemas (server/src/config/index.ts:6-92,
client/src/config/index.ts:6-148). Same shape and defaults; pydantic replaces
Joi. TPU-specific knobs (mesh, dtype, KV cache) join the worker schema per
SURVEY.md §5.6.

Defaults preserved from the reference:
- server port 4000 (server/src/config/index.ts:10)
- workerHeartbeatTimeout 15000 ms (:24), workerCleanupInterval 5000 ms (:25)
- jobTimeout 600000 ms (:28), retryAttempts 3 / retryDelay 5000 ms (:29-30)
- maxConcurrentJobsPerWorker 1 (:31) — the TPU engine supersedes this with
  continuous batching, so the default here is per-engine slot count
- bus key prefix "GridLLM:" (:17)
- worker heartbeatInterval 5000 ms (client/src/config/index.ts:94)
"""

from __future__ import annotations

import dataclasses
import os
import uuid
from typing import Any, Literal

from pydantic import BaseModel, Field, ValidationError


# ---------------------------------------------------------------------------
# GRIDLLM_* environment registry (ISSUE 8)
#
# Every ``GRIDLLM_*`` variable the system reads is declared here ONCE with
# its default and a one-line description, and read ONLY through the typed
# accessors below. The config-discipline rule (gridllm_tpu/analysis/)
# enforces both halves statically: a direct ``os.environ`` read of a
# GRIDLLM_* name outside this module is a finding, and so is an accessor
# call for an unregistered name. The README "Configuration" table is
# cross-checked against this registry by the same rule, so docs cannot
# drift from code.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One registered environment knob: the single source of truth for its
    default and documentation."""

    name: str
    default: str          # raw string form; "" means unset/empty default
    description: str


ENV_VARS: dict[str, EnvVar] = {}


def register_env(name: str, default: str, description: str) -> None:
    if name in ENV_VARS:
        # silent last-writer-wins would let two registrations (a bad
        # merge) disagree on the default with no signal anywhere — the
        # registry is single-source or it is nothing
        raise ValueError(f"duplicate register_env({name!r})")
    ENV_VARS[name] = EnvVar(name, default, description)


def _registered(name: str) -> EnvVar:
    var = ENV_VARS.get(name)
    if var is None:
        raise KeyError(
            f"unregistered env var {name!r}: declare it in "
            "gridllm_tpu/utils/config.py ENV_VARS (register_env) so the "
            "default and description live in one place"
        )
    return var


def compile_cache_dir() -> str:
    """The persistent XLA compile cache's directory. JAX's own
    JAX_COMPILATION_CACHE_DIR when the environment sets it — jax reads
    that itself and the program sets no other — else one fixed directory
    inside the checkout, ignored by git. Fixed because the path is part
    of the cache key: a directory that moves never hits. Every process
    of the program (workers, tests, chip_smoke.py) resolves it
    here, so they share one cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def env_raw(name: str) -> str | None:
    """The raw environment value, or None when unset. The name must be
    registered — callers with bespoke parsing start here."""
    _registered(name)
    return os.environ.get(name)


def env_str(name: str) -> str:
    var = _registered(name)
    raw = os.environ.get(name)
    return raw if raw is not None else var.default


def env_int(name: str) -> int:
    """Fail-fast: a set-but-malformed value raises (load_config turns that
    into a startup SystemExit) rather than silently serving the default —
    GRIDLLM_PROC_ID=two colliding with the real liaison process is exactly
    the failure mode a registry exists to prevent."""
    var = _registered(name)
    raw = os.environ.get(name)
    if not raw:
        return int(var.default or 0)
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not a valid integer "
            f"(default: {var.default or 0})") from None


def env_float(name: str) -> float:
    var = _registered(name)
    raw = os.environ.get(name)
    if not raw:
        return float(var.default or 0.0)
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not a valid number "
            f"(default: {var.default or 0.0})") from None


def env_int_lenient(name: str) -> int:
    """Like env_int, but a malformed value degrades to the registry
    default instead of raising — for reads on serving paths (engine step,
    KV migration mid-handoff) where an operator typo must fail the launch
    if anything, never a request already in flight."""
    try:
        return env_int(name)
    except ValueError:
        return int(_registered(name).default or 0)


def env_float_lenient(name: str) -> float:
    try:
        return env_float(name)
    except ValueError:
        return float(_registered(name).default or 0.0)


_FALSY = ("0", "off", "false", "no")
_TRUTHY = ("1", "on", "true", "yes")


def env_bool(name: str) -> bool:
    """One boolean grammar for every knob: the truthy/falsy sets below,
    anything else raises. The per-site parsers this replaced disagreed on
    unrecognized values (truthy-set sites read GRIDLLM_DISAGG=disable as
    off, falsy-set sites read it as on) — failing fast beats silently
    picking either side."""
    var = _registered(name)
    raw = os.environ.get(name)
    if not raw:
        return var.default.lower() in _TRUTHY
    low = raw.lower()
    if low in _TRUTHY:
        return True
    if low in _FALSY:
        return False
    raise ValueError(
        f"{name}={raw!r} is not a valid boolean "
        f"(truthy: {'/'.join(_TRUTHY)}; falsy: {'/'.join(_FALSY)})")


# -- registry: one entry per GRIDLLM_* knob, grouped by subsystem -----------

register_env("GRIDLLM_ENV", "development",
             "Deployment environment name (NODE_ENV also honored).")
register_env("GRIDLLM_LOG_LEVEL", "info",
             "Log level for the structured logger (debug/info/warning/error).")
register_env("GRIDLLM_BUS_URL", "",
             "Message-bus endpoint; empty = in-memory bus, "
             "resp://host:port = wire broker/Redis.")
register_env("GRIDLLM_BUS_ENDPOINTS", "",
             "Ordered comma list of resp://host:port broker endpoints "
             "(primary FIRST, warm standbys after) for client-driven "
             "failover with epoch fencing; empty = GRIDLLM_BUS_URL only.")
register_env("GRIDLLM_BUS_REJOIN_GRACE_MS", "10000",
             "After this process's bus session reconnects, hold worker-"
             "death verdicts and orphan sweeps this long (ms) so a "
             "broker bounce is not misread as a fleet-wide worker loss.")
register_env("GRIDLLM_BUS_RING_CAP", "512",
             "Broker replay-ring capacity per durable channel (messages)"
             " — the RESUME window a reconnecting subscriber can recover"
             " after an outage.")

# engine
register_env("GRIDLLM_MODELS", "",
             "Comma-separated model registry names this worker serves.")
register_env("GRIDLLM_CHECKPOINT_DIR", "",
             "Directory holding model checkpoints (safetensors layouts).")
register_env("GRIDLLM_DTYPE", "bfloat16",
             "Model compute/weight dtype.")
register_env("GRIDLLM_MAX_SEQ_LEN", "8192",
             "Maximum sequence length (prompt + generation) per request.")
register_env("GRIDLLM_MAX_BATCH_SLOTS", "8",
             "Continuous-batching slot count per engine.")
register_env("GRIDLLM_KV_PAGE_SIZE", "128",
             "Tokens per KV-cache page.")
register_env("GRIDLLM_STREAM_FLUSH_MS", "20",
             "Least time between two frames of one streamed response "
             "(ms), and the most a token is held: the worker keeps up to "
             "this much of a stream back, so that a stall reads that much "
             "shorter.")
register_env("GRIDLLM_PREFILL_BUCKETS", "512,1024,2048,4096,8192",
             "Comma-separated prefill padding buckets (tokens); prompts "
             "compile per bucket, not per length.")
register_env("GRIDLLM_MESH_SHAPE", "",
             "Device-mesh axes, e.g. \"tp:8\" or \"pp:2,tp:4\"; empty = "
             "single device.")
register_env("GRIDLLM_ALLOW_SYNTHETIC_WEIGHTS", "0",
             "Serve randomly initialized weights when no checkpoint is "
             "found (test/bench only).")
register_env("GRIDLLM_POOL_PAD", "0",
             "Force the lane-padded KV pool layout in Pallas interpret "
             "mode (kernel-coverage testing).")

# ops / kernels
register_env("GRIDLLM_PALLAS", "auto",
             "Pallas kernel policy: auto (TPU only), 1 (force on), "
             "0 (force off), interpret (CPU interpreter mode).")
register_env("GRIDLLM_MOE_RAGGED", "auto",
             "MoE grouped-matmul via ragged_dot: auto (a TPU under a mesh; "
             "one chip takes the grouped kernel instead, by the rows of "
             "the call, models/mixtral.py expert_form), 1 (force on), 0 "
             "(the all-experts form everywhere).")

# tiered KV cache (ISSUE 11): host-RAM spill + int8 KV pages
register_env("GRIDLLM_KV_HOST_BYTES", "0",
             "Host-RAM KV tier capacity in bytes: prefix-cache pages "
             "evicted from HBM spill here and page back in on "
             "match_prefix hits; 0 disables the tier.")
register_env("GRIDLLM_KV_SPILL_INT8", "1",
             "Quantize fp16/bf16 KV pages to int8 (scale-per-page) on "
             "host-tier spill, halving spill bytes; 0 spills raw bytes "
             "(lossless — restored streams byte-identical).")
register_env("GRIDLLM_KV_INT8", "0",
             "Resident int8 KV pool (per-row scales, dequant epilogue in "
             "the attention read path): halves KV HBM at a bounded "
             "accuracy cost; 1 enables.")
register_env("GRIDLLM_PREEMPT_AFTER_MS", "0",
             "Scheduler preemption: a queued higher-priority generation "
             "unplaceable for this long triggers suspend-to-host of one "
             "lower-priority running job; 0 disables preemption.")

# prefix caching
register_env("GRIDLLM_PREFIX_CACHE", "1",
             "Automatic prefix caching of completed requests' KV pages; "
             "0 disables.")
register_env("GRIDLLM_PREFIX_CACHE_PAGES", "-1",
             "Reuse-LRU capacity in pages; -1 = unbounded (whole pool), "
             "0 = off.")
register_env("GRIDLLM_PREFIX_AFFINITY_WEIGHT", "0.25",
             "Load-score bonus for workers whose heartbeat digest holds "
             "the request's prefix key; 0 disables affinity routing.")

# speculative decoding
register_env("GRIDLLM_SPEC_DECODE", "1",
             "Speculative decoding (n-gram drafting + batched "
             "verification); 0 disables.")
register_env("GRIDLLM_SPEC_K", "4",
             "Speculation depth: drafted tokens per slot per verify step "
             "(static per process); 0 disables.")
register_env("GRIDLLM_SPEC_DRAFTER", "ngram",
             "Drafter implementation (\"ngram\" is the phase-1 option).")
register_env("GRIDLLM_SPEC_NGRAM_MAX", "4",
             "Longest n-gram the prompt-lookup drafter matches on.")
register_env("GRIDLLM_SPEC_NGRAM_MIN", "1",
             "Shortest n-gram the prompt-lookup drafter falls back to.")
register_env("GRIDLLM_SPEC_LOOKBACK", "0",
             "Drafter match window over the slot history in tokens; "
             "0 = unbounded.")
register_env("GRIDLLM_SPEC_DRAFT_MODEL", "",
             "Registered config name of a tiny same-tokenizer draft model "
             "for model-based tree drafting; empty keeps n-gram drafting.")
register_env("GRIDLLM_SPEC_DRAFT_CHECKPOINT", "",
             "Checkpoint dir for the draft model; empty = fresh "
             "PRNGKey(0) init (test/bench path).")
register_env("GRIDLLM_SPEC_TREE_WIDTH", "2",
             "Draft-tree sibling fan-out at depth 1 (tree node budget is "
             "1 + K + width - 1); 1 = pure chain.")
register_env("GRIDLLM_SPEC_DRAFT_INGEST", "64",
             "Fixed catch-up chunk width (tokens) of the draft model's "
             "context-ingest forward.")

# multi-host SPMD
register_env("GRIDLLM_COORD_ADDR", "",
             "host:port of process 0 (jax distributed coordinator).")
register_env("GRIDLLM_NUM_PROCS", "1",
             "Total processes in the worker slice.")
register_env("GRIDLLM_PROC_ID", "0",
             "This process's id in the slice (0 = liaison).")

# scheduler / gateway / worker roles
register_env("GRIDLLM_DISAGG", "1",
             "Two-phase prefill/decode placement on split fleets; "
             "0 forces whole-request placement.")
register_env("GRIDLLM_WORKER_ROLE", "unified",
             "Fleet role of this worker: unified, prefill, or decode.")
register_env("GRIDLLM_WORKER_ADVERTISE_ADDR", "",
             "host:port other workers reach this worker's health server "
             "at (direct KV-transfer fallback); empty = 127.0.0.1:port.")
register_env("GRIDLLM_ENFORCE_KEEP_ALIVE", "0",
             "Unload models whose keep_alive window lapses (Ollama "
             "semantics); off by default — TPU reloads cost minutes.")

# KV migration (disaggregated serving)
register_env("GRIDLLM_KVX_CHUNK_BYTES", "262144",
             "KV-migration chunk size on the bus path (bytes).")
register_env("GRIDLLM_KVX_WINDOW", "8",
             "KV-migration chunks in flight before awaiting receiver "
             "progress.")
register_env("GRIDLLM_KVX_TIMEOUT_MS", "15000",
             "End-to-end KV-transfer deadline (ms).")
register_env("GRIDLLM_KVX_HTTP_BYTES", "8388608",
             "Payload size beyond which migration uses one direct "
             "worker-to-worker HTTP POST instead of bus chunks.")

# observability: SLO / watchdog / flight recorder
register_env("GRIDLLM_SLO_ENABLED", "1",
             "SLO engine (attainment, burn rate, goodput); 0 disables.")
register_env("GRIDLLM_SLO_CLASSES", "",
             "JSON object replacing the default per-class objective table "
             "({class: {ttft_ms, itl_ms, e2e_ms, target}}).")
register_env("GRIDLLM_SLO_WINDOWS", "",
             "Comma list of burn-rate window seconds (default 300,3600).")
register_env("GRIDLLM_WATCHDOG_ENABLED", "1",
             "Per-phase hang watchdog; 0 disables.")
register_env("GRIDLLM_WATCHDOG_INTERVAL", "1000",
             "Watchdog sweep interval (ms).")
register_env("GRIDLLM_WATCHDOG_QUEUE_DEADLINE", "120000",
             "Queue-phase hang deadline (ms).")
register_env("GRIDLLM_WATCHDOG_DISPATCH_DEADLINE", "60000",
             "Dispatch-phase hang deadline (ms).")
register_env("GRIDLLM_WATCHDOG_PREFILL_DEADLINE", "240000",
             "Prefill-phase hang deadline (ms).")
register_env("GRIDLLM_WATCHDOG_DECODE_STALL", "60000",
             "Decode-step stall deadline after the first token (ms).")
register_env("GRIDLLM_WATCHDOG_REQUEUE", "1",
             "Cancel + front-requeue jobs the watchdog catches hung; "
             "0 = diagnose only.")
register_env("GRIDLLM_WATCHDOG_PROFILE_S", "0",
             "Auto jax.profiler capture length on decode-step hangs "
             "(seconds); 0 disables (stop-flush starves heartbeats).")
register_env("GRIDLLM_FLIGHTREC_CAPACITY", "256",
             "Flight-recorder ring capacity per subsystem.")

# observability: fleet timeline & incident forensics (ISSUE 17)
register_env("GRIDLLM_TIMELINE", "1",
             "Fleet-wide causal timeline: arm the HLC-stamped event "
             "publisher (and, on control-plane members, the store + "
             "incident collector behind /admin/timeline and "
             "/admin/incidents). 0 disarms all of it.")
register_env("GRIDLLM_TIMELINE_QUEUE", "2048",
             "Bounded timeline publisher queue (events); overflow drops "
             "the OLDEST events and counts them in "
             "gridllm_timeline_dropped_events_total — emitters never "
             "block.")
register_env("GRIDLLM_TIMELINE_FLUSH_MS", "200",
             "Timeline publisher flush interval (ms): queued events "
             "batch onto one obs:event message per flush.")
register_env("GRIDLLM_TIMELINE_BATCH", "256",
             "Max events per obs:event batch message.")
register_env("GRIDLLM_TIMELINE_STORE", "4096",
             "TimelineStore global event ring capacity (per member "
             "running a store).")
register_env("GRIDLLM_TIMELINE_REQUESTS", "512",
             "TimelineStore per-request index: max distinct request ids "
             "(LRU).")
register_env("GRIDLLM_TIMELINE_INCIDENT_WINDOW_MS", "5000",
             "Causal window (± ms around the trigger event) an incident "
             "report snapshots from the fleet timeline.")
register_env("GRIDLLM_TIMELINE_INCIDENTS", "32",
             "Max retained incident reports (oldest evicted).")

# observability: usage attribution / capacity signals
register_env("GRIDLLM_TENANT_HEADER", "X-GridLLM-Tenant",
             "HTTP header the gateway reads the tenant id from; falls "
             "back to a hash of the Authorization bearer, else "
             "'anonymous'.")
register_env("GRIDLLM_TENANT_LRU", "64",
             "Max distinct tenant label values per registry; overflow "
             "tenants are folded into the 'other' bucket.")
register_env("GRIDLLM_CAPACITY_EWMA_HALFLIFE_S", "60",
             "Half-life (seconds) of the per-model arrival/service rate "
             "and wait-time EWMAs behind /admin/capacity.")

# observability: active fleet health (ISSUE 19) — canary prober + detector
register_env("GRIDLLM_PROBE_INTERVAL_MS", "0",
             "Canary probe cadence per scheduler shard (ms between "
             "rounds); each round probes one (worker, model) pair "
             "round-robin. 0 disables the prober.")
register_env("GRIDLLM_PROBE_CONCURRENCY", "1",
             "Max canary probes in flight at once per shard (rate bound: "
             "a slow fleet must never accumulate probe backlog).")
register_env("GRIDLLM_PROBE_TIMEOUT_MS", "15000",
             "Per-probe timeout (ms); a timed-out canary counts as a "
             "failed round for the worker's health verdict.")
register_env("GRIDLLM_PROBE_TOKENS", "8",
             "Tokens each canary generates (greedy, fixed seed) — the "
             "byte-determinism surface the golden hash covers.")
register_env("GRIDLLM_HEALTH_EWMA_HALFLIFE_S", "60",
             "Half-life (seconds) of the per-worker baseline EWMAs "
             "(canary e2e latency, decode ITL, heartbeat gap).")
register_env("GRIDLLM_HEALTH_Z_THRESHOLD", "3.0",
             "z-score above which a baseline observation counts as a "
             "regression strike against its worker.")
register_env("GRIDLLM_HEALTH_MIN_SAMPLES", "5",
             "Baseline observations required before z-score judgments "
             "begin (warmup; earlier observations only train the EWMA).")
register_env("GRIDLLM_HEALTH_DEGRADE_STRIKES", "2",
             "Consecutive regression strikes that move an online worker "
             "to degraded (placement penalty applied).")
register_env("GRIDLLM_HEALTH_QUARANTINE_STRIKES", "3",
             "Consecutive strikes while degraded that quarantine the "
             "worker (drained via the graceful-drain path).")
register_env("GRIDLLM_HEALTH_PROBATION_PASSES", "2",
             "Clean canary rounds a probation (or degraded) worker needs "
             "to rejoin the online pool.")
register_env("GRIDLLM_HEALTH_DEGRADED_PENALTY", "0.5",
             "Load-score penalty _select_worker adds to degraded/"
             "probation workers (same scale as the proportional load "
             "term; mirrors prefix_affinity_weight).")

# elastic serving (ISSUE 20) — snapshot tier, placement
register_env("GRIDLLM_WEIGHT_SNAPSHOT_BYTES", "0",
             "Host-RAM weight snapshot tier capacity (bytes). Unloading "
             "a model parks its device params as host arrays keyed by "
             "checkpoint identity; a later load restores via host-to-"
             "device transfer instead of re-reading the checkpoint. "
             "LRU-evicted past capacity; 0 disables the tier.")
register_env("GRIDLLM_PLACEMENT_INTERVAL_MS", "0",
             "Model-placement controller cadence per scheduler shard "
             "(ms between ticks). Each tick compares per-model demand "
             "(queue depth, scale hints) against resident replicas and "
             "issues load/unload admin ops to live workers. 0 disables "
             "the controller (static placement).")
register_env("GRIDLLM_MODEL_IDLE_TTL_MS", "0",
             "Idle time (ms, no queued/active work and no arrivals) "
             "after which the placement controller unloads a model's "
             "replicas above its min-replica floor, releasing slots and "
             "HBM. 0 disables idle unload (models stay resident).")
register_env("GRIDLLM_SWAP_COOLDOWN_MS", "10000",
             "Hysteresis: minimum gap (ms) between placement actions "
             "for the same model, so demand flapping around a threshold "
             "cannot thrash load/unload cycles.")
register_env("GRIDLLM_MODEL_FLOORS", "",
             "Comma-separated model=N min-replica floors (SLO classes): "
             "the placement controller never drops a listed model below "
             "N replicas, and restores it toward N when under.")

# observability: perf introspection
register_env("GRIDLLM_RECOMPILE_BUDGET", "4",
             "Steady-state recompiles tolerated per window before a "
             "recompile-storm diagnosis.")
register_env("GRIDLLM_RECOMPILE_WINDOW", "60",
             "Recompile-storm budget window (seconds).")
register_env("GRIDLLM_PROFILE_DIR", "",
             "jax.profiler artifact root; empty = /tmp/gridllm-profiles.")
register_env("GRIDLLM_PROFILE_KEEP", "4",
             "Profiler captures kept before the oldest are pruned.")

# fault tolerance (ISSUE 9): drain / resume / retry shaping / deadlines
register_env("GRIDLLM_DRAIN_BUDGET_MS", "5000",
             "Graceful-drain budget: how long a draining worker lets "
             "in-flight jobs finish before live-migrating the rest (ms).")
register_env("GRIDLLM_RESUME_SNAPSHOT_TOKENS", "8",
             "Publish a decode-state resume snapshot every N generated "
             "tokens (crash-resume watermark); 0 disables snapshots.")
register_env("GRIDLLM_RETRY_BACKOFF_MAX_MS", "60000",
             "Cap for the retry ladder's exponential backoff (full "
             "jitter; base is the retry delay).")
register_env("GRIDLLM_RETRY_BUDGET_PER_MIN", "120",
             "Fleet-wide retry budget (token bucket, retries/min): when "
             "burning, further retries shed to immediate failure with "
             "retry_budget_exhausted; 0 = unlimited.")
register_env("GRIDLLM_REQUEST_DEADLINE_MS", "0",
             "Queued-job deadline from submission (ms): jobs still "
             "queued past it are shed with deadline_exceeded (HTTP 504);"
             " 0 disables.")
register_env("GRIDLLM_REQUEST_DEADLINE_CLASSES", "",
             "JSON object of per-SLO-class deadline overrides (ms), e.g."
             " {\"interactive\": 30000, \"batch\": 600000}.")

# deterministic fault injection (ISSUE 9, faults.py)
register_env("GRIDLLM_FAULT_SPEC", "",
             "Deterministic fault-injection spec: comma list of "
             "site=probability, site=@N (Nth call), or site=@N+ (from "
             "the Nth call); empty disables.")
register_env("GRIDLLM_FAULT_SEED", "0",
             "Seed for the per-site fault-injection RNGs; the decision "
             "sequence is a pure function of (seed, site, call #).")

# scaled control plane (ISSUE 15): sharded schedulers + gateway replicas
register_env("GRIDLLM_CONTROLPLANE", "local",
             "Control-plane mode: local (scheduler in-process, the "
             "default single-box layout) or gateway (stateless replica "
             "that publishes submissions to scheduler shards over the "
             "bus; run shards with python -m gridllm_tpu.controlplane).")
register_env("GRIDLLM_CONTROLPLANE_ID", "",
             "Stable member id of this control-plane process (gateway "
             "replica or scheduler shard); empty = generated cp-<hex>.")
register_env("GRIDLLM_SHARD_COUNT", "1",
             "Scheduler shard count M: the job-id space is partitioned "
             "deterministically over M shards (every member must agree).")
register_env("GRIDLLM_SHARD_ID", "0",
             "Home shard index of this scheduler-shard process (0..M-1);"
             " the shard also adopts orphaned partitions whose lease "
             "expires.")
register_env("GRIDLLM_SHARD_LEASE_TTL_MS", "6000",
             "Shard-ownership lease TTL (ms): a shard silent past this "
             "is presumed dead and its partition is adopted (epoch "
             "bump) by a surviving shard.")
register_env("GRIDLLM_SHARD_RENEW_MS", "2000",
             "Shard lease renew/sweep interval (ms); must be well under "
             "the lease TTL.")
register_env("GRIDLLM_SHARD_STATUS_MS", "2000",
             "Control-plane status-envelope publish interval (ms) — "
             "feeds the gateway replicas' fleet-wide /metrics, "
             "/admin/slo, and /health/workers aggregation.")
register_env("GRIDLLM_SHARD_HEALTH_PORT", "4100",
             "HTTP port a scheduler-shard process serves /metrics, "
             "/admin/slo, and /admin/dump on; 0 disables the listener.")
register_env("GRIDLLM_RATELIMIT_SCOPE", "replica",
             "Gateway rate-limit bucket scope: replica (per-process "
             "buckets — N replicas multiply every limit by N) or fleet "
             "(bucket state shared through the bus so the limit is "
             "fleet-wide).")

# static analysis / sanitizers (ISSUE 8)
register_env("GRIDLLM_ENDPOINT", "http://localhost:4000",
             "Gateway endpoint the integration differential harness "
             "drives (tests/integration).")
register_env("GRIDLLM_SANITIZE", "0",
             "Runtime lock-discipline sanitizer: instrument Lock/RLock "
             "acquires, build the lock-order graph, fail tests on cycles "
             "or unlocked allocator mutation.")
register_env("GRIDLLM_NUMCHECK_SAMPLE", "0.05",
             "Numerics sanitizer (on the GRIDLLM_SANITIZE switch): "
             "fraction of kernel dispatches shadow-executed against their "
             "jnp reference at the KERNELS-registry tolerance (1.0 = every "
             "dispatch; CI numcheck-smoke forces 1.0).")
register_env("GRIDLLM_NUMCHECK_SEED", "0",
             "Seed for the numerics sanitizer's per-op sampling streams; "
             "decisions are a pure function of (seed, op, trace #).")


def _env(name: str, default: Any) -> Any:
    raw = os.environ.get(name)
    if raw is None:
        return default
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


class BusConfig(BaseModel):
    """reference: redis block of server/src/config/index.ts:12-18."""

    url: str = ""                      # "" → in-memory bus; "resp://host:port" → wire
    host: str = "localhost"
    port: int = 6379
    password: str | None = None
    db: int = 0
    key_prefix: str = "GridLLM:"
    # Bus HA (ISSUE 10): ordered broker endpoint list, primary first —
    # clients walk it on every (re)connect, promote the first reachable
    # standby only after every earlier endpoint failed, and fence off
    # resurrected stale primaries by epoch. Empty = url only.
    endpoints: list[str] = Field(default_factory=list)


class SchedulerConfig(BaseModel):
    """reference: performance/scheduling block, server/src/config/index.ts:22-33."""

    worker_heartbeat_timeout_ms: int = Field(15_000, gt=0)
    worker_cleanup_interval_ms: int = Field(5_000, gt=0)
    connection_monitor_interval_ms: int = Field(5_000, gt=0)
    quick_disconnect_window_ms: int = Field(15_000, gt=0)
    orphan_assign_threshold_ms: int = Field(10_000, gt=0)
    job_timeout_ms: int = Field(600_000, gt=0)
    retry_attempts: int = Field(3, ge=0)
    retry_delay_ms: int = Field(5_000, ge=0)
    # Retry shaping (ISSUE 9): retry_delay_ms is the BASE of a capped
    # exponential backoff with full jitter (delay ~ U[0, min(cap,
    # base·2^attempt)]), and the fleet-wide retry budget is a token
    # bucket — when a degraded fleet is burning retries faster than the
    # budget refills, further retries shed to immediate failure with
    # ``retry_budget_exhausted`` instead of melting the fleet.
    retry_backoff_max_ms: int = Field(60_000, ge=0)
    retry_budget_per_min: float = Field(120, ge=0)
    # Per-class request deadlines (ISSUE 9): a job still QUEUED past its
    # deadline (measured from first submission) is shed with
    # ``deadline_exceeded`` (the gateway maps it to HTTP 504) instead of
    # occupying the queue. 0 disables; the class dict overrides per
    # SLO class (obs classify_request).
    request_deadline_ms: int = Field(0, ge=0)
    request_deadline_classes: dict[str, int] = Field(default_factory=dict)
    # Partition-aware liveness (ISSUE 10): while this process's own bus
    # session is degraded, the registry suspends worker-death verdicts
    # and the scheduler defers orphan sweeps; both stay held this long
    # after the session rejoins so heartbeats published during the
    # outage can land (the RESUME replay) before anyone is pronounced
    # dead. Without this, a 10-second broker restart triggers a mass
    # orphan-requeue storm of perfectly healthy jobs.
    bus_rejoin_grace_ms: int = Field(10_000, ge=0)
    # Preemption-based priority (ISSUE 11): when a queued generation of a
    # strictly higher priority class has been unplaceable for this long
    # (ms) and a lower-priority job is running on a worker serving its
    # model, the scheduler asks that worker to suspend the job to the
    # host KV tier (``job_preempt``); the victim requeues at the BACK of
    # its own priority class with its resume watermark and pages back in
    # from host when pressure clears. 0 (default) disables preemption.
    preempt_after_ms: int = Field(0, ge=0)
    # capacity NACKs requeue without consuming the retry ladder, but only
    # this many times — a nack storm then falls through to the real ladder
    max_nacks: int = Field(25, ge=0)
    max_concurrent_jobs_per_worker: int = Field(1, ge=1)
    # TPU change: the reference polled a 1 s tick (JobScheduler.ts:128-135);
    # we dispatch event-driven, with this tick only as a fallback sweep.
    sweep_interval_ms: int = Field(1_000, gt=0)
    # Prefix-affinity routing (ISSUE 3): a worker whose heartbeat digest
    # contains the job's prefixKey gets this subtracted from its
    # proportional-load score. Affinity never overrides the load cap
    # (candidates are pre-filtered by availability) — it breaks ties and
    # outweighs load differences up to this fraction, so a hot worker
    # still sheds. 0 disables the term.
    prefix_affinity_weight: float = Field(0.25, ge=0)
    # Disaggregated prefill/decode serving (ISSUE 7): when the fleet has
    # BOTH a prefill pool and a decode pool for a model, generation jobs
    # get two-phase placement (prefill worker + planned decode handoff
    # with KV-page migration). Default on — with a homogeneous unified
    # fleet there are no pools, so nothing changes. GRIDLLM_DISAGG=0
    # forces whole-request placement even on a split fleet.
    disagg_enabled: bool = True


class GatewayConfig(BaseModel):
    """reference: server block, server/src/config/index.ts:8-11, 38-43."""

    host: str = "0.0.0.0"
    port: int = 4000
    max_body_bytes: int = 10 * 1024 * 1024  # express json limit 10mb (index.ts:47)
    rate_limit_window_ms: int = 900_000
    rate_limit_max_requests: int = 100
    rate_limit_enabled: bool = True
    # Multi-replica rate limiting (ISSUE 15): "replica" keeps the
    # original per-process fixed-window buckets — N gateway replicas
    # therefore multiply every limit by N, which is the documented
    # semantics of this scope. "fleet" shares bucket state through the
    # bus (one read-modify-write per counted request) so the limit is
    # fleet-wide regardless of which replica serves the request.
    rate_limit_scope: Literal["replica", "fleet"] = "replica"
    default_request_timeout_ms: int = 300_000
    # Ollama-exact idle residency: unload a model when its keep_alive
    # window passes with no requests (Ollama defaults to 5m). OFF by
    # default — a TPU reload of a 70B checkpoint costs minutes, so the
    # default here keeps weights resident and honors keep_alive only as
    # the advertised /api/ps expiry. GRIDLLM_ENFORCE_KEEP_ALIVE=1 opts in.
    enforce_keep_alive: bool = False


class EngineConfig(BaseModel):
    """TPU engine knobs — NEW (replaces the reference's ollama block,
    client/src/config/index.ts:82-89)."""

    models: str = ""                   # comma-separated model specs to serve
    checkpoint_dir: str = ""
    dtype: str = "bfloat16"
    max_seq_len: int = 8192
    max_batch_slots: int = 8           # continuous-batching slot count
    prefill_buckets: str = "512,1024,2048,4096,8192"
    kv_page_size: int = 128
    stream_flush_ms: int = 20          # least ms between a stream's frames; most a token is held
    # mesh axes (parallel/mesh.py): e.g. "tp:8", "pp:2,tp:4", "dp:2,tp:4";
    # "" → single device
    mesh_shape: str = ""
    decode_steps_per_host_sync: int = 8


class WorkerConfig(BaseModel):
    """reference: client/src/config/index.ts:6-148."""

    worker_id: str = Field(default_factory=lambda: f"worker-{uuid.uuid4().hex[:12]}")
    host: str = "0.0.0.0"
    port: int = 3000
    heartbeat_interval_ms: int = Field(5_000, gt=0)
    resource_monitor_interval_ms: int = Field(10_000, gt=0)
    max_reconnect_attempts: int = 10
    max_concurrent_tasks: int = 1      # superseded by engine.max_batch_slots when engine present
    performance_tier: str = "medium"
    # Disaggregated serving (ISSUE 7): this worker's fleet role
    # (GRIDLLM_WORKER_ROLE). "prefill" workers take phase-1 placements
    # and export KV; "decode" workers admit from imported pages;
    # "unified" (default) serves whole requests as before.
    role: Literal["unified", "prefill", "decode"] = "unified"
    # host:port other workers can reach this worker's health HTTP server
    # at (GRIDLLM_WORKER_ADVERTISE_ADDR) — the direct worker-to-worker
    # KV-transfer fallback path. "" → 127.0.0.1:{port} (single-host
    # deployments and tests).
    advertise_addr: str = ""
    # Graceful drain (ISSUE 9): on SIGTERM / POST /admin/drain, how long
    # in-flight jobs get to finish before the worker live-migrates the
    # remaining decodes (suspend + KV export + job:drain handoff).
    drain_budget_ms: int = Field(5_000, ge=0)


class SLOClassConfig(BaseModel):
    """Latency objectives for one request class (ISSUE 2). ``None`` means
    the objective does not apply to the class (embeddings have no ITL)."""

    ttft_ms: float | None = None       # submit → first streamed token
    itl_ms: float | None = None        # mean inter-token latency
    e2e_ms: float | None = None        # submit → final result
    target: float = Field(0.99, gt=0, le=1)  # attainment objective


def default_slo_classes() -> dict[str, SLOClassConfig]:
    """Request classes and their default objectives. Classification
    (obs/slo.py classify_request): streaming generation is interactive,
    non-streaming generation is batch, embeddings are their own class."""
    return {
        "interactive": SLOClassConfig(ttft_ms=2_000, itl_ms=200,
                                      e2e_ms=120_000, target=0.99),
        "batch": SLOClassConfig(e2e_ms=300_000, target=0.95),
        "embedding": SLOClassConfig(e2e_ms=10_000, target=0.99),
    }


class SLOConfig(BaseModel):
    """SLO engine knobs (obs/slo.py). ``GRIDLLM_SLO_CLASSES`` may carry a
    JSON object {class: {ttft_ms, itl_ms, e2e_ms, target}} that REPLACES
    the defaults wholesale (partial per-class merges would make the
    effective objective ambiguous)."""

    enabled: bool = True
    classes: dict[str, SLOClassConfig] = Field(
        default_factory=default_slo_classes)
    # burn-rate windows (seconds): one fast window for paging, one slow
    # window for ticket-level alerts (multi-window burn-rate alerting)
    windows_s: list[int] = Field(default_factory=lambda: [300, 3600])


class WatchdogConfig(BaseModel):
    """Hang watchdog (obs/watchdog.py): per-phase deadlines after which a
    request is flagged as wedged. Defaults are generous — first-compile on
    a cold worker is minutes, and a false hang requeue wastes real work."""

    enabled: bool = True
    interval_ms: int = Field(1_000, gt=0)
    # open queue.wait span older than this → phase "queue"
    queue_deadline_ms: int = Field(120_000, gt=0)
    # assigned, no stream frame yet → "dispatch" past this ...
    dispatch_deadline_ms: int = Field(60_000, gt=0)
    # ... and "prefill" past this (gateway-side the two are only
    # distinguishable by age; worker-side engine probes refine it)
    prefill_deadline_ms: int = Field(240_000, gt=0)
    # first token seen but no frame for this long → "decode-step"
    decode_stall_ms: int = Field(60_000, gt=0)
    # abort + requeue hung ACTIVE jobs (reason "hang"); queue-phase hangs
    # are diagnosis-only (there is nothing to requeue)
    requeue: bool = True
    # on a decode-step hang, auto-start a short jax.profiler capture
    # (obs/perf.py) so the trace covers the wedge itself; 0 (default)
    # disables — OPT-IN via GRIDLLM_WATCHDOG_PROFILE_S because the
    # capture's stop-flush serializes profiler data while holding the
    # GIL for seconds, which can starve heartbeats/streams mid-incident
    # and turn a surgical hang-requeue into a worker-crash orphaning.
    # Only meaningful when the engine runs in THIS process (bench,
    # single-process deploys) — split deployments use the worker health
    # port's POST /admin/profile instead.
    profile_on_hang_s: float = Field(0.0, ge=0)


class ControlPlaneConfig(BaseModel):
    """Horizontally scaled control plane (ISSUE 15): N stateless gateway
    replicas in front of M scheduler shards, each owning a deterministic
    partition of the job-id space via bus-backed leases fenced by epoch.

    ``mode`` selects what THIS process is: ``local`` (default) keeps the
    scheduler in the gateway process — exactly the pre-ISSUE-15 layout;
    ``gateway`` runs a stateless replica that publishes submissions on
    ``ctrl:submit`` and rebuilds streaming state from the durable
    result/stream channels (any replica can serve any request). Shard
    processes run ``python -m gridllm_tpu.controlplane`` and are
    configured by ``shard_id``/``num_shards`` plus the lease timers."""

    mode: Literal["local", "gateway"] = "local"
    member_id: str = ""                # "" → generated cp-<hex>
    num_shards: int = Field(1, ge=1)
    shard_id: int = Field(0, ge=0)
    lease_ttl_ms: int = Field(6_000, gt=0)
    renew_interval_ms: int = Field(2_000, gt=0)
    status_interval_ms: int = Field(2_000, gt=0)
    shard_health_port: int = Field(4_100, ge=0)


class TimelineConfig(BaseModel):
    """Fleet timeline & incident forensics (ISSUE 17): the HLC-stamped
    event publisher every member arms, plus the store/collector sizes on
    members that serve /admin/timeline + /admin/incidents."""

    enabled: bool = True
    queue_capacity: int = Field(2_048, gt=0)
    flush_ms: float = Field(200.0, gt=0)
    batch_max: int = Field(256, gt=0)
    store_capacity: int = Field(4_096, gt=0)
    store_requests: int = Field(512, gt=0)
    incident_window_ms: float = Field(5_000.0, gt=0)
    max_incidents: int = Field(32, gt=0)


class ObsConfig(BaseModel):
    """Interpretation-layer observability (ISSUE 2): SLO engine, hang
    watchdog, flight recorder."""

    slo: SLOConfig = Field(default_factory=SLOConfig)
    watchdog: WatchdogConfig = Field(default_factory=WatchdogConfig)
    # per-subsystem ring capacity of the flight recorder
    flightrec_capacity: int = Field(256, gt=0)
    # fleet timeline & incident forensics (ISSUE 17)
    timeline: TimelineConfig = Field(default_factory=TimelineConfig)


class Config(BaseModel):
    env: str = "development"
    bus: BusConfig = Field(default_factory=BusConfig)
    scheduler: SchedulerConfig = Field(default_factory=SchedulerConfig)
    gateway: GatewayConfig = Field(default_factory=GatewayConfig)
    worker: WorkerConfig = Field(default_factory=WorkerConfig)
    engine: EngineConfig = Field(default_factory=EngineConfig)
    obs: ObsConfig = Field(default_factory=ObsConfig)
    controlplane: ControlPlaneConfig = Field(
        default_factory=ControlPlaneConfig)


def _slo_config_from_env() -> SLOConfig:
    """SLO objectives from the environment. ``GRIDLLM_SLO_CLASSES`` is a
    JSON object replacing the default class table; ``GRIDLLM_SLO_WINDOWS``
    is a comma list of burn-rate window seconds."""
    import json

    kw: dict[str, Any] = {"enabled": env_bool("GRIDLLM_SLO_ENABLED")}
    raw = env_raw("GRIDLLM_SLO_CLASSES")
    if raw:
        kw["classes"] = {
            name: SLOClassConfig(**spec)
            for name, spec in json.loads(raw).items()
        }
    windows = env_raw("GRIDLLM_SLO_WINDOWS")
    if windows:
        kw["windows_s"] = [int(w) for w in windows.split(",") if w]
    return SLOConfig(**kw)


def _deadline_classes_from_env() -> dict[str, int]:
    """GRIDLLM_REQUEST_DEADLINE_CLASSES: JSON {class: deadline_ms}."""
    import json

    raw = env_raw("GRIDLLM_REQUEST_DEADLINE_CLASSES")
    if not raw:
        return {}
    return {str(k): int(v) for k, v in json.loads(raw).items()}


def load_config() -> Config:
    """Build Config from the environment; raise on invalid values (the
    reference fails fast at import on Joi errors, server/src/config/index.ts:45-49)."""
    try:
        return Config(
            env=_env("NODE_ENV", env_str("GRIDLLM_ENV")),
            bus=BusConfig(
                url=env_str("GRIDLLM_BUS_URL"),
                host=_env("REDIS_HOST", "localhost"),
                port=_env("REDIS_PORT", 6379),
                password=os.environ.get("REDIS_PASSWORD") or None,
                db=_env("REDIS_DB", 0),
                key_prefix=_env("REDIS_KEY_PREFIX", "GridLLM:"),
                endpoints=[e.strip() for e in
                           env_str("GRIDLLM_BUS_ENDPOINTS").split(",")
                           if e.strip()],
            ),
            scheduler=SchedulerConfig(
                worker_heartbeat_timeout_ms=_env("WORKER_HEARTBEAT_TIMEOUT", 15_000),
                worker_cleanup_interval_ms=_env("WORKER_CLEANUP_INTERVAL", 5_000),
                job_timeout_ms=_env("JOB_TIMEOUT", 600_000),
                retry_attempts=_env("JOB_RETRY_ATTEMPTS", 3),
                retry_delay_ms=_env("JOB_RETRY_DELAY", 5_000),
                max_concurrent_jobs_per_worker=_env("MAX_CONCURRENT_JOBS_PER_WORKER", 1),
                sweep_interval_ms=_env("SCHEDULER_SWEEP_INTERVAL", 1_000),
                prefix_affinity_weight=env_float(
                    "GRIDLLM_PREFIX_AFFINITY_WEIGHT"),
                disagg_enabled=env_bool("GRIDLLM_DISAGG"),
                retry_backoff_max_ms=env_int("GRIDLLM_RETRY_BACKOFF_MAX_MS"),
                retry_budget_per_min=env_float(
                    "GRIDLLM_RETRY_BUDGET_PER_MIN"),
                request_deadline_ms=env_int("GRIDLLM_REQUEST_DEADLINE_MS"),
                request_deadline_classes=_deadline_classes_from_env(),
                bus_rejoin_grace_ms=env_int("GRIDLLM_BUS_REJOIN_GRACE_MS"),
                preempt_after_ms=env_int("GRIDLLM_PREEMPT_AFTER_MS"),
            ),
            gateway=GatewayConfig(
                host=_env("HOST", "0.0.0.0"),
                port=_env("PORT", 4000),
                rate_limit_window_ms=_env("RATE_LIMIT_WINDOW_MS", 900_000),
                rate_limit_max_requests=_env("RATE_LIMIT_MAX_REQUESTS", 100),
                rate_limit_enabled=_env("RATE_LIMIT_ENABLED", True),
                rate_limit_scope=env_str("GRIDLLM_RATELIMIT_SCOPE"),
                enforce_keep_alive=env_bool("GRIDLLM_ENFORCE_KEEP_ALIVE"),
            ),
            controlplane=ControlPlaneConfig(
                mode=env_str("GRIDLLM_CONTROLPLANE"),
                member_id=env_str("GRIDLLM_CONTROLPLANE_ID"),
                num_shards=env_int("GRIDLLM_SHARD_COUNT"),
                shard_id=env_int("GRIDLLM_SHARD_ID"),
                lease_ttl_ms=env_int("GRIDLLM_SHARD_LEASE_TTL_MS"),
                renew_interval_ms=env_int("GRIDLLM_SHARD_RENEW_MS"),
                status_interval_ms=env_int("GRIDLLM_SHARD_STATUS_MS"),
                shard_health_port=env_int("GRIDLLM_SHARD_HEALTH_PORT"),
            ),
            worker=WorkerConfig(
                worker_id=_env("WORKER_ID", f"worker-{uuid.uuid4().hex[:12]}"),
                host=_env("WORKER_HOST", "0.0.0.0"),
                port=_env("WORKER_PORT", 3000),
                heartbeat_interval_ms=_env("HEARTBEAT_INTERVAL", 5_000),
                max_reconnect_attempts=_env("MAX_RECONNECT_ATTEMPTS", 10),
                max_concurrent_tasks=_env("MAX_CONCURRENT_TASKS", 1),
                performance_tier=_env("PERFORMANCE_TIER", "medium"),
                role=env_str("GRIDLLM_WORKER_ROLE"),
                advertise_addr=env_str("GRIDLLM_WORKER_ADVERTISE_ADDR"),
                drain_budget_ms=env_int("GRIDLLM_DRAIN_BUDGET_MS"),
            ),
            engine=EngineConfig(
                models=env_str("GRIDLLM_MODELS"),
                checkpoint_dir=env_str("GRIDLLM_CHECKPOINT_DIR"),
                dtype=env_str("GRIDLLM_DTYPE"),
                max_seq_len=env_int("GRIDLLM_MAX_SEQ_LEN"),
                max_batch_slots=env_int("GRIDLLM_MAX_BATCH_SLOTS"),
                kv_page_size=env_int("GRIDLLM_KV_PAGE_SIZE"),
                stream_flush_ms=env_int("GRIDLLM_STREAM_FLUSH_MS"),
                prefill_buckets=env_str("GRIDLLM_PREFILL_BUCKETS"),
                mesh_shape=env_str("GRIDLLM_MESH_SHAPE"),
            ),
            obs=ObsConfig(
                slo=_slo_config_from_env(),
                watchdog=WatchdogConfig(
                    enabled=env_bool("GRIDLLM_WATCHDOG_ENABLED"),
                    interval_ms=env_int("GRIDLLM_WATCHDOG_INTERVAL"),
                    queue_deadline_ms=env_int(
                        "GRIDLLM_WATCHDOG_QUEUE_DEADLINE"),
                    dispatch_deadline_ms=env_int(
                        "GRIDLLM_WATCHDOG_DISPATCH_DEADLINE"),
                    prefill_deadline_ms=env_int(
                        "GRIDLLM_WATCHDOG_PREFILL_DEADLINE"),
                    decode_stall_ms=env_int(
                        "GRIDLLM_WATCHDOG_DECODE_STALL"),
                    requeue=env_bool("GRIDLLM_WATCHDOG_REQUEUE"),
                    profile_on_hang_s=env_float(
                        "GRIDLLM_WATCHDOG_PROFILE_S"),
                ),
                flightrec_capacity=env_int("GRIDLLM_FLIGHTREC_CAPACITY"),
                timeline=TimelineConfig(
                    enabled=env_bool("GRIDLLM_TIMELINE"),
                    queue_capacity=env_int("GRIDLLM_TIMELINE_QUEUE"),
                    flush_ms=env_float("GRIDLLM_TIMELINE_FLUSH_MS"),
                    batch_max=env_int("GRIDLLM_TIMELINE_BATCH"),
                    store_capacity=env_int("GRIDLLM_TIMELINE_STORE"),
                    store_requests=env_int("GRIDLLM_TIMELINE_REQUESTS"),
                    incident_window_ms=env_float(
                        "GRIDLLM_TIMELINE_INCIDENT_WINDOW_MS"),
                    max_incidents=env_int("GRIDLLM_TIMELINE_INCIDENTS"),
                ),
            ),
        )
    except (ValidationError, ValueError) as e:  # pragma: no cover - fail fast
        raise SystemExit(f"Invalid configuration: {e}") from e
