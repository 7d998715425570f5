"""Continuous-batching inference engine.

Replaces the reference's external Ollama daemon (SURVEY.md §0: the entire
compute path was `client/src/services/OllamaService.ts` HTTP calls). Design
(SURVEY.md §7 steps 4-5):

- One static device state: paged KV pool shared by `max_slots` concurrent
  requests, per-slot sampler params, per-slot context token counts. All
  compiled functions are shape-static; a prompt's chunks pad to one of
  the chunk program's widths (without a mixed step: to the smallest bucket).
- Continuous batching: requests join/leave the batch between decode steps
  (the reference capped workers at 1 job, server/src/config/index.ts:31 —
  here concurrency is a device-state property, not a scheduler constant).
- Decode runs in BLOCKS of `decode_block` fused steps (lax.scan of
  model step + sampler + bookkeeping inside ONE jit call), with up to
  `pipeline_depth` blocks dispatched ahead of the host. Round-3's 76 tok/s
  was dominated by per-step host round-trips (~60-150 ms each over the
  device transport vs ~11 ms of device compute); blocks amortize the fetch
  and the pipeline hides it entirely in steady state. Host-side bookkeeping
  (EOS, stop sequences, num_predict) lags the device by up to
  decode_block × pipeline_depth wasted steps per finishing stream — pure
  compute waste, never a correctness hazard: page-table sentinels drop
  out-of-capacity writes and fetched post-finish tokens are discarded.
- Admission never synchronizes: the prefill (the chunk region of a mixed
  step where the family has one, so running streams decode in the same
  launch) samples the first token on device and folds it into the step
  state; the host first sees it in the mixed launch's own block, beside
  the decode rows, or after a prefill launch of its own in the NEXT
  block's row 0 (blocks return [K+1, S] — input tokens + K sampled),
  matched by a per-slot dispatch-generation tag.
- Speculative decoding (ISSUE 5, default on via GRIDLLM_SPEC_DECODE):
  the host drafts up to GRIDLLM_SPEC_K candidate tokens per slot
  (prompt-lookup n-gram, ops/spec.py), ONE batched verify forward
  (mod.verify_step) scores the whole [S, K+1] candidate block against
  the paged prefix, and the accept/reject kernel (ops/sampling.py
  spec_accept) keeps the longest accepted prefix + one corrected token
  — 1..K+1 tokens per step, greedy streams byte-identical to spec-off,
  sampled streams exactly rejection-sampled. Candidate KV is written
  optimistically and rolled back by length (ops/kvcache.py). While
  drafts are being accepted the spec path fetches every verify step (the
  next draft depends on this step's tokens), trading the block pipeline
  for multi-token steps — the win when the model forward dominates step
  time and the workload repeats. While none is, a launch emits one token
  a slot and needs nothing from the host, so the runner keeps draftless
  verify launches (the same program) in flight by the decode pipeline's
  rules (_step_spec has the rule that decides).
- Ollama semantics honored at this layer: sampler option surface (via
  ops/sampling), `seed` determinism per request (unseeded requests draw a
  random seed host-side — seed 0 is NOT a fixed default), real timing
  fields in nanoseconds (the reference zeroed them, SURVEY.md §2.8),
  `stop` sequences, `num_predict`, EOS from the tokenizer.

repeat_penalty follows llama.cpp's penalty_last_n semantics: it applies
over the last `repeat_last_n` context tokens (prompt + generated; -1 →
the request's context size, 0 → disabled), maintained as a device-side
window buffer (ops/sampling.py) capped at EngineConfig.repeat_window.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import random
import threading
import time
from collections import deque
from functools import partial
from statistics import median
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from gridllm_tpu import faults
from gridllm_tpu.engine.tokenizer import DetokState, Tokenizer, get_tokenizer
from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import ModelConfig, get_config
from gridllm_tpu.obs import SIZE_BUCKETS, default_flight_recorder, default_registry
from gridllm_tpu.obs.perf import (
    ADMIT_WAIT_SECONDS,
    MOE_EXPERT_ROWS_TOTAL,
    MOE_EXPERTS_TOUCHED_TOTAL,
    MOE_FORM_ROWS_TOTAL,
    MOE_PICKS_TOTAL,
    VERIFY_CTX_TOKENS_TOTAL,
    VERIFY_WINDOW_TOKENS_TOTAL,
    XLA_COMPILE_SECONDS,
    PhaseClock,
    RecompileTripwire,
    capture_span,
    compile_owner,
)
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    PageAllocator,
    QuantPages,
    commit_tree_path,
    rollback_to_length,
)
from gridllm_tpu.ops.kvtier import set_tier_gauges
from gridllm_tpu.ops.sampling import (
    ROW_LEN,
    SamplingParams,
    sample_tokens,
    spec_accept,
    spec_accept_tree,
    topk_stages,
    window_push,
    window_set_slot,
)
from gridllm_tpu.ops.spec import (
    DraftModelDrafter,
    make_drafter,
    tree_ancestor_mask,
    tree_depths,
    tree_topology,
)
from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh
from gridllm_tpu.parallel.sharding import param_shardings, shard_params
from gridllm_tpu.utils.config import (
    compile_cache_dir,
    env_bool,
    env_int,
    env_str,
)
from gridllm_tpu.utils.logging import get_logger

log = get_logger("engine")

# Engine-plane instruments (process-global registry → the worker's
# /metrics). Updated from the runner thread / step() only, so the metric
# locks are uncontended on the hot path.
_OBS = default_registry()
_TOKENS_TOTAL = _OBS.counter(
    "gridllm_engine_tokens_total",
    "Tokens processed, by model and kind (prefill = prompt tokens "
    "dispatched, decode = tokens sampled and ingested).",
    ("model", "kind"),
)
_STEP_DURATION = _OBS.histogram(
    "gridllm_engine_step_duration_seconds",
    "Per-decode-step wall time (fused-block fetch time divided by the "
    "block's step count), by model.",
    ("model",),
)
_BATCH_OCCUPANCY = _OBS.histogram(
    "gridllm_engine_batch_occupancy",
    "Active slots at each decode-block dispatch, by model.",
    ("model",), buckets=SIZE_BUCKETS,
)
_KV_PAGES_USED = _OBS.gauge(
    "gridllm_engine_kv_pages_used", "KV page-pool pages in use, by model.",
    ("model",),
)
_KV_PAGES_FREE = _OBS.gauge(
    "gridllm_engine_kv_pages_free", "KV page-pool pages free, by model.",
    ("model",),
)
_KV_PAGES_CACHED = _OBS.gauge(
    "gridllm_engine_kv_pages_cached",
    "KV page-pool pages parked in the prefix-cache reuse LRU (refcount 0, "
    "evictable), by model.",
    ("model",),
)
_PREFIX_HIT_RATE = _OBS.gauge(
    "gridllm_prefix_cache_hit_rate",
    "Cumulative prompt-page prefix-cache hit rate (hits / (hits+misses)), "
    "by model.",
    ("model",),
)
# chunked prefill: how often each width of the chunk program runs, and how
# much of what it computes is prompt (real over padded = the fill)
_CHUNK_LAUNCHES = _OBS.counter(
    "gridllm_engine_chunk_launches_total",
    "Chunked-prefill launches (mixed_chunk / prefill_chunk), by model and "
    "the token width the chunk was padded to.",
    ("model", "width"),
)
_CHUNK_TOKENS = _OBS.counter(
    "gridllm_engine_chunk_tokens_total",
    "Tokens through chunked-prefill launches, by model and kind (real = "
    "prompt tokens, padded = the launches' widths, padding included).",
    ("model", "kind"),
)
# the jitted calls of dispatch_prefill's `seed` stage (beside its seconds
# in gridllm_engine_stage_seconds): 1-2 an admission whatever is cached
_SEED_LAUNCHES = _OBS.counter(
    "gridllm_engine_seed_launches_total",
    "Jitted calls of an admission's seed stage (the sampler row and the "
    "repeat-penalty window's tail in one program, and a state restore "
    "where the family has a second-kind cache), by model.",
    ("model",),
)
_KV_ROW_BYTES = _OBS.gauge(
    "gridllm_kv_row_bytes",
    "Bytes of one token's row in one layer of the page pool as stored "
    "(lane padding included), by model and kind: latent (one row shared "
    "by every head) or kv (K and V per KV head). Set at pool creation.",
    ("model", "kind"),
)
_STATE_BYTES = _OBS.gauge(
    "gridllm_state_bytes",
    "Device bytes of a hybrid family's recurrent state, by model and kind: "
    "slot (every slot's state, convolution rows and pending rows) or "
    "snapshot (the prefix cache's snapshot pool). Set at pool creation.",
    ("model", "kind"),
)
_STATE_SNAP_USED = _OBS.gauge(
    "gridllm_state_snapshot_pool_used",
    "Entries of the recurrent-state snapshot pool that hold a snapshot.",
    ("model",),
)
_STATE_SNAP_CAPACITY = _OBS.gauge(
    "gridllm_state_snapshot_pool_capacity",
    "Entries the recurrent-state snapshot pool has.",
    ("model",),
)
_WINDOW_ROWS = _OBS.gauge(
    "gridllm_window_rows",
    "Rows the live slots' window layers hold, a layer: ring (the rings "
    "they do hold: a window, a launch's rows and a page a slot) or table "
    "(what the same slots' pages of one table would hold).",
    ("model", "held"),
)
_SAMPLER_TOPK_STAGES = _OBS.gauge(
    "gridllm_sampler_topk_stages",
    "How many top_k the sampler's top-128 candidates take at this model's "
    "vocabulary: 1 (one pass over the vocabulary) or 3 (the maxima of "
    "128-wide blocks, the winning blocks' maxima by blocks of 16, the "
    "2,048 values left; exact). Set where the programs are built.",
    ("model",),
)
_KV_ROW_BYTES_EQUIV = _OBS.gauge(
    "gridllm_kv_row_bytes_per_head_equiv",
    "Bytes the same row would take stored as K and V per head at the "
    "model's own head sizes, unpadded; equals gridllm_kv_row_bytes for a "
    "family that stores K and V per head unpadded.",
    ("model",),
)
# elastic serving (ISSUE 20): cold-start cost, by how the weights arrived
# — "snapshot" (host-RAM weight tier hit), "checkpoint" (safetensors
# re-read), "init" (fresh random init). The ModelColdStartSlow alert keys
# on this series: snapshot restores taking checkpoint-class time mean the
# tier is thrashing or the host is paging.
_MODEL_LOAD_SECONDS = _OBS.histogram(
    "gridllm_model_load_seconds",
    "Engine weight-load wall time at (re)construction, by model and "
    "weight source (snapshot = host-RAM tier hit, checkpoint = disk "
    "safetensors, init = fresh init).",
    ("model", "source"),
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0),
)

# Persistent XLA compilation cache: a restarted worker, or a swapped-in
# model, replays its compiles from disk instead of re-running XLA — the
# compile half of a fast cold start. The directory is part of the cache
# key, so it must not move: utils.config.compile_cache_dir names the one
# in force.


def ensure_compile_cache() -> None:
    """Point jax at the compile cache (idempotent, process-global). A
    directory jax already holds stands — from JAX_COMPILATION_CACHE_DIR
    in the environment, or set earlier in this process — so two
    constructions never produce two paths."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
        log.info("persistent compile cache enabled",
                 dir=jax.config.jax_compilation_cache_dir)
    # cache the sub-second programs too: a worker's start-up is dozens of
    # them, and tiny-model CPU runs would otherwise never exercise the
    # persistent path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# speculative decoding (ISSUE 5): draft-token accounting. proposed =
# drafts sent to a verify step, accepted = drafts the model agreed with,
# rejected = proposed - accepted (a draft discarded because an EARLIER one
# missed counts as rejected too — it was wasted verify work either way).
# The per-step histogram is the acceptance-collapse signal: spec on with
# rate ≈ 0 means drafting is pure overhead (prometheus alert). The
# "drafter" label (ISSUE 18) splits the series by drafting backend —
# "ngram" (prompt-lookup) vs "model" (draft-model tree) — so an A/B or a
# collapse localizes to the backend that caused it.
_SPEC_PROPOSED = _OBS.counter(
    "gridllm_spec_proposed_tokens_total",
    "Draft tokens proposed to speculative verify steps, by model and "
    "drafter kind.",
    ("model", "drafter"),
)
_SPEC_LOOKUPS = _OBS.counter(
    "gridllm_spec_draft_lookups_total",
    "N-gram drafter lookups, one per live slot per verify step, by model "
    "and outcome (hit: the history's suffix recurred and tokens were "
    "proposed; miss: nothing to propose).",
    ("model", "outcome"),
)
_SPEC_LAUNCHES = _OBS.counter(
    "gridllm_spec_launches_total",
    "Verify launches of the chain drafter's path, by model and mode "
    "(serial: drafted for, then fetched before anything else was "
    "dispatched; ahead: dispatched without drafts on the pipelined "
    "schedule, up to pipeline_depth in flight, each fetched while a later "
    "one runs).",
    ("model", "mode"),
)
_SPEC_ACCEPTED = _OBS.counter(
    "gridllm_spec_accepted_tokens_total",
    "Draft tokens accepted by speculative verify steps, by model and "
    "drafter kind.",
    ("model", "drafter"),
)
_SPEC_REJECTED = _OBS.counter(
    "gridllm_spec_rejected_tokens_total",
    "Draft tokens rejected (or discarded past the first miss) by "
    "speculative verify steps, by model and drafter kind.",
    ("model", "drafter"),
)
_SPEC_ACCEPT_RATE = _OBS.histogram(
    "gridllm_spec_acceptance_rate",
    "Per-verify-step draft acceptance rate (accepted/proposed, over steps "
    "with at least one proposed draft), by model and drafter kind.",
    ("model", "drafter"), buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
)
# flight recorder (obs/flightrec.py): lifecycle events land in the "engine"
# ring; block dispatches are SAMPLED (one record per _FLIGHT_SAMPLE
# generations) so the hot loop stays a deque append every few dozen steps
_FLIGHTREC = default_flight_recorder()
_FLIGHT_SAMPLE = 16

# Verify launches in a row in which no slot's first proposed token was the
# token the model then emitted, after which the speculative runner stops
# fetching each launch before it dispatches the next (_step_spec). Long
# enough that traffic whose drafts are accepted now and then never gets
# there, short against a run of thousands of launches that accepts none.
# Observed, not set: no option reaches it.
_AHEAD_AFTER = 32

# KV pool sizing (EngineConfig.num_pages = None). 1024 pages is every
# slot of the worker's defaults at full context (8 slots x 128 pages);
# more would only deepen the prefix cache.
DEFAULT_NUM_PAGES = 1024
# Device memory kept out of the pool for what XLA allocates per program
# beside weights and KV: activations of a 1024-token prefill chunk, the
# [slots, K+1, vocab] verify logits and the sampler's sorts over them,
# the draft model's pool.
WORKSPACE_RESERVE_BYTES = 2 << 30


def _device_memory_stats(device) -> dict[str, int]:
    """The allocator's statistics for one device ({} where the backend
    keeps none: CPU). A seam: tests size pools against a faked limit."""
    return device.memory_stats() or {}


# Every engine with a mixed step admits every prompt through it: the chunk
# and one decode row for every running stream share a launch, where a
# prefill of its own stalls the streams for a launch and a half (PERF.md,
# PRs 33 and 39). What differs by family is the widths of that launch.
#
# A routed family's one chunk width (where EngineConfig.prefill_chunk is
# wider). Its launch read every expert it holds whatever rows it carried,
# so a chunk that took about a verify launch's period (35 ms at 512 rows
# beside 34 on the v5e; PERF.md, PR 33) cost the streams least. That
# reading is STALE: since PRs 53 and 58 both launches read the experts
# their rows pick (a 528-row launch 18-24 ms beside a verify launch of
# 7-8: PERF.md, PR 58), and the width has not been read again (ROADMAP
# S4 b). One width: a narrower last chunk would put a step in the time
# to the first token at the prompt length it starts from
ROUTED_CHUNK = 512
# A dense family's width for the FIRST chunk of an uncached prompt where
# it holds the whole prompt (else the full chunk): what the smallest
# prefill bucket was, inside the mixed program. Not narrower: the median
# chat prompt is 257 tokens, and a width boundary inside the bulk of a
# mix's prompt lengths puts two modes under the median time to the first
# token (PERF.md, PR 33 finding 2). Behind a prefix the last chunk keeps
# EngineConfig.prefill_chunk_narrow
FIRST_CHUNK = 512


# the form a launch reads each kind of cache in (ModelConfig.cache_kinds)
_ATTN_FORMS = {"kv": "per_head", "latent": "absorbed", "state": "delta",
               "window": "ring"}
# a hybrid family's snapshot pool where the engine sizes it: this share of
# what the device has left after the weights, the reserve and the slots'
# state (the rest is pages), and this many entries a slot where there is
# no device to size against (CPU)
SNAPSHOT_SHARE = 0.3
SNAPSHOTS_PER_SLOT = 4


def _host_i32(values: list[int], size: int) -> np.ndarray:
    """`values` zero-padded to `size` as a host int32 buffer — what a
    jitted call takes for a token chunk or a page-table row. Passed
    straight in, it is transferred by the call's argument path; wrapped
    in jnp.asarray it would be a device program of its own."""
    buf = np.zeros((size,), np.int32)
    buf[:len(values)] = values
    return buf


# Admission's host record (admit_seed_fn's one host argument): the slot,
# the tail's length, the sampler row (SamplingParams.pack_row), the tail.
# _seed_record writes it and _seed_fields reads it, in the program
_SEED_TAIL = 2 + ROW_LEN


def _seed_record(slot: int, upd: dict[str, Any], tail: list[int],
                 width: int) -> np.ndarray:
    """ONE host int32 array of the slot, the sampler values `upd` and
    `tail`, the last tokens of the prefix-cached span, zero-padded to
    `width` (repeat_window). One array, not five: each host argument of a
    jitted call is a transfer of its own."""
    rec = np.zeros((_SEED_TAIL + width,), np.int32)
    rec[0], rec[1] = slot, len(tail)
    rec[2:_SEED_TAIL] = SamplingParams.pack_row(upd)
    rec[_SEED_TAIL:_SEED_TAIL + len(tail)] = tail
    return rec


def _seed_fields(rec: jnp.ndarray):
    """(slot, tail length, sampler row, padded tail) of a _seed_record."""
    return rec[0], rec[1], rec[2:_SEED_TAIL], rec[_SEED_TAIL:]


def _model_module(cfg: ModelConfig):
    if cfg.family in ("mixtral", "smallthinker"):
        # one routed-experts module: the two differ in data (activation,
        # the router's tap, the layer pattern), not in code
        from gridllm_tpu.models import mixtral

        return mixtral
    if cfg.family == "deepseek_v2":
        from gridllm_tpu.models import deepseek

        return deepseek
    if cfg.family == "olmo_hybrid":
        from gridllm_tpu.models import olmo_hybrid

        return olmo_hybrid
    if cfg.family == "laguna":
        from gridllm_tpu.models import laguna

        return laguna
    if cfg.family == "kimi_linear":
        from gridllm_tpu.models import kimi_linear

        return kimi_linear
    if cfg.family == "longcat_flash":
        from gridllm_tpu.models import longcat_flash

        return longcat_flash
    if cfg.family == "granite_hybrid":
        from gridllm_tpu.models import granite_hybrid

        return granite_hybrid
    if cfg.family == "bert_embed":
        from gridllm_tpu.models import bert_embed

        return bert_embed
    if cfg.family == "llava":
        from gridllm_tpu.models import llava

        return llava
    if cfg.family == "gemma2":
        from gridllm_tpu.models import gemma

        return gemma
    return llama  # llama, qwen2, qwen3 share the decoder skeleton


@dataclasses.dataclass
class EngineConfig:
    model: str
    checkpoint_path: str | None = None   # None → random init (tests/synthetic bench)
    tokenizer: str | None = None         # None/"byte" → ByteTokenizer
    dtype: str = "bfloat16"
    # "int8" → per-out-channel weight-only quantization of the matmul
    # leaves (ops/quant.py). Halves weight HBM + decode bandwidth; the
    # only way llama3:70b fits a v5e-8 slice (BASELINE config #3).
    quantize: str | None = None
    max_slots: int = 8
    page_size: int = 64
    # KV pool pages. None → the engine sizes the pool at construction:
    # DEFAULT_NUM_PAGES, or what the device's free memory holds after
    # the weights and WORKSPACE_RESERVE_BYTES when that is fewer. A
    # number is taken as given and must fit the same budget. A backend
    # that reports no memory statistics (CPU) is never sized against.
    num_pages: int | None = None
    max_pages_per_slot: int = 128
    prefill_buckets: tuple[int, ...] = (64, 256, 1024, 4096)
    mesh: MeshConfig | None = None       # None → no mesh (single device)
    max_queue: int = 512
    seed: int | None = None              # engine-level seed for unseeded reqs
    embed_batch: int = 32                # max texts per embedding forward
    # prompts longer than this prefill in fixed-size chunks against the
    # cached prefix (ONE compiled chunk program for all lengths) instead of
    # padding to the next bucket; rounded down to a multiple of page_size
    # (the in-place page-write kernel requires page-aligned chunk starts)
    prefill_chunk: int = 1024
    # the narrow width of a dense family's chunk program: a prompt's LAST
    # chunk BEHIND A PREFIX (a long prompt's tail, or the fresh tail of a
    # prefix-cache hit) is padded to it when it fits, to prefill_chunk
    # otherwise. A cached re-ask is a few dozen to 192 fresh tokens; a
    # 256-wide launch costs a third of a 1024-wide one (PERF.md, PR 32).
    # A prompt's first chunk has its own width (FIRST_CHUNK); a routed
    # family has one width for all (ROUTED_CHUNK). Each width is a
    # model-sized program, so no ladder; page-aligned like prefill_chunk,
    # and without effect where prefill_chunk is no wider
    prefill_chunk_narrow: int = 256
    # decode steps fused per dispatch in the runner loop (step() always
    # uses 1 — exact per-token semantics for tests/sync callers)
    decode_block: int = 8
    # blocks dispatched ahead of the host fetch (2 = fetch block N while
    # block N+1 computes; enough to hide the transfer latency)
    pipeline_depth: int = 2
    # prefills admitted per block boundary while other streams are running
    # (idle engines admit everything; bounding protects running streams'
    # inter-token latency from admission bursts — VERDICT r03 #3)
    admit_per_block: int = 2
    # static width of the per-slot repeat-penalty window buffer;
    # repeat_last_n (and its -1 → num_ctx resolution) clamps to this
    repeat_window: int = 256
    # automatic prefix caching (ISSUE 3): completed requests park their
    # full KV pages in a content-addressed reuse LRU; new requests skip
    # prefill over their longest cached prefix. None → env
    # GRIDLLM_PREFIX_CACHE (default on; "0" disables — bit-identical to
    # the pre-cache engine). prefix_cache_pages bounds the LRU (None →
    # GRIDLLM_PREFIX_CACHE_PAGES, default unbounded; 0 disables — same
    # semantics as PageAllocator.cache_pages; negative → unbounded).
    prefix_cache: bool | None = None
    prefix_cache_pages: int | None = None
    # speculative decoding (ISSUE 5): n-gram (prompt-lookup) drafting +
    # batched K-token verification. None → env GRIDLLM_SPEC_DECODE
    # (default on; "0" disables — exact legacy decode path). spec_k is the
    # speculation depth K (drafted tokens verified per step; the verify
    # block is [S, K+1] — candidates plus the committed last token), FIXED
    # per process so every shape stays static and the recompile tripwire
    # stays green. None → GRIDLLM_SPEC_K (default 4); 0 also disables.
    # Greedy streams are byte-identical spec-on vs spec-off; sampled
    # streams keep the target distribution via rejection sampling
    # (ops/sampling.py spec_accept).
    spec_decode: bool | None = None
    spec_k: int | None = None
    # draft-model + tree speculation (ISSUE 18). draft_model names a
    # registered config for a tiny SAME-TOKENIZER draft model loaded next
    # to the target (sharing the device mesh) — "" keeps n-gram drafting.
    # None → GRIDLLM_SPEC_DRAFT_MODEL. draft_checkpoint is its weight
    # path ("" → fresh init, the tier-1/bench path); None →
    # GRIDLLM_SPEC_DRAFT_CHECKPOINT. With a draft model active the verify
    # block generalizes from the [S, K+1] chain to a static token TREE:
    # a depth-K greedy chain plus (spec_tree_width - 1) first-level
    # sibling alternatives, verified in one tree-masked forward
    # (ops/spec.py tree_topology). width 1 = pure chain; None →
    # GRIDLLM_SPEC_TREE_WIDTH (default 2). An incompatible draft model
    # (vocab mismatch, no verify/decode path) logs and falls back to
    # n-gram rather than failing the engine.
    draft_model: str | None = None
    draft_checkpoint: str | None = None
    spec_tree_width: int | None = None
    # tiered KV cache (ISSUE 11). kv_host_bytes: host-RAM tier capacity —
    # prefix-cache pages evicted from HBM spill there (wire-codec encoded)
    # and page back in on match_prefix hits; the capacity IS the enable
    # (0 = off). None → GRIDLLM_KV_HOST_BYTES. kv_spill_int8: int8-
    # quantize fp pages on spill (scale-per-page; halves host bytes) —
    # 0 spills raw bytes so tier-on streams stay byte-identical to
    # tier-off. None → GRIDLLM_KV_SPILL_INT8 (default on). kv_int8:
    # resident int8 KV pool (QuantPages — per-row scales, dequant
    # epilogue in the attention read path), halving KV HBM. None →
    # GRIDLLM_KV_INT8 (default off). Single-device pools only: meshes
    # keep the fp layout.
    kv_host_bytes: int | None = None
    kv_spill_int8: bool | None = None
    kv_int8: bool | None = None


@dataclasses.dataclass
class GenerationRequest:
    id: str
    prompt: str | None = None
    prompt_ids: list[int] | None = None  # pre-tokenized (Ollama `context` path)
    options: dict[str, Any] = dataclasses.field(default_factory=dict)
    raw: bool = False                    # skip BOS when prompt_ids is None
    images: list[str] | None = None      # base64 images (vision models only)
    # disaggregated prefill (ISSUE 7): finish at the FIRST host-visible
    # token with done_reason "export" — the prompt's KV pages land in the
    # prefix cache (free+register, exactly the normal finish path) ready
    # for export_prefix_pages; no text is detokenized or streamed
    export_only: bool = False
    # decode resume (ISSUE 9): token ids a previous attempt already
    # generated. They are appended to the prompt for prefill/alloc (so a
    # cached/migrated prefix makes resume cheap) but seeded into the
    # slot's GENERATED state — detok/stop/num_predict/eval_count all
    # continue exactly where the lost worker left off, and the sampler's
    # (seed, step) chain restarts at step = len(resume_ids), so a greedy
    # or seeded stream is byte-identical to the undisturbed run.
    resume_ids: list[int] | None = None
    # chars of the resumed text already delivered downstream: emission
    # restarts past this offset, so clients never see a duplicate char
    resume_sent: int = 0
    # write the (generated ids, text) resume watermark every N surviving
    # tokens (0 = never): each write copies the full generated list, so
    # an every-token cadence would be O(n^2) on the engine hot loop
    snapshot_every: int = 0
    # called from the engine loop: (text_delta, done, result|None)
    on_chunk: Callable[[str, bool, "GenerationResult | None"], None] | None = None
    # perf_counter_ns at submit(): the engine's stamp, for admit_wait
    t_submit_ns: int = 0


@dataclasses.dataclass
class GenerationResult:
    id: str
    text: str = ""
    token_ids: list[int] = dataclasses.field(default_factory=list)
    context: list[int] = dataclasses.field(default_factory=list)
    done_reason: str = "stop"
    prompt_eval_count: int = 0
    # prompt tokens served from the prefix cache (prefill skipped); always
    # ≤ prompt_eval_count, 0 with caching off
    cached_tokens: int = 0
    prompt_eval_duration_ns: int = 0
    # submit() → popped for admission: the wait in the engine's pending
    # queue, which ends where prompt_eval_duration_ns begins
    admit_wait_ns: int = 0
    eval_count: int = 0
    eval_duration_ns: int = 0
    load_duration_ns: int = 0
    total_duration_ns: int = 0
    # speculative decoding (ISSUE 5): drafts proposed/accepted for this
    # request's verify steps (both 0 with speculation off)
    spec_proposed: int = 0
    spec_accepted: int = 0
    # usage attribution (ISSUE 16): device-seconds this request's share of
    # decode steps consumed, and KV page-occupancy (pages held × resident
    # wall seconds) — the raw cost signals behind gridllm_usage_*
    decode_device_s: float = 0.0
    kv_page_s: float = 0.0
    retryable: bool = True  # meaningful when done_reason == "error"
    # when done_reason == "error": the failure message. `text` stays the
    # partial output actually generated, so a streaming client's concatenated
    # deltas always equal `text` (they must never be retroactively replaced
    # by an error string).
    error: str = ""


class _Slot:
    __slots__ = (
        "req", "ids", "prompt_len", "generated", "detok", "text", "emitted_len",
        "num_predict", "stop_seqs", "eos_ids", "capacity", "joined_gen",
        "first_row", "cached_tokens", "spec_proposed", "spec_accepted",
        "shadow", "export_only", "snapshot",
        "t_start", "t_prefill_ns", "t_first_decode", "t_last_ingest",
        "t_admit_wall", "pages_held", "device_s", "admit_wait_ns",
    )

    def __init__(self, req: GenerationRequest, ids: list[int], capacity: int,
                 num_predict: int, stop_seqs: list[str], eos_ids: frozenset[int]):
        self.req = req
        self.ids = ids                   # prompt ids (grows with generation)
        self.prompt_len = len(ids)
        self.generated: list[int] = []
        self.detok = DetokState()
        self.text = ""
        self.emitted_len = 0             # chars of `text` already sent out
        self.num_predict = num_predict
        self.stop_seqs = stop_seqs
        self.eos_ids = eos_ids
        self.capacity = capacity         # max total tokens this slot may hold
        self.cached_tokens = 0           # prompt tokens reused from the prefix cache
        self.spec_proposed = 0           # drafts sent to verify steps
        self.spec_accepted = 0           # drafts the model accepted
        # the drafter's first proposal for this stream's next token where
        # the runner ran ahead and verified none (-1: none), held against
        # that token when it is ingested
        self.shadow = -1
        self.export_only = req.export_only  # disagg prefill: stop at token 1
        # last consistent (generated ids, text) pair, published as the
        # crash-resume watermark (ISSUE 9). Written only by the engine
        # thread as ONE immutable tuple per surviving token, so a reader
        # on another thread always sees a matched pair.
        self.snapshot: tuple[list[int], str] | None = None
        # dispatch generation of the FIRST block that carries a token of
        # this slot, and the row of it that holds the prefill-sampled one:
        # row 0 (block-input tokens) of the block dispatched after a prefill
        # launch of its own, row 1 (the decode rows' row) of the block of
        # the mixed launch that ran the prompt's last chunk; blocks with a
        # lower generation predate the slot (or belong to the slot's
        # previous occupant) and are skipped for it
        self.joined_gen = 0
        self.first_row = 0
        self.t_start = time.perf_counter_ns()
        self.t_prefill_ns = 0
        self.t_first_decode = 0
        self.t_last_ingest = 0.0  # epoch seconds of last host-visible token
        # usage attribution (ISSUE 16)
        self.t_admit_wall = time.time()  # wall clock at admission
        self.pages_held = 0              # KV pages allocated to this slot
        self.device_s = 0.0              # accumulated decode device-second share
        self.admit_wait_ns = 0           # submit → popped for admission

    def holdback(self) -> int:
        """Chars at the tail of `text` that could still become a stop
        sequence (longest proper-prefix match) — must not be emitted yet."""
        hold = 0
        for seq in self.stop_seqs:
            for k in range(min(len(seq), len(self.text)), 0, -1):
                if self.text.endswith(seq[:k]):
                    hold = max(hold, k)
                    break
        return hold


class InferenceEngine:
    """Synchronous core; drive with step() (tests) or the worker's async
    facade (worker/service.py wraps step() in a thread executor)."""

    def __init__(self, config: EngineConfig):
        ensure_compile_cache()
        self.config = config
        try:
            self.cfg = get_config(config.model)
        except KeyError:
            if not config.checkpoint_path:
                raise
            # unregistered name + checkpoint dir → read the HF config.json
            # (serve any local HF-layout checkpoint, no registry edit needed)
            from gridllm_tpu.models.configs import config_from_hf_dir

            self.cfg = config_from_hf_dir(config.model, config.checkpoint_path)
        if self.cfg.vocab_held:
            # a chip's slice of the vocabulary is a smaller vocabulary:
            # tokenizer, logits and sampling are over the held rows
            self.cfg = dataclasses.replace(
                self.cfg, vocab_size=self.cfg.vocab_held)
        self.mod = _model_module(self.cfg)
        # each layer's sliding window (none: it reads the whole context),
        # and how many layers have one
        self._layer_windows = np.asarray(
            [w or np.inf for w in self.cfg.layer_windows])
        self._windowed = int(np.isfinite(self._layer_windows).sum())
        kinds = self.cfg.cache_kinds
        # a second kind of cache beside the pages: a recurrent state a slot
        self._hybrid = "state" in kinds
        # or a ring of the window layers' last rows a slot; either way the
        # prefix cache keeps snapshots of it beside the pages (`_second`)
        self._ring = "window" in kinds
        self._second = self._hybrid or self._ring
        self._expert_forms: dict[int, str] = {}   # _expert_meta's memo
        self.embedding_only = self.cfg.family == "bert_embed"
        self.tokenizer: Tokenizer = get_tokenizer(
            config.tokenizer, self.cfg.vocab_size
        )
        self.mesh = build_mesh(config.mesh) if config.mesh else None
        # the mesh as GRIDLLM_MESH_SHAPE writes it ("tp:4"; "" unmeshed):
        # for log records and batch_state's shape
        self.mesh_axes = "" if self.mesh is None else ",".join(
            f"{a}:{n}" for a, n in self.mesh.shape.items() if n > 1)
        # family-specific mesh constraints fail HERE (engine startup), not
        # at the first request's trace (e.g. gemma2 has no sp variant)
        getattr(self.mod, "validate_mesh", lambda *_: None)(self.cfg, self.mesh)
        if self.mesh is not None and self.mesh.shape.get("pp", 1) > 1:
            # tp/dp/ep/sp meshes run the kernels inside a full-manual
            # shard_map at the kernel boundary (ops.kvcache.kernel_mesh_axis
            # — kv-heads split over tp, VERDICT r04 #2). The pipeline's
            # partial-manual pp region is the remaining exception: it pins
            # the jnp paths. Per-engine (on the cfg copy) so co-hosted
            # single-device engines keep their kernels.
            self.cfg = dataclasses.replace(self.cfg, use_pallas=False)
        self._rng = random.Random(config.seed)
        # prefix-cache capacity, resolved ONCE (env reads at startup, not
        # per admission): 0 = off, < 0 = unbounded reuse LRU, > 0 = cap.
        # sp > 1 prefills whole prompts via ring attention — there is no
        # chunked path to start mid-prompt from, so caching is off there.
        sp_prefill = self.mesh is not None and self.mesh.shape.get("sp", 1) > 1
        self._prefix_cache_cap = (
            0 if sp_prefill else self._resolve_prefix_cache_cap()
        )
        # tiered KV cache (ISSUE 11), both knobs resolved ONCE at startup:
        # the pool layout depends on kv_int8, and the host tier outlives
        # device-state resets (content-addressed pages stay valid)
        self._kv_int8 = self._resolve_kv_int8()
        self.host_tier = self._build_host_tier()
        self._lock = threading.Lock()
        # allocator guard (ISSUE 7): page allocation/free runs on the
        # driving thread (admission/finish), while KV export/import runs
        # on the worker's executor threads — both mutate PageAllocator
        # state, so every allocator mutation sits under this lock. Lock
        # order where both are held: _alloc_lock BEFORE dispatch_lock.
        self._alloc_lock = threading.RLock()
        self._kv_install_fn: Callable | None = None  # lazy (ISSUE 7 import)
        self._pending: deque[GenerationRequest] = deque()
        self._slots: dict[int, _Slot] = {}
        self._free_slots = list(range(config.max_slots - 1, -1, -1))
        # dispatch pipeline state (runner thread / step()):
        self._gen = 0                     # generation counter of dispatched blocks
        # (gen, what the launch handed back, its fused steps, a verify
        # launch's per-slot draft counts or None): decode blocks, mixed
        # launches and verify launches in dispatch order (_fetch_oldest)
        self._inflight: deque[
            tuple[int, Any, int, np.ndarray | None]] = deque()
        # recompile tripwire (obs/perf.py): every jitted entry point is
        # wrapped; armed after the first naturally completed request, at
        # which point any new compile signature is a flagged steady-state
        # recompile (counter + flight-recorder event with the shapes)
        self.perf = RecompileTripwire(context=self.cfg.name)
        self._perf_armed = False
        # speculative decoding (ISSUE 5): depth resolved in _build_fns
        # (it needs the resolved family module); 0 = off. spec_stats are
        # cumulative host-side totals (bench + batch_state read them).
        self._spec_k = 0
        self._drafter = None
        self._tree_width = 1
        self.spec_stats = {"steps": 0, "proposed": 0, "accepted": 0,
                           "emitted": 0, "draft_ns": 0}
        # first proposals that matched since the last verify ingest, and
        # the verify launches in a row that ended with none (_AHEAD_AFTER)
        self._spec_hits = 0
        self._spec_quiet = 0
        # the runner's last eight of each, in seconds: its wait at a verify
        # launch's fetch (and whether another launch was in flight behind
        # it: the runner ahead of the device), an iteration's host work
        # (what the phase clock closed of ingest, draft and the launch call
        # since the iteration before: _step_spec reads it, and an
        # admission, a fetch or an idle wait is another phase's), and an
        # admission's host time (pop to dispatched). _step_spec compares
        # their medians: a median, because an admission that compiled a
        # program is no admission's measure
        self._fetch_waits: deque[tuple[float, bool]] = deque(maxlen=8)
        self._host_works: deque[float] = deque(maxlen=8)
        self._host_spent = 0.0      # the clock's reading an iteration ago
        self._admits: deque[float] = deque(maxlen=8)
        # the runner thread's wall time, phase by phase (obs/perf.py);
        # runner_wall_s is the same stretch measured on its own, _run
        # entry to exit, so a test can hold the phases to it
        self._clock = PhaseClock(self.cfg.name)
        self.runner_wall_s = 0.0
        # cross-thread control requests: ("cancel" | "suspend", req_id)
        self._ctl: deque[tuple[str, str]] = deque()
        self._work = threading.Condition()
        self._runner: threading.Thread | None = None
        self._runner_stop = threading.Event()
        # Multi-host SPMD (SURVEY §5.8b): in a worker group every process
        # must issue the SAME jitted computations in the same order or the
        # first cross-host collective deadlocks. The liaison's engine
        # emits one record per device-dispatching action (admit / block /
        # deact / reset) through `plan_sink`; follower engines replay them
        # via apply_plan_op. All record payloads are plain host data
        # (token ids, page rows, resolved sampler values incl. the seed),
        # so replay is bit-identical. `dispatch_lock` makes (emission,
        # dispatch) atomic; worker/main.py shares ONE lock across all of a
        # slice's engines so the liaison's cross-engine dispatch order
        # equals the plan order followers replay (embed dispatches from
        # the executor thread serialize through it too).
        self.plan_sink: Callable[[dict[str, Any]], None] | None = None
        self.dispatch_lock: threading.RLock = threading.RLock()
        self.prewarm_duration_ns = 0
        with compile_owner(self.cfg.name):
            self._load()
            self._build_fns()

    # ---------------------------------------------------------- state setup

    def _load(self) -> None:
        c, mc = self.config, self.cfg
        dtype = jnp.dtype(c.dtype)
        t0 = time.perf_counter_ns()
        if c.quantize and c.quantize != "int8":
            raise ValueError(f"unknown quantize mode: {c.quantize!r}")
        if c.quantize and self.embedding_only:
            # bert_embed consumes its weights with plain dots (no qdot
            # routing) — loud failure beats a TypeError mid-forward
            raise ValueError(
                f"{self.cfg.name}: quantize is not supported for "
                "embedding-only models"
            )

        def _maybe_quant(p):
            if c.quantize == "int8":
                from gridllm_tpu.ops.quant import quantize_params

                return quantize_params(p)
            return p

        def _init():
            return _maybe_quant(
                self.mod.init_params(mc, jax.random.PRNGKey(0), dtype))

        init_times: dict[str, float] = {}
        # Weight snapshot tier (ISSUE 20): a parked host copy of this
        # exact checkpoint identity skips the safetensors re-read (or
        # re-init) — host→device transfer only. An injected restore fault
        # degrades to the disk/init path below, never a wedged load.
        snap = None
        from gridllm_tpu.engine.loader import weight_snapshot_tier

        tier = weight_snapshot_tier()
        if tier.enabled:
            try:
                faults.inject("swap.snapshot_restore")
                snap = tier.restore(self.snapshot_key())
            except faults.InjectedFault:
                log.warning("weight snapshot restore fault; degrading to "
                            "disk load", model=self.cfg.name)
                snap = None
        if snap is not None:
            # snapshots were parked post-quantization — re-materialize on
            # device as-is (no re-quantize), then reshard if meshed
            self.params = jax.tree_util.tree_map(jnp.asarray, snap)
            if self.mesh is not None:
                self.params = shard_params(self.params, self.mesh)
            self.load_source = "snapshot"
        elif c.checkpoint_path:
            from gridllm_tpu.engine.loader import load_checkpoint

            shardings = None
            if self.mesh is not None:
                shardings = param_shardings(jax.eval_shape(_init), self.mesh)
            self.params = load_checkpoint(
                mc, c.checkpoint_path, dtype, shardings, quantize=c.quantize
            )
            self.load_source = "checkpoint"
        else:
            self.params, init_times = self._init_weights(_init)
            self.load_source = "init"
        self._log_weights_ready(init_times)
        if self.embedding_only:
            # no generation state: encoder families have no KV cache,
            # sampler, or decode loop — just the pooled-forward embed path
            self.load_duration_ns = time.perf_counter_ns() - t0
            self.max_context = mc.max_seq_len
            self._set_buckets()
            _MODEL_LOAD_SECONDS.observe(
                self.load_duration_ns / 1e9,
                model=self.cfg.name, source=self.load_source,
            )
            return
        self.config = c = dataclasses.replace(
            c, num_pages=self._resolve_num_pages())
        self._init_device_state()
        self.load_duration_ns = time.perf_counter_ns() - t0
        self.max_context = min(
            mc.max_seq_len, c.max_pages_per_slot * c.page_size
        )
        self._set_buckets()
        _MODEL_LOAD_SECONDS.observe(
            self.load_duration_ns / 1e9,
            model=self.cfg.name, source=self.load_source,
        )

    def _init_weights(self, init: Callable[[], Any]) -> tuple[Any, dict]:
        """Synthetic weights. Under a mesh the tree is born sharded: one
        jitted program whose out-shardings are the mesh's layout, so no
        chip ever holds more than its share (a 12B tree does not pass
        through device 0; the out-shardings move no bit, and against the
        eager call XLA's fusion rounds about one element in a million the
        other way by one bf16 step). Unmeshed it stays the eager per-leaf
        call (ROADMAP D12). Returns the tree and the init's seconds."""
        with capture_span("gridllm.init_params", model=self.cfg.name,
                          mesh=self.mesh_axes):
            t0 = time.perf_counter()
            if self.mesh is None:
                params = jax.block_until_ready(init())
                return params, {"initRunS": time.perf_counter() - t0}
            # compiled ahead of its one call, so that the record can tell
            # the compile's seconds from the run's
            program = self.perf.wrap("weights_init", jax.jit(
                init, out_shardings=param_shardings(
                    jax.eval_shape(init), self.mesh)),
                armable=False).lower().compile()
            t1 = time.perf_counter()
            params = jax.block_until_ready(program())
            return params, {"initCompileS": t1 - t0,
                            "initRunS": time.perf_counter() - t1}

    def _log_weights_ready(self, init_times: dict[str, float]) -> None:
        """One record of where the weights are: the most parameter bytes
        any one device holds is what a sharded birth is for."""
        # from the shardings' metadata, as /admin/memory counts: walking
        # addressable_shards would leave one live Array a shard behind
        held: dict[Any, int] = {}
        for leaf in jax.tree_util.tree_leaves(self.params):
            nbytes = math.prod(
                leaf.sharding.shard_shape(leaf.shape)) * leaf.dtype.itemsize
            for device in leaf.sharding.addressable_devices:
                held[device] = held.get(device, 0) + nbytes
        log.info("weights ready", model=self.cfg.name,
                 source=self.load_source, mesh=self.mesh_axes,
                 devices=len(held),
                 paramBytesMaxDevice=max(held.values(), default=0),
                 **{k: round(v, 3) for k, v in init_times.items()})

    def snapshot_key(self) -> str:
        """Checkpoint identity for the weight snapshot tier: everything
        that changes the materialized param pytree. Two engines with the
        same key are guaranteed interchangeable weights."""
        c = self.config
        return "|".join((
            self.cfg.name,
            c.checkpoint_path or "init",
            str(c.dtype),
            c.quantize or "none",
            str(c.mesh or ""),
        ))

    def park_weights(self) -> bool:
        """Park this engine's params into the host snapshot tier (call
        after stop(), on the unload path). On success the device
        references are dropped so HBM weight gauges fall to zero."""
        from gridllm_tpu.engine.loader import weight_snapshot_tier

        tier = weight_snapshot_tier()
        if not tier.enabled or self.params is None:
            return False
        ok = tier.park(self.snapshot_key(), self.params)
        if ok:
            self.params = None
        return ok

    def prewarm(self) -> None:
        """Run every program a first request can need, before serving:
        the chunk program at each width admission can launch (without a
        mixed step: bucketed prefill at each bucket a prompt up to one
        chunk can pad to, and the chunked-prefill program), the decode or
        verify step, and the prefix-cache admission — inline, as greedy
        requests of the lengths that reach them. A worker that advertised
        a model first would compile these inside its first requests,
        minutes on a cold cache, under the scheduler's deadlines and the
        hang watchdog's requeue; and a kernel that cannot compile fails
        here, at construction. With the persistent cache a restart reads
        them from disk. The recompile tripwire stays disarmed: the first REAL
        completed request still ends warm-up."""
        if self.embedding_only or self.running:
            return
        t0 = time.perf_counter_ns()
        room = self.max_context - 2          # a prompt plus two tokens
        lengths: list[int] = []
        if self._use_mixed:
            # a first chunk's own width; the full one too where no chunk
            # and one below reaches it
            lengths.append(min(self._chunk_first, room))
            if self._chunk_first < room <= self._chunk_len:
                lengths.append(room)
        else:
            for b in self._buckets:
                lengths.append(min(b, room))
                if self._use_chunked and b >= self._chunk_len:
                    break
        if self._use_chunked and self._chunk_len < room:
            # a full chunk and a one-token last chunk behind it: the full
            # and the narrow width of the chunk program (_chunk_width);
            # sent twice when the prefix cache is on, so the second
            # admission is a hit (a second-kind cache's state_restore,
            # then the narrow width behind a cached prefix)
            lengths += [self._chunk_len + 1] * (
                2 if self._prefix_cache_cap != 0 else 1)
        # one fill token per length: prompts that shared a first page
        # would hit the prefix cache and skip their width
        prompts = [(n, 1 + lengths.index(n)) for n in lengths]
        if self.mesh is not None:
            # the first request again, as a new prompt: under a mesh its
            # programs (admit_seed, the first width's chunk) were
            # compiled for the state as created, on one device, and every
            # step leaves the state laid out over the mesh as XLA chose, so
            # their second call compiles again with no new Python signature
            # (gridllm_xla_compile_seconds sees it, the tripwire cannot):
            # here, not inside a user's request
            prompts.append((lengths[0], 1 + len(lengths)))
        self._perf_armed = True              # _finish arms only when False
        # what follows is tracing and lowering: a few hundred thousand
        # objects that stay alive in jax's caches, so the cyclic
        # collector's passes over them free next to nothing and cost a
        # warm start a third of a second (PERF.md, PR 32). Paused, with the
        # one pass that is due made at the end, inside what set-up times
        gc_was_on = gc.isenabled()
        gc.disable()
        try:
            for i, (n, fill) in enumerate(prompts):
                t1 = time.perf_counter()
                res = self.generate(GenerationRequest(
                    id=f"prewarm-{i}", raw=True, prompt_ids=[fill] * n,
                    options={"temperature": 0, "seed": 0, "num_predict": 2},
                ))
                if res.done_reason == "error":
                    raise RuntimeError(
                        f"{self.cfg.name}: prewarm request of {n} tokens "
                        f"failed: {res.error}")
                log.info("prewarm step", model=self.cfg.name, promptTokens=n,
                         cachedTokens=res.cached_tokens,
                         ms=int((time.perf_counter() - t1) * 1000))
        finally:
            self._perf_armed = False
            if gc_was_on:
                gc.enable()
                gc.collect()
        self.prewarm_duration_ns = time.perf_counter_ns() - t0
        # what jax has built for this model so far (init and prewarm; a
        # mesh's layout recompile of the first program among them): a
        # later rise of the series is a compile under traffic
        log.info("engine prewarmed", model=self.cfg.name,
                 ms=self.prewarm_duration_ns // 1_000_000,
                 xlaCompiles=XLA_COMPILE_SECONDS.count(model=self.cfg.name),
                 xlaCompileS=round(
                     XLA_COMPILE_SECONDS.sum(model=self.cfg.name), 3))

    def _set_chunk_widths(self) -> None:
        """The widths a chunk launch can take (_chunk_width picks among
        them): the full chunk, a first chunk that holds its whole prompt,
        the last chunk behind a prefix. Page-aligned: the in-place
        page-write kernel requires chunk starts at page boundaries. A
        routed launch's cost is flat in its rows (every expert is read
        whatever it carries), so a routed family has one width; a dense
        launch's is not."""
        ps = self.config.page_size

        def aligned(n: int) -> int:
            return max(ps, (n // ps) * ps)

        full = min(self.config.prefill_chunk, self.max_context)
        if self.cfg.num_experts and self._use_mixed:
            self._chunk_len = aligned(min(full, ROUTED_CHUNK))
            self._chunk_first = self._chunk_narrow = self._chunk_len
        else:
            self._chunk_len = aligned(full)
            self._chunk_first = min(aligned(FIRST_CHUNK), self._chunk_len)
            self._chunk_narrow = aligned(self.config.prefill_chunk_narrow)

    def _set_buckets(self) -> None:
        # always include max_context so every admissible length maps to a
        # fixed padded shape — a length above the largest configured bucket
        # must not fall through to per-length recompiles
        self._buckets = sorted(
            {min(b, self.max_context) for b in self.config.prefill_buckets}
            | {self.max_context}
        )

    def _resolve_prefix_cache_cap(self) -> int:
        """EngineConfig overrides env; GRIDLLM_PREFIX_CACHE=0 disables,
        GRIDLLM_PREFIX_CACHE_PAGES bounds the reuse LRU (default unbounded
        — the whole page pool doubles as the cache, evicted on demand;
        0 ALSO disables, matching PageAllocator.cache_pages)."""
        on = self.config.prefix_cache
        if on is None:
            on = env_bool("GRIDLLM_PREFIX_CACHE")
        if not on:
            return 0
        pages = self.config.prefix_cache_pages
        if pages is None:
            pages = env_int("GRIDLLM_PREFIX_CACHE_PAGES")
        return max(pages, -1)

    def _resolve_kv_int8(self) -> bool:
        """Resident int8 KV pool (ISSUE 11). EngineConfig overrides env.
        Single-device pools only: a mesh shards the pool arrays and the
        QuantPages scale operands have no shard_map plumbing — meshes
        keep the fp layout (logged, not silent)."""
        on = self.config.kv_int8
        if on is None:
            on = env_bool("GRIDLLM_KV_INT8")
        if not on or self.embedding_only:
            return False
        if self.cfg.kv_lora_rank:
            raise ValueError(
                f"{self.cfg.name}: an int8 KV pool is not served for a "
                "latent cache (one row is key and value at once; no "
                "quantised read of it has been written or measured): "
                "unset GRIDLLM_KV_INT8 / EngineConfig.kv_int8")
        if self._second:
            raise ValueError(
                f"{self.cfg.name}: an int8 KV pool is not served beside a "
                "recurrent state or the window layers' rings (the pages "
                "are a quarter of the layers; no quantised read has been "
                "measured against the second cache): unset "
                "GRIDLLM_KV_INT8 / EngineConfig.kv_int8")
        if self.mesh is not None:
            log.info("int8 KV pool disabled: meshed pools keep the fp "
                     "layout", model=self.cfg.name)
            return False
        return True

    def _build_host_tier(self):
        """Host-RAM KV tier (ISSUE 11): the spill target behind the HBM
        reuse LRU. Needs the prefix cache (the spill unit IS a
        content-addressed cached page) and a process-local unsharded
        pool — the same constraints as KV migration."""
        cap = self.config.kv_host_bytes
        if cap is None:
            cap = env_int("GRIDLLM_KV_HOST_BYTES")
        if cap <= 0 or self.embedding_only:
            return None
        if self.cfg.kv_lora_rank:
            raise ValueError(
                f"{self.cfg.name}: the host KV tier is not served for a "
                "latent cache (its spill format is K and V pages): unset "
                "GRIDLLM_KV_HOST_BYTES / EngineConfig.kv_host_bytes")
        if self._second:
            raise ValueError(
                f"{self.cfg.name}: the host KV tier (and park_to_host) is "
                "not served beside a recurrent state or the window layers' "
                "rings (pages that come back without their snapshot admit "
                "nothing): unset GRIDLLM_KV_HOST_BYTES / "
                "EngineConfig.kv_host_bytes")
        if self._prefix_cache_cap == 0 or self.mesh is not None:
            log.info("host KV tier disabled: needs the prefix cache and "
                     "an unsharded pool", model=self.cfg.name,
                     prefixCache=self._prefix_cache_cap != 0,
                     meshed=self.mesh is not None)
            return None
        spill_int8 = self.config.kv_spill_int8
        if spill_int8 is None:
            spill_int8 = env_bool("GRIDLLM_KV_SPILL_INT8")
        from gridllm_tpu.ops.kvtier import HostKVTier

        log.info("host KV tier enabled", model=self.cfg.name,
                 capacityBytes=cap,
                 spillDtype="int8-page" if spill_int8 else "raw")
        return HostKVTier(cap, model=self.cfg.name, spill_int8=spill_int8)

    def _resolve_spec_k(self) -> int:
        """Speculation depth K (0 = off). EngineConfig overrides env;
        GRIDLLM_SPEC_DECODE=0 disables, GRIDLLM_SPEC_K sets the depth
        (default 4 — a [S, 5] verify block). Fixed per process: K is a
        static jit arg, so a single verify program serves steady state."""
        on = self.config.spec_decode
        if on is None:
            on = env_bool("GRIDLLM_SPEC_DECODE")
        if not on:
            return 0
        k = self.config.spec_k
        if k is None:
            k = env_int("GRIDLLM_SPEC_K")
        return max(int(k), 0)

    def _resolve_draft_model(self) -> str:
        """Draft-model config name ("" = n-gram drafting, the default).
        EngineConfig overrides GRIDLLM_SPEC_DRAFT_MODEL."""
        name = self.config.draft_model
        if name is None:
            name = env_str("GRIDLLM_SPEC_DRAFT_MODEL")
        return (name or "").strip()

    def _resolve_tree_width(self) -> int:
        """Tree sibling fan-out at depth 1 (1 = pure chain). EngineConfig
        overrides GRIDLLM_SPEC_TREE_WIDTH. Clamped so the node budget
        1 + K + (width-1) is at least the root + chain."""
        w = self.config.spec_tree_width
        if w is None:
            w = env_int("GRIDLLM_SPEC_TREE_WIDTH")
        return max(int(w), 1)

    def _build_model_drafter(self, spec_k: int):
        """Construct the draft-model tree drafter (ISSUE 18), or None when
        no draft model is configured / the configured one is incompatible
        with the target — the caller then keeps the n-gram drafter, so a
        bad knob degrades speculation quality instead of failing serving.

        The draft model shares the target's mesh and dtype but owns a
        small fixed-stripe KV pool (DraftModelDrafter): per slot, enough
        pages for the engine's max_context plus the draft chain, page
        size matching the engine's."""
        name = self._resolve_draft_model()
        if not name:
            return None
        if self._second:
            log.warning("draft-model (tree) speculation is not served for a "
                        "recurrent state or a window ring; falling back to "
                        "n-gram",
                        model=self.cfg.name, draftModel=name)
            return None
        try:
            dcfg = get_config(name)
        except Exception:
            log.warning("draft model unknown; falling back to n-gram",
                        model=self.cfg.name, draftModel=name)
            return None
        dmod = _model_module(dcfg)
        if dcfg.vocab_size != self.cfg.vocab_size:
            # acceptance compares token ids — different vocabs make the
            # rejection test meaningless (and usually out-of-range)
            log.warning("draft model vocab mismatch; falling back to n-gram",
                        model=self.cfg.name, draftModel=name,
                        vocab=self.cfg.vocab_size, draftVocab=dcfg.vocab_size)
            return None
        if not (hasattr(dmod, "verify_step") and hasattr(dmod, "decode_step")):
            log.warning("draft model family lacks verify/decode steps; "
                        "falling back to n-gram",
                        model=self.cfg.name, draftModel=name)
            return None
        c = self.config
        dtype = jnp.dtype(c.dtype)
        ckpt = self.config.draft_checkpoint
        if ckpt is None:
            ckpt = env_str("GRIDLLM_SPEC_DRAFT_CHECKPOINT")
        ckpt = (ckpt or "").strip()
        if ckpt:
            from gridllm_tpu.engine.loader import load_checkpoint

            shardings = None
            if self.mesh is not None:
                proto = jax.eval_shape(
                    lambda: dmod.init_params(dcfg, jax.random.PRNGKey(0),
                                             dtype)
                )
                shardings = param_shardings(proto, self.mesh)
            dparams = load_checkpoint(dcfg, ckpt, dtype, shardings)
        else:
            dparams = dmod.init_params(dcfg, jax.random.PRNGKey(0), dtype)
            if self.mesh is not None:
                dparams = shard_params(dparams, self.mesh)
        # pool sizing: the engine never drafts past its own max_context,
        # and the decode steps write ≤ spec_k rows past it
        mpps = -(-(self.max_context + spec_k + 1) // c.page_size)
        drafter = DraftModelDrafter(
            dmod, dcfg, dparams,
            max_slots=c.max_slots, page_size=c.page_size,
            max_pages_per_slot=mpps, mesh=self.mesh,
            ingest_width=max(env_int("GRIDLLM_SPEC_DRAFT_INGEST"), 1),
            dtype=dtype, wrap=self.perf.wrap,
        )
        log.info("draft-model speculation enabled", model=self.cfg.name,
                 draftModel=name, checkpoint=ckpt or "(fresh init)",
                 treeWidth=self._resolve_tree_width(),
                 draftPoolPages=c.max_slots * mpps)
        return drafter

    def _pool_head_dim(self) -> int:
        """Page-pool head dim: lane-padded to 128 when the Pallas kernels
        will run (Mosaic's alignment constraint), so d=64 models (qwen2.5
        class) keep the kernel decode path instead of the jnp gather
        (VERDICT r04 #5). Resolved with the SAME policy the op dispatchers
        use (_pallas_mode with the per-engine use_pallas override —
        ADVICE r05), so a config that forces kernels on where the env says
        off still gets the padded pool its kernels require. Interpret mode
        keeps the model's dim (tests stay fast) unless GRIDLLM_POOL_PAD=1
        forces the padded layout for coverage. The ops dispatchers
        pad/slice at the boundary."""
        from gridllm_tpu.ops.kvcache import _pallas_mode, lane_pad_dim

        # no layout keeps a narrower head unpadded where kernels compile:
        # Mosaic tiles a [.., KVH, 64] page at 128 lanes whatever its
        # shape says and refuses every kernel's slice of 64 of them
        # (tests/test_granite_hybrid.py compiles the ragged kernel and
        # the three writes for a described v5e both ways, PR 61); a
        # latent family's row (cfg.cache_dim: 576 at DeepSeek-V2-Lite) is
        # tiled at 640 lanes likewise (PERF.md, PR 36)
        d = self.cfg.cache_dim
        use, interpret = _pallas_mode(self.cfg.use_pallas)
        if not use or (interpret and not env_bool("GRIDLLM_POOL_PAD")):
            return d
        return lane_pad_dim(d)

    def _new_cache(self, num_pages: int) -> PagedKVCache:
        """An empty cache with a pool of `num_pages` (traceable)."""
        c, mc = self.config, self.cfg
        dpool = self._pool_head_dim()
        if not self._kv_int8:
            cache = PagedKVCache.create(
                mc.cache_layers, num_pages, c.page_size, mc.cache_heads,
                dpool, c.max_slots, c.max_pages_per_slot,
                dtype=jnp.dtype(c.dtype), latent=bool(mc.kv_lora_rank),
            )
            if self._hybrid:
                cache.rec = self._new_state(self._snapshots)
            if self._ring:
                cache.win = self._new_ring(self._snapshots)
            return cache
        # resident int8 pool (ISSUE 11): QuantPages where the fp pool
        # arrays would sit — int8 values + one f32 scale per (layer,
        # page, row). Scales init to 1.0 so unwritten rows dequant to
        # exact zeros. Halves KV HBM; the write dispatchers quantize
        # per row at the boundary, the ragged kernel / jnp fallbacks
        # dequantize on read.
        shape = (mc.num_layers, num_pages, c.page_size, mc.num_kv_heads,
                 dpool)

        def pages():
            return QuantPages(jnp.zeros(shape, jnp.int8),
                              jnp.ones(shape[:3], jnp.float32))

        return PagedKVCache(
            k=pages(), v=pages(),
            page_table=jnp.full((c.max_slots, c.max_pages_per_slot), -1,
                                jnp.int32),
            lengths=jnp.zeros((c.max_slots,), jnp.int32),
            page_size=c.page_size,
        )

    def _new_state(self, snapshots: int):
        """A hybrid family's recurrent state for every slot, a verify
        launch's rows wide, with a pool of `snapshots` (traceable)."""
        return self.mod.new_state(
            self.cfg, self.config.max_slots, self._resolve_spec_k() + 1,
            snapshots, jnp.dtype(self.config.dtype))

    def _ring_launch_rows(self) -> int:
        """Rows one launch may write into one slot: the widest chunk
        (_set_chunk_widths) or a verify launch's K + 1."""
        c = self.config
        ps = c.page_size
        full = min(c.prefill_chunk, self.cfg.max_seq_len,
                   c.max_pages_per_slot * ps)
        if self.cfg.num_experts and hasattr(self.mod, "mixed_step"):
            full = min(full, ROUTED_CHUNK)
        return max(ps, full // ps * ps, self._resolve_spec_k() + 1)

    def _new_ring(self, snapshots: int):
        """The window layers' rings for every slot, a launch wide, with a
        pool of `snapshots` behind them (traceable)."""
        return self.mod.new_ring(
            self.cfg, self.config.max_slots, self._ring_launch_rows(),
            snapshots, self.config.page_size, self._pool_head_dim(),
            jnp.dtype(self.config.dtype))

    def _page_bytes_per_device(self) -> int:
        """Bytes ONE pool page (K and V, every layer, int8 scales
        included) takes on the device that holds most of it."""
        proto = jax.eval_shape(partial(self._new_cache, 1))
        pools = jax.tree.leaves((proto.k, proto.v))
        if self.mesh is None:
            return sum(math.prod(a.shape) * a.dtype.itemsize for a in pools)
        from gridllm_tpu.parallel.sharding import cache_shardings

        sh = cache_shardings(proto, self.mesh)
        return sum(
            math.prod(s.shard_shape(a.shape)) * a.dtype.itemsize
            for a, s in zip(pools, jax.tree.leaves((sh.k, sh.v))))

    def _resolve_num_pages(self) -> int:
        """KV pool size in pages, checked against the device (see
        EngineConfig.num_pages). Called with the weights loaded, so the
        allocator's bytes_in_use is the weights plus whatever else this
        process already holds (other engines of a multi-model worker).
        Logs pages, tokens and bytes; a pool that cannot fit raises here,
        with the figures, instead of as an XLA out-of-memory error inside
        the first request."""
        c = self.config
        devices = (list(self.mesh.devices.flat) if self.mesh is not None
                   else jax.devices()[:1])
        jax.block_until_ready(self.params)   # init temporaries are freed
        free = limit = None
        for d in devices:
            stats = (_device_memory_stats(d)
                     if d.process_index == jax.process_index() else {})
            if stats.get("bytes_limit"):
                f = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
                if free is None or f < free:
                    free, limit = f, stats["bytes_limit"]
        want = DEFAULT_NUM_PAGES if c.num_pages is None else c.num_pages
        self._snapshots = state_bytes = 0
        page_bytes = self._page_bytes_per_device()
        if self._second:
            # the slots' state (or rings) comes off the top; of what is then
            # left the snapshot pool takes its share, the pages the rest
            proto = jax.eval_shape(partial(
                self._new_state if self._hybrid else self._new_ring, 1))
            self._snapshots = (
                SNAPSHOTS_PER_SLOT * c.max_slots if free is None
                else max(int(SNAPSHOT_SHARE * max(
                    free - WORKSPACE_RESERVE_BYTES - proto.slot_nbytes, 0))
                    // proto.snap_nbytes, SNAPSHOTS_PER_SLOT))
            state_bytes = (proto.slot_nbytes
                           + self._snapshots * proto.snap_nbytes)
        if free is None:
            pages = want
        else:
            fit = max(free - WORKSPACE_RESERVE_BYTES - state_bytes,
                      0) // page_bytes
            # an explicit count is a contract; the default shrinks to the
            # device, down to one slot at full context
            floor = want if c.num_pages is not None else min(
                want, c.max_pages_per_slot)
            if fit < floor:
                raise ValueError(
                    f"{self.cfg.name}: KV pool does not fit the device: "
                    f"{floor} pages of {c.page_size} tokens "
                    f"({floor * page_bytes / 2**30:.2f} GiB at "
                    f"{page_bytes} B/page/device) needed, {fit} fit — "
                    f"device limit {limit / 2**30:.2f} GiB, "
                    f"{(limit - free) / 2**30:.2f} GiB in use after "
                    f"loading weights, {WORKSPACE_RESERVE_BYTES / 2**30:.2f}"
                    " GiB reserved for workspace")
            pages = min(want, fit)
        log.info(
            "kv pool sized", model=self.cfg.name, pages=pages,
            tokens=pages * c.page_size, pageSize=c.page_size,
            bytesPerDevice=pages * page_bytes, requestedPages=c.num_pages,
            deviceFreeBytes=free, deviceLimitBytes=limit,
            reserveBytes=WORKSPACE_RESERVE_BYTES if free is not None else None,
            stateBytes=state_bytes, stateSnapshots=self._snapshots,
            devices=len(devices))
        return pages

    def _init_device_state(self) -> None:
        """(Re)build all device-side mutable generation state: KV pool,
        page allocator, sampler params, context counts, token/active rows."""
        c, mc = self.config, self.cfg
        # a rebuild (reset_device_state) frees the old pool FIRST: the pool
        # is sized to fill the device, so two do not fit side by side, and
        # a step that failed before it ran (a compile error) donated
        # nothing — the old buffers are still live
        old, self.cache = getattr(self, "cache", None), None
        for buf in jax.tree.leaves(old):
            buf.delete()
        dpool = self._pool_head_dim()
        if dpool != mc.cache_dim:
            # lane padding multiplies KV bytes per page while num_pages is
            # config-fixed — say so at startup instead of silently serving
            # with a pool that costs dpool/d× the HBM the config budgeted
            # (ADVICE r05: d=64 models pay 2×)
            log.warning(
                "page pool lane-padded; KV bytes per page scaled",
                model=mc.name, head_dim=mc.cache_dim, pool_head_dim=dpool,
                kv_bytes_factor=round(dpool / mc.cache_dim, 2),
                num_pages=c.num_pages,
                hint=f"to keep KV HBM at the unpadded budget, set "
                     f"num_pages={int(c.num_pages * mc.cache_dim / dpool)}",
            )
        itemsize = 1 if self._kv_int8 else jnp.dtype(c.dtype).itemsize
        kind = mc.cache_kinds[0]
        # the row as stored: every cache head of K (and V) at the pool's width
        row_bytes = mc.kv_row_values // mc.cache_dim * dpool * itemsize
        _KV_ROW_BYTES.set(row_bytes, model=mc.name, kind=kind)
        # K and V per head at the model's own head sizes: a latent family
        # would store a key of head_dim and a value of v_head_dim a head
        _KV_ROW_BYTES_EQUIV.set(
            (mc.num_heads * (mc.qk_nope_head_dim + mc.qk_rope_head_dim
                             + mc.v_head_dim) if mc.kv_lora_rank
             else mc.kv_row_values) * itemsize, model=mc.name)
        log.info("kv pool rows", model=mc.name, cacheRow=kind,
                 kvRowBytes=row_bytes, rowValues=mc.kv_row_values,
                 poolRowDim=dpool, cacheLayers=mc.cache_layers,
                 modelLayers=mc.num_layers)
        if self.mesh is not None:
            # built under jit with the mesh's shardings: no device ever
            # holds more than its shard (a pool sized to several chips'
            # memory does not fit the one a plain create would fill first)
            from gridllm_tpu.parallel.sharding import cache_shardings

            make = partial(self._new_cache, c.num_pages)
            self.cache = self.perf.wrap("kv_pool_init", jax.jit(
                make, out_shardings=cache_shardings(
                    jax.eval_shape(make), self.mesh)), armable=False)()
        else:
            self.cache = self._new_cache(c.num_pages)
        self.alloc = PageAllocator(
            c.num_pages, c.page_size, c.max_pages_per_slot,
            cache_pages=self._prefix_cache_cap, model=mc.name,
            snapshots=self._snapshots if self._prefix_cache_cap else 0,
        )
        if self._second:
            rec = self.cache.rec if self._hybrid else self.cache.win
            _STATE_BYTES.set(rec.slot_nbytes, model=mc.name, kind="slot")
            _STATE_BYTES.set(rec.snap_nbytes, model=mc.name, kind="snapshot")
            _STATE_SNAP_CAPACITY.set(self.alloc.snapshots, model=mc.name)
            shape = ({"linearLayers": mc.linear_layers,
                      "stepRows": rec.step_rows} if self._hybrid else
                     {"ringLayers": mc.ring_layers,
                      "ringRows": rec.ring_pages * c.page_size,
                      "snapshotRows": rec.snap_pages * c.page_size})
            log.info("recurrent state" if self._hybrid else "window rings",
                     model=mc.name,
                     slotStateBytes=rec.slot_nbytes // c.max_slots,
                     snapshotBytes=rec.snap_nbytes // max(self._snapshots, 1),
                     snapshots=self._snapshots, **shape)
        if self.host_tier is not None:
            # tiered KV cache (ISSUE 11): eviction spills to host RAM,
            # match_prefix misses consult it — both fire under
            # _alloc_lock from inside the allocator
            self.alloc.spill_sink = self._spill_page_to_host
            self.alloc.restore_source = self._restore_page_from_host
        # lock-discipline sanitizer (ISSUE 8): under GRIDLLM_SANITIZE=1
        # every mutating allocator call asserts _alloc_lock ownership at
        # the call site instead of corrupting refcounts three requests
        # later; dormant (no import, no wrap) otherwise
        if env_bool("GRIDLLM_SANITIZE"):
            from gridllm_tpu.analysis.lockcheck import guard_allocator
            from gridllm_tpu.analysis.statecheck import track_object

            guard_allocator(self.alloc, self._alloc_lock)
            # shared-state sanitizer (ISSUE 13): allocator state is
            # mutated from the runner thread AND gateway executor
            # threads — every write must hold _alloc_lock in common,
            # which the write tracker verifies independently of the
            # call-site guard above
            track_object(self.alloc, f"alloc:{mc.name}", (
                "_free", "_owned", "_refs", "_key_of", "_page_by_key",
                "_staged_stats"))
        self.sampling = SamplingParams.defaults(c.max_slots)
        self.counts = jnp.zeros((c.max_slots, mc.vocab_size), jnp.int32)
        # repeat-penalty window: last ≤ repeat_last_n context tokens per
        # slot (ops/sampling.py window_* helpers maintain it + counts)
        self.window = jnp.zeros((c.max_slots, c.repeat_window), jnp.int32)
        self.wlen = jnp.zeros((c.max_slots,), jnp.int32)
        self.tokens = jnp.zeros((c.max_slots,), jnp.int32)
        self.active = jnp.zeros((c.max_slots,), bool)

    def reset_device_state(self) -> None:
        """Recover from a failed jitted step. prefill_fn/decode_fn donate the
        cache/counts buffers, so an exception mid-call can leave self.cache
        referencing deleted arrays; serving again on that state
        deterministically fails every subsequent request. Params are never
        donated and survive; everything else is rebuilt. Callers should
        abort_all() first — slot state is discarded here."""
        if self.embedding_only:
            return
        with self._alloc_lock, self.dispatch_lock:
            self._slots.clear()
            self._inflight.clear()
            self._spec_hits = self._spec_quiet = 0
            self._free_slots = list(range(self.config.max_slots - 1, -1, -1))
            self._init_device_state()
            if self._drafter is not None and hasattr(self._drafter, "reset"):
                # the drafter's jitted entries donate ITS cache — an
                # exception mid-draft can leave it referencing deleted
                # buffers, same failure mode this reset exists to cure
                self._drafter.reset()
            self._update_kv_gauges()
            if self.plan_sink is not None:  # after-success; see _try_admit
                self.plan_sink({"op": "reset"})

    def _build_fns(self) -> None:
        mc = self.cfg
        # pooled hidden states for the embeddings path — batched [B, T],
        # jit-compiled (one program per (batch-bucket, len-bucket) pair)
        # armable=False: embed compiles per (batch-bucket, len-bucket)
        # pair ON DEMAND — a decoder model's first embed request can land
        # long after generation warms, and flagging that bounded,
        # legitimate compile as a steady-state recompile would page on
        # healthy behavior (same for the vision pair below)
        self._embed_fn = self.perf.wrap("embed", jax.jit(
            lambda params, tokens, lens: self.mod.hidden_states(
                params, mc, tokens, seq_lens=lens, mesh=self.mesh
            )
        ), armable=False)
        if self.embedding_only:
            return
        # the form every sampler call site below lowers to, by the width
        # of the logits' last axis alone (ops/sampling.py)
        _SAMPLER_TOPK_STAGES.set(topk_stages(mc.vocab_size), model=mc.name)

        # sp > 1 → sequence-parallel prefill: ring attention splits the
        # prompt's T axis over the sp mesh axis (ops/ring_attention.py)
        attn = None
        if self.mesh is not None and self.mesh.shape["sp"] > 1:
            from gridllm_tpu.ops.ring_attention import ring_attention

            attn = partial(ring_attention, mesh=self.mesh)

        # pp > 1 → pipeline parallelism: layer blocks as token-passing
        # stages (parallel/pipeline.py); same family API, so the jitted
        # step fns below are oblivious to which module serves them
        mod = self.mod
        if self.mesh is not None and self.mesh.shape.get("pp", 1) > 1:
            from gridllm_tpu.parallel import pipeline

            pipeline.validate(self.cfg, self.mesh)
            mod = pipeline

        def _gather_sp(sp: SamplingParams, slot) -> SamplingParams:
            return jax.tree.map(lambda a: a[slot][None], sp)

        # a routed family's decode / verify steps also return what they
        # routed ([live rows, experts touched], summed over layers): two
        # integers that travel to the host in the fetch a launch makes
        # anyway, and feed gridllm_moe_*
        self._step_stats = bool(
            getattr(mod, "STEP_STATS", False) and mc.num_experts)
        stats_kw = {"with_stats": True} if self._step_stats else {}

        # Prefill folds EVERYTHING into device state — the sampled first
        # token lands in `tokens[slot]` and the host never synchronizes on
        # it (it arrives with the next decode block's row 0). sp.step for
        # the slot advances to 1: the prefill sample consumed draw 0.
        # The repeat-penalty window resets to the prompt's last
        # repeat_last_n tokens (llama.cpp penalty_last_n semantics).
        @partial(jax.jit, donate_argnums=(2, 3, 4, 5, 6, 7, 8))
        def prefill_fn(params, prompt, cache, counts, window, wlen, tokens,
                       active, sp, length, slot, table_row, embeds=None):
            logits, cache = mod.prefill(
                params, mc, prompt, length, cache, slot, table_row, attn=attn,
                mesh=self.mesh, embeds=embeds,
            )
            rl = sp.repeat_last_n[slot]
            window, wlen, counts = window_set_slot(
                window, wlen, counts, slot, prompt, jnp.int32(0), length,
                rl, mc.vocab_size,
            )
            tok = sample_tokens(logits[None], _gather_sp(sp, slot), counts[slot][None])[0]
            tokens = tokens.at[slot].set(tok)
            one = jnp.zeros_like(active).at[slot].set(True)
            window, wlen, counts = window_push(
                window, wlen, counts, tokens, one, sp.repeat_last_n,
                mc.vocab_size,
            )
            active = active.at[slot].set(True)
            # step continues from the admission value (0 normally; the
            # already-generated count on a decode resume, ISSUE 9) — the
            # prefill sample consumed that draw, so +1
            sp = dataclasses.replace(
                sp, step=sp.step.at[slot].set(sp.step[slot] + 1))
            return cache, counts, window, wlen, tokens, active, sp

        @partial(jax.jit, donate_argnums=(2, 3, 4, 5, 6, 7, 8))
        def prefill_chunk_fn(params, prompt, cache, counts, window, wlen,
                             tokens, active, sp, start, length, slot,
                             table_row, is_final, embeds=None):
            logits, cache = mod.prefill_chunk(
                params, mc, prompt, start, length, cache, slot, table_row,
                mesh=self.mesh, embeds=embeds,
            )
            rl = sp.repeat_last_n[slot]
            window, wlen, counts = window_set_slot(
                window, wlen, counts, slot, prompt, start, length,
                rl, mc.vocab_size,
            )
            tok = sample_tokens(
                logits[None], _gather_sp(sp, slot), counts[slot][None]
            )[0]
            # intermediate chunks sample garbage (discarded on device);
            # only the final chunk activates the slot and counts its token
            tokens = tokens.at[slot].set(jnp.where(is_final, tok, tokens[slot]))
            one = jnp.zeros_like(active).at[slot].set(is_final)
            window, wlen, counts = window_push(
                window, wlen, counts, tokens, one, sp.repeat_last_n,
                mc.vocab_size,
            )
            active = active.at[slot].set(is_final | active[slot])
            sp = dataclasses.replace(
                sp, step=sp.step.at[slot].set(
                    jnp.where(is_final, sp.step[slot] + 1, sp.step[slot])
                )
            )
            return cache, counts, window, wlen, tokens, active, sp

        # Ragged mixed step (ISSUE 6): ONE forward serving the admitting
        # slot's prefill chunk AND a decode token for every active slot —
        # a mixed prefill+decode step is a single attention launch per
        # layer, so long chunked prefills no longer stall running streams
        # between decode blocks. Bookkeeping is the union of
        # prefill_chunk_fn's (chunk slot rows) and decode_block_fn's
        # (active slot rows) — per-slot state rows are disjoint, so each
        # region's updates are bit-identical to the per-phase programs'.
        # Returns a [2, S] block (row 0 = input tokens, row 1 = this
        # step's decode samples) that rides the normal ingest protocol.
        @partial(jax.jit, donate_argnums=(2, 3, 4, 5, 6, 7, 8))
        def mixed_chunk_fn(params, chunk, cache, counts, window, wlen,
                           tokens, active, sp, start, length, slot,
                           table_row, is_final, embeds=None, state_io=None):
            tokens_in = tokens
            active_in = active
            # a hybrid family's launch is also told at which page
            # boundaries it passes to save its state, and where
            state_kw = {} if state_io is None else {"state_io": state_io}
            chunk_logits, dec_logits, cache = mod.mixed_step(
                params, mc, chunk, start, length, slot, table_row, tokens,
                cache, active, mesh=self.mesh, embeds=embeds, **state_kw,
            )
            # chunk-slot bookkeeping (exactly prefill_chunk_fn's)
            rl = sp.repeat_last_n[slot]
            window, wlen, counts = window_set_slot(
                window, wlen, counts, slot, chunk, start, length,
                rl, mc.vocab_size,
            )
            tok = sample_tokens(
                chunk_logits[None], _gather_sp(sp, slot), counts[slot][None]
            )[0]
            tokens = tokens.at[slot].set(
                jnp.where(is_final, tok, tokens[slot])
            )
            one = jnp.zeros_like(active).at[slot].set(is_final)
            window, wlen, counts = window_push(
                window, wlen, counts, tokens, one, sp.repeat_last_n,
                mc.vocab_size,
            )
            active = active.at[slot].set(is_final | active[slot])
            sp = dataclasses.replace(
                sp, step=sp.step.at[slot].set(
                    jnp.where(is_final, sp.step[slot] + 1, sp.step[slot])
                )
            )
            # decode bookkeeping for the slots that were active at entry
            # (exactly decode_block_fn's body, k = 1)
            sampled = sample_tokens(dec_logits, sp, counts)
            tokens = jnp.where(active_in, sampled, tokens)
            window, wlen, counts = window_push(
                window, wlen, counts, tokens, active_in, sp.repeat_last_n,
                mc.vocab_size,
            )
            sp = dataclasses.replace(
                sp, step=sp.step + active_in.astype(jnp.int32)
            )
            out = jnp.stack([tokens_in, tokens])  # [2, S]
            return out, cache, counts, window, wlen, tokens, active, sp

        # One decode block: k fused (model step + sample + bookkeeping)
        # iterations under lax.scan. Returns ([k+1, S] tokens, a routed
        # family's statistics or None) first — row 0 of the tokens is the
        # block's INPUT tokens (a newly admitted slot's prefill sample),
        # rows 1..k the block's samples.
        @partial(jax.jit, static_argnames=("k",),
                 donate_argnums=(1, 2, 4, 5, 6, 7))
        def decode_block_fn(params, cache, tokens, active, counts, window,
                            wlen, sp, *, k):
            first = tokens

            def body(carry, _):
                tokens, cache, counts, window, wlen, sp = carry
                logits, cache, *stats = mod.decode_step(
                    params, mc, tokens, cache, active, mesh=self.mesh,
                    **stats_kw,
                )
                sampled = sample_tokens(logits, sp, counts)
                tokens = jnp.where(active, sampled, tokens)
                window, wlen, counts = window_push(
                    window, wlen, counts, tokens, active, sp.repeat_last_n,
                    mc.vocab_size,
                )
                sp = dataclasses.replace(
                    sp, step=sp.step + active.astype(jnp.int32)
                )
                return (tokens, cache, counts, window, wlen, sp), (tokens, stats)

            (tokens, cache, counts, window, wlen, sp), (toks, stats) = (
                jax.lax.scan(
                    body, (tokens, cache, counts, window, wlen, sp), None,
                    length=k))
            out = jnp.concatenate([first[None], toks])  # [k+1, S]
            # a routed family's statistics, summed over the k steps: a
            # second output that the block's own fetch brings along
            stats = stats[0].sum(axis=0) if stats else None
            return (out, stats), tokens, cache, counts, window, wlen, sp

        # Admission's sampler state (ISSUE 25, ISSUE 60): ONE donated
        # program of integer and scalar state an admission, fed ONE host
        # record (_seed_record). It writes the slot's sampler row (an eager
        # `.at[slot].set(v)` per field is several one-element programs,
        # each a Python + PJRT dispatch with the chip idle: PERF.md, PR 25)
        # and rebuilds the slot's repeat-penalty window, wlen and counts row
        # from the record's tail, the last min(cached, repeat_window) tokens
        # of the prefix-cached span: those tokens skip the model forward but
        # must still be in the window, or a warm request's sampler state
        # (and so its tokens) would part from the cold path's. A window
        # keeps the last min(total, repeat_last_n <= repeat_window) tokens,
        # so the tail alone gives what appending the span chunk by chunk
        # gave, bit for bit, whatever `cached` is; the first real chunk
        # behind it appends (start = cached != 0). With nothing cached the
        # tail is empty and the reset is the one the first chunk does anyway.
        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def admit_seed_fn(sp, window, wlen, counts, rec):
            slot, tail_len, row, tail = _seed_fields(rec)
            sp = sp.set_row(slot, row)
            window, wlen, counts = window_set_slot(
                window, wlen, counts, slot, tail, jnp.int32(0), tail_len,
                sp.repeat_last_n[slot], mc.vocab_size,
            )
            return sp, window, wlen, counts

        # a finished slot's active flag: one donated program too
        @partial(jax.jit, donate_argnums=(0,))
        def deactivate_fn(active, slot):
            return active.at[slot].set(False)

        if self._second:
            # a prefix-cache admission's state (or rings): one snapshot,
            # taken at position `at`, copied into the slot ahead of its
            # first chunk launch
            @partial(jax.jit, donate_argnums=(0,))
            def state_restore_fn(cache, slot, entry, at):
                if self._ring:
                    return mod.restore_snapshot(cache, slot, entry, at)
                return dataclasses.replace(
                    cache, rec=cache.rec.restore(slot, entry))

            self._state_restore_fn = self.perf.wrap(
                "state_restore", state_restore_fn)
        self._admit_seed_fn = self.perf.wrap("admit_seed", admit_seed_fn)
        self._deactivate_fn = self.perf.wrap("deactivate", deactivate_fn)
        # vision models legitimately double the prefill signature space
        # post-warmup: an image request adds the embeds leaf to the same
        # bucket a text request compiled without it, so armed prefill
        # probes would flag the first image request as a steady-state
        # recompile. Decode stays armed — the hot loop's shapes are
        # vision-independent.
        text_only = not self.cfg.vision
        self._prefill_fn = self.perf.wrap("prefill", prefill_fn,
                                          armable=text_only)
        self._prefill_chunk_fn = self.perf.wrap("prefill_chunk",
                                                prefill_chunk_fn,
                                                armable=text_only)
        if self.cfg.vision:
            # vision path (llava family): encode_images per image-count
            # (jit caches per shape — image counts are tiny), splice per
            # (bucket, image-count) pair
            def encode_fn(params, px):
                emb = self.mod.encode_images(params, mc, px)  # [n, N, E]
                return emb.reshape(-1, emb.shape[-1])

            self._encode_fn = self.perf.wrap(
                "encode_images", jax.jit(encode_fn), armable=False)
            self._splice_fn = self.perf.wrap("splice_embeds", jax.jit(
                lambda params, toks, ie, off: self.mod.splice_embeds(
                    params, mc, toks, ie, off
                )
            ), armable=False)
        # ring attention (sp) runs whole-prompt prefill; the chunked path
        # reads the paged prefix instead and has no sp variant yet
        self._use_chunked = attn is None
        # mixed steps need the chunked path AND a family mixed_step.
        # parallel/pipeline.py is the one module without a mixed schedule:
        # pp engines admit chunk by chunk through prefill_chunk_fn, which
        # exists for them alone
        self._use_mixed = self._use_chunked and hasattr(mod, "mixed_step")
        if self._use_mixed:
            self._mixed_chunk_fn = self.perf.wrap(
                "mixed_chunk", mixed_chunk_fn, armable=text_only
            )
        self._set_chunk_widths()
        self._decode_block_fn = self.perf.wrap("decode_block", decode_block_fn)

        # Speculative decoding (ISSUE 5): one verify step = ONE batched
        # forward over each slot's [K+1] candidate block (committed last
        # token + K host-drafted candidates) + the accept/reject kernel +
        # the KV rollback commit — all inside one jit call. Emits 1..K+1
        # tokens per slot per dispatch. K is static (fixed per process) so
        # the program compiles once; the recompile tripwire wraps it like
        # every other entry point.
        spec_k = self._resolve_spec_k()
        if not hasattr(mod, "verify_step"):
            # pp>1 routes decode through parallel/pipeline.py, which has
            # no verify schedule yet — serve exact non-speculative decode
            # rather than failing at the first request
            if spec_k:
                log.info("speculative decoding disabled: no verify_step "
                         "for this decode path", model=mc.name)
            self._spec_k = 0
        else:
            self._spec_k = spec_k
            if spec_k:
                # draft-model tree drafting (ISSUE 18) when configured and
                # compatible; n-gram prompt-lookup otherwise
                self._drafter = (self._build_model_drafter(spec_k)
                                 or make_drafter())
            self._tree_width = self._resolve_tree_width()
            # the verify program is built even with speculation off so a
            # multi-host follower can replay a liaison's "verify" plan ops
            # regardless of its own env (K comes from the record; nothing
            # compiles unless a verify is actually dispatched)

            @partial(jax.jit, static_argnames=("k1",),
                     donate_argnums=(1, 2, 4, 5, 6, 7))
            def verify_block_fn(params, cache, tokens, active, counts,
                                window, wlen, sp, drafts, dlen, *, k1):
                # candidates [S, K+1]: col 0 is the device's committed
                # last token — the host never needs to know it (a freshly
                # admitted slot's prefill sample stays device-side, same
                # no-sync admission contract as the block path)
                cand = jnp.concatenate([tokens[:, None], drafts], axis=1)
                logits, cache, *stats = mod.verify_step(
                    params, mc, cand, cache, active, mesh=self.mesh,
                    **stats_kw,
                )
                out, n_emit, last, counts, window, wlen, sp = spec_accept(
                    logits, cand, dlen, sp, counts, window, wlen, active,
                    mc.vocab_size,
                )
                tokens = jnp.where(active, last, tokens)
                # commit accepted length; rejected candidate rows roll back
                cache = rollback_to_length(
                    cache,
                    jnp.minimum(cache.lengths + n_emit, cache.max_context),
                )
                if self._hybrid:
                    # the state's commit: of the launch's pending rows the
                    # accepted ones count (rollback's counterpart)
                    cache = mod.commit_verify(cache, n_emit, active)
                # block protocol: [K+2, S] — row 0 = block-input tokens
                # (a just-admitted slot's prefill sample), rows 1..K+1 the
                # emitted tokens, valid up to n_emit per slot
                block = jnp.concatenate([cand[:, :1].T, out])
                if stats:  # behind the per-slot counts: [S + 2]
                    n_emit = jnp.concatenate([n_emit, *stats])
                return block, n_emit, tokens, cache, counts, window, wlen, sp

            self._verify_fn = self.perf.wrap("verify_block", verify_block_fn)

            # Tree verification (ISSUE 18): one program per draft-tree
            # TOPOLOGY (parents tuple) — static per process for the local
            # drafter, but a follower replaying a liaison's "verify_tree"
            # plan op rebuilds the fn from the record's parents, so the
            # hosts never need to agree on env knobs. The depth/ancestor
            # arrays are jit-closure constants; per-slot raggedness
            # travels as the node-validity operand (data, not shape), so
            # steady state compiles each topology exactly once.
            self._tree_fns: dict[tuple, Any] = {}

            def _tree_fn_for(parents):
                key = tuple(int(p) for p in parents)
                fn = self._tree_fns.get(key)
                if fn is not None:
                    return fn
                parents_np = np.asarray(key, np.int32)
                depths = tree_depths(parents_np)
                anc = tree_ancestor_mask(parents_np)

                @partial(jax.jit, donate_argnums=(1, 2, 4, 5, 6, 7))
                def verify_tree_fn(params, cache, tokens, active, counts,
                                   window, wlen, sp, drafts, valid):
                    # candidates [S, N]: col 0 = the device's committed
                    # last token (tree root), cols 1.. = drafted nodes in
                    # topological order. Node i's KV is written
                    # optimistically at storage row lengths + i; its
                    # LOGICAL position is lengths + depth[i] (rope +
                    # ancestor-masked attention inside verify_step).
                    cand = jnp.concatenate([tokens[:, None], drafts],
                                           axis=1)
                    logits, cache, *stats = mod.verify_step(
                        params, mc, cand, cache, active, mesh=self.mesh,
                        tree_pos=depths, tree_mask=anc, **stats_kw,
                    )
                    (out, path, n_emit, last, counts, window, wlen,
                     sp) = spec_accept_tree(
                        logits, cand, parents_np, valid, sp, counts,
                        window, wlen, active, mc.vocab_size,
                    )
                    tokens = jnp.where(active, last, tokens)
                    # compact the accepted root-to-leaf path over the
                    # optimistic rows, then roll forward — rejected
                    # branches vanish without ever touching host state
                    cache = commit_tree_path(cache, path, active)
                    cache = rollback_to_length(
                        cache,
                        jnp.minimum(cache.lengths + n_emit,
                                    cache.max_context),
                    )
                    # block protocol: [N+1, S], same contract as the
                    # chain path (row 0 = block-input tokens)
                    block = jnp.concatenate([cand[:, :1].T, out])
                    if stats:
                        n_emit = jnp.concatenate([n_emit, *stats])
                    return (block, n_emit, tokens, cache, counts, window,
                            wlen, sp)

                fn = self.perf.wrap("verify_tree", verify_tree_fn)
                self._tree_fns[key] = fn
                return fn

            self._tree_fn_for = _tree_fn_for

    # ------------------------------------------------------------ admission

    def submit(self, req: GenerationRequest) -> None:
        if self.embedding_only:
            self._fail(req, f"{self.cfg.name} is an embedding model; "
                            "it does not support generation", retryable=False)
            return
        if req.images and not self.cfg.vision:
            # images travel the full protocol (API-surface parity with the
            # reference's Ollama passthrough); capability is per-model.
            # Loud reject > silently ignoring pixels the client sent.
            self._fail(req, f"{self.cfg.name} does not support image inputs",
                       retryable=False)
            return
        with self._lock:
            if len(self._pending) >= self.config.max_queue:
                raise RuntimeError("engine queue full")
            if not req.t_submit_ns:  # a requeue keeps its first stamp
                req.t_submit_ns = time.perf_counter_ns()
            self._pending.append(req)
        with self._work:
            self._work.notify_all()

    def _tokenize(self, req: GenerationRequest) -> list[int]:
        """The prompt's ids, never none: an admission is at least one
        launch of one token, so an empty raw prompt is its BOS alone
        (llama.cpp's rule)."""
        if req.prompt_ids is not None:
            ids = list(req.prompt_ids)
        else:
            ids = self.tokenizer.encode(req.prompt or "", add_bos=not req.raw)
        return ids or [self.tokenizer.bos_id or 0]

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _fail(self, req: GenerationRequest, msg: str, retryable: bool = True) -> None:
        log.warning("request rejected", id=req.id, reason=msg)
        res = GenerationResult(id=req.id, done_reason="error", error=msg,
                               retryable=retryable)
        if req.on_chunk:
            req.on_chunk("", True, res)

    def _try_admit(self) -> bool:
        """Admit one pending request into a free slot. Returns True if
        admitted (caller loops until False)."""
        with self._lock:
            if not self._pending or not self._free_slots:
                return False
            req = self._pending.popleft()
        # marked only once a request was popped: the phase's count is
        # the number of admissions tried
        self._clock.mark("admit", stage="tokenize", request=req.id)
        t_pop = time.perf_counter_ns()
        wait_ns = t_pop - req.t_submit_ns if req.t_submit_ns else 0
        ids = self._tokenize(req)
        images = list(req.images or [])
        # decode resume (ISSUE 9): tokens a previous attempt already
        # generated join the PROMPT for prefill/alloc (so a cached or
        # migrated prefix covers them) but seed the slot's generated
        # state below — vision requests can't resume (their KV encodes
        # spliced pixels token ids alone don't address)
        resume = [] if images else [int(t) for t in req.resume_ids or []]
        if resume:
            ids = ids + resume
        if images:
            try:
                ids = self._expand_image_tokens(ids, len(images))
            except ValueError as e:
                self._fail(req, str(e), retryable=False)
                return True
        elif (
            self.cfg.vision and req.prompt_ids is not None
            and self.cfg.vision_cfg
            and self.cfg.vision_cfg.image_token in ids
        ):
            # Ollama `context` round-trip from an image turn: the expanded
            # image-token run is in the context but the pixels are not.
            # Prefilling placeholder embeddings would silently answer
            # about an image the model cannot see — fail loudly instead.
            self._fail(req, "context contains image tokens; follow-ups on "
                            "image conversations must re-send the images",
                       retryable=False)
            return True
        opts = req.options or {}
        # num_ctx caps THIS request's context (Ollama option; engine-wide
        # max_context still bounds it) — VERDICT r03 weak #7
        num_ctx = int(opts.get("num_ctx") or 0)
        eff_ctx = (
            min(num_ctx, self.max_context) if num_ctx > 0 else self.max_context
        )
        # floor of 2: one prompt token + one generated; num_ctx=1 would
        # also make the truncation slice ids[-0:] a no-op
        eff_ctx = max(eff_ctx, 2)
        if len(ids) >= eff_ctx:
            ids = ids[-(eff_ctx - 1):]  # Ollama truncates from the left
            if images:
                vc = self.cfg.vision_cfg
                if ids.count(vc.image_token) != len(images) * vc.num_patches:
                    # truncation cut into an image span — the splice would
                    # misalign patch rows; loud failure beats garbage
                    self._fail(req, "context window too small for image "
                                    "inputs", retryable=False)
                    return True
        self._clock.stage(None)
        num_predict = int(opts.get("num_predict", -1))
        want = (
            # resumed tokens are already in `ids`; capacity reserves only
            # the REMAINING budget so resume matches the original reservation
            len(ids) + max(num_predict - len(resume), 0)
            if num_predict >= 0
            else eff_ctx
        )
        want = min(max(want, len(ids) + 1), eff_ctx)
        if not self.alloc.fits_slot_cap(want):
            self._fail(req, f"context {want} exceeds slot capacity")
            return True
        slot = self._free_slots[-1]
        # longest cached prefix first (pins matched pages via refcount),
        # then allocate the remainder. Images are excluded — token ids
        # alone don't address spliced pixel embeddings — and sp meshes
        # have no chunked path to resume from (cap forced to 0 there).
        self._clock.stage("match")
        with self._alloc_lock:
            cached = 0
            if self._prefix_cache_cap != 0 and not images:
                cached = self.alloc.match_prefix(slot, ids)
            pages = self.alloc.alloc(slot, want)
            if pages is None:
                # pool exhausted: unpin any matched prefix, requeue at
                # front, wait for a slot to free pages
                self.alloc.free(slot)
                with self._lock:
                    self._pending.appendleft(req)
                return False
            state_plan = (self._plan_state(slot, ids, cached)
                          if self._second else None)
        self._clock.stage(None)
        self._free_slots.pop()

        stop = opts.get("stop") or []
        stop_seqs = [stop] if isinstance(stop, str) else list(stop)
        st = _Slot(req, ids, want, num_predict, stop_seqs, self.tokenizer.eos_ids)
        # observed once a slot and its pages are held: a request put back
        # for want of pages is still waiting
        st.admit_wait_ns = wait_ns
        ADMIT_WAIT_SECONDS.observe(wait_ns / 1e9, model=self.cfg.name)
        if resume:
            # continue, don't restart: generated/detok/text pick up where
            # the lost attempt stopped (num_predict, stop scanning, and
            # eval_count all see the prior tokens), and emission resumes
            # past the chars the client already received
            st.prompt_len = max(len(ids) - len(resume), 0)
            st.generated = list(resume)
            st.text = st.detok.delta(self.tokenizer, st.generated)
            st.emitted_len = max(int(req.resume_sent or 0), 0)

        # per-slot sampler params (Ollama option names)
        seed = opts.get("seed")
        if seed is None:
            seed = self._rng.getrandbits(31)
        # repeat_last_n (llama.cpp penalty_last_n): -1 → the request's
        # context size, 0 → disabled; clamped to the window buffer width
        rl = int(opts.get("repeat_last_n", 64))
        if rl < 0:
            rl = want
        rl = min(rl, self.config.repeat_window)
        upd = {
            "temperature": float(opts.get("temperature", 0.8)),
            "top_k": int(opts.get("top_k", 40)),
            "top_p": float(opts.get("top_p", 0.9)),
            "min_p": float(opts.get("min_p", 0.0)),
            "repeat_penalty": float(opts.get("repeat_penalty", 1.1)),
            "repeat_last_n": rl,
            "seed": int(seed) & 0x7FFFFFFF,
            # the (seed, step) rng chain restarts at the number of draws
            # the lost attempt consumed, so seeded resume samples the
            # same continuation the undisturbed run would have
            "step": len(resume),
        }
        # capped at prompt_len: a warm RESUME's cache match can cover the
        # resumed tokens too, but cached_tokens reports prompt tokens
        # served from cache and must stay <= prompt_eval_count (no-op for
        # ordinary admissions, where prompt_len == len(ids) >= cached)
        st.cached_tokens = min(cached, st.prompt_len)
        row_list = self.alloc.table_row(slot)
        # the pages the slot holds, not the table row's padded width
        st.pages_held = self.alloc.pages_owned(slot)
        t0 = time.perf_counter_ns()
        # the span that caused the program launch; what follows the
        # dispatch in this function (counters, gauges) stays in this phase
        state_meta = {} if state_plan is None else {
            "state_restored_at": cached if state_plan["restore"] >= 0 else 0,
            "replayed_tokens": state_plan["replayed"]}
        self._clock.mark("dispatch_prefill", request=req.id,
                         prompt_tokens=len(ids), cached_tokens=cached,
                         **state_meta)
        with self.dispatch_lock:
            # emit AFTER the dispatch succeeds: a record for a program the
            # liaison never actually issued would make followers replay a
            # phantom computation and silently desync the slice. (If a
            # MULTI-chunk prefill fails partway, the liaison's own stream
            # is already unpaired and the slice-failure machinery tears the
            # group down — there is no cheap reconciliation for that.)
            self._dispatch_prefill(slot, ids, row_list, upd, images=images,
                                   cached=cached, state_plan=state_plan)
            self._clock.stage("book")
            if self.plan_sink is not None:
                # SNAPSHOT the ids: the list is also _Slot.ids, which
                # _ingest APPENDS generated tokens to — a by-reference
                # record serialized after the first ingest would make
                # followers prefill phantom tokens and silently desync
                # the slice (caught by the vision replay test comparing
                # follower state against the liaison's actual pool)
                rec = {"op": "admit", "slot": slot, "ids": list(ids),
                       "row": list(row_list), "sp": dict(upd),
                       "cached": cached}
                if images:
                    # raw base64 payload: followers re-run the
                    # deterministic preprocessing + encode themselves
                    rec["images"] = images
                self.plan_sink(rec)
        # dispatch wall time only — the prefill runs asynchronously and its
        # sampled token first becomes host-visible in the next block fetch;
        # t_prefill_ns is finalized there (admission → first-token)
        st.t_prefill_ns = time.perf_counter_ns() - t0
        if self._use_mixed:
            # the last chunk's own launch: no launch more before the stream
            # has its first token
            st.joined_gen, st.first_row = self._gen, 1
        else:
            st.joined_gen = self._gen + 1  # first block dispatched after this
        self._slots[slot] = st
        _TOKENS_TOTAL.inc(len(ids) - cached, model=self.cfg.name,
                          kind="prefill")
        if cached:
            _TOKENS_TOTAL.inc(cached, model=self.cfg.name,
                              kind="prefill_cached")
        _FLIGHTREC.record("engine", "admit", model=self.cfg.name,
                          request=req.id, slot=slot, promptTokens=len(ids),
                          cachedTokens=cached)
        self._update_kv_gauges()
        self._admits.append((time.perf_counter_ns() - t_pop) / 1e9)
        return True

    def _plan_state(self, slot: int, ids: list[int],
                    cached: int) -> dict[str, Any]:
        """A hybrid admission's plan for the state (under _alloc_lock,
        after alloc): the snapshot to restore into the slot (-1: none; the
        pages' match was already cut to it, `cached`), and the page
        boundaries the prompt's launches pass at which the state is saved,
        each with its snapshot entry: where this asker's own page match
        ended if no snapshot stood there (a later asker of the same prefix
        finds one), and the prompt's last two (a prompt that shares this
        one up to a question at its end matches at one of them). A launch
        hands back `SAVES` states at most, so a boundary past that many in
        one launch (in the order above) is not planned, and nothing is
        registered that no launch writes. Plain host integers: a record
        of the admit plan."""
        found, _kept, entry = self.alloc.state_match(slot)
        ps = self.config.page_size
        last = len(ids) // ps * ps
        want: list[int] = []
        per_launch: dict[int, int] = {}
        for b in dict.fromkeys((found, last, last - ps)):
            # _dispatch_prefill's launches: (cached + i c, cached + (i + 1) c]
            launch = (b - cached - 1) // self._chunk_len
            if b > cached and per_launch.get(launch, 0) < self.mod.SAVES:
                per_launch[launch] = per_launch.get(launch, 0) + 1
                want.append(b)
        saves: list[tuple[int, int]] = []
        if want and self.alloc.snapshots:
            keys = self.alloc.chain_keys(ids, n_pages=max(want) // ps)
            entries = self.alloc.snapshot_entries(
                [keys[b // ps - 1] for b in want])
            saves = [(b, e) for b, e in zip(want, entries) if e >= 0]
        return {"restore": entry, "replayed": found - cached, "saves": saves}

    def _update_kv_gauges(self) -> None:
        free = self.alloc.free_pages
        cached = self.alloc.cached_pages
        _KV_PAGES_FREE.set(free, model=self.cfg.name)
        _KV_PAGES_CACHED.set(cached, model=self.cfg.name)
        # per-tier residency (ISSUE 11): hbm = reuse-LRU pages at pool
        # bytes/page, host = encoded bytes actually held by the tier
        kv_bytes = self.cache.pool_nbytes
        bpp = kv_bytes / max(self.config.num_pages, 1)
        tier = self.host_tier
        set_tier_gauges(
            self.cfg.name, cached, int(cached * bpp),
            tier.pages if tier is not None else 0,
            tier.bytes_used if tier is not None else 0,
        )
        # "used" = pages referenced by live requests; cached-but-evictable
        # pages are their own series so dashboards don't read a warm cache
        # as pool pressure
        _KV_PAGES_USED.set(self.config.num_pages - free - cached,
                           model=self.cfg.name)
        total = self.alloc.hits + self.alloc.misses
        if total:
            _PREFIX_HIT_RATE.set(self.alloc.hits / total, model=self.cfg.name)
        if self._second:
            _STATE_SNAP_USED.set(self.alloc.snapshots_used,
                                 model=self.cfg.name)
        if self._ring:
            # rows a live slot's window layers hold in their rings (a slot
            # fills its ring a page at a time, then wraps), beside the rows
            # the pages it owns would hold in those layers under one table
            ps = self.config.page_size
            owned = [self.alloc.pages_owned(s) for s in list(self._slots)]
            ring = self.cache.win.ring_pages
            _WINDOW_ROWS.set(sum(min(n, ring) for n in owned) * ps,
                             model=self.cfg.name, held="ring")
            _WINDOW_ROWS.set(sum(owned) * ps,
                             model=self.cfg.name, held="table")

    def _expand_image_tokens(self, ids: list[int], n_images: int) -> list[int]:
        """Expand image placeholders to num_patches copies each (the splice
        contract, models/llava.py). Prompts carrying explicit placeholders
        (HF-style `<image>`) must have exactly one per image; marker-free
        prompts (the Ollama API shape — images as a side list) get all
        image spans inserted up front, after BOS, matching Ollama's
        images-before-prompt layout."""
        vc = self.cfg.vision_cfg
        if vc is None:
            raise ValueError(f"{self.cfg.name}: vision model without "
                             "vision_cfg")
        tok, n = vc.image_token, vc.num_patches
        count = ids.count(tok)
        if count == 0:
            at = 1 if (ids and ids[0] == self.tokenizer.bos_id) else 0
            return ids[:at] + [tok] * (n * n_images) + ids[at:]
        if count == n_images * n:
            # already expanded — an Ollama `context` round-trip of a prior
            # image turn (st.ids carries the expanded runs) with the
            # images re-sent; splice positions line up as-is
            return list(ids)
        if count != n_images:
            raise ValueError(
                f"prompt has {count} image placeholder(s) for "
                f"{n_images} image(s)"
            )
        out: list[int] = []
        for t in ids:
            out.extend([tok] * n if t == tok else [t])
        return out

    def _image_embeds(self, images: list[str]) -> jnp.ndarray:
        """base64 images → flattened projected patch rows [n*N, E]."""
        from gridllm_tpu.engine.images import preprocess_images

        px = preprocess_images(images, self.cfg.vision_cfg.image_size)
        return self._encode_fn(self.params, px)

    def _chunk_width(self, left: int, start: int) -> int:
        """The width of the next chunk launch, `left` prompt tokens still
        to go through the model behind `start` that stand in the slot's
        pages: the narrower width that holds all that is left (a first
        chunk's, or the one behind a prefix), else the full chunk. The one
        place the choice is made, from host integers of the admit plan and
        the widths _set_chunk_widths read off the model, so a follower's
        replay picks the same program."""
        fit = self._chunk_narrow if start else self._chunk_first
        return fit if left <= fit < self._chunk_len else self._chunk_len

    def _dispatch_prefill(self, slot: int, ids: list[int],
                          row_list: list[int], upd: dict[str, Any],
                          images: list[str] | None = None,
                          cached: int = 0,
                          state_plan: dict[str, Any] | None = None) -> None:
        """The device half of admission — everything a multi-host follower
        must replay identically: sampler row update + prefill dispatch.
        All inputs are plain host values (the admit plan record). `cached`
        (page-aligned, from match_prefix) marks the prompt prefix whose KV
        pages are already installed in `row_list`: those tokens skip the
        model forward (window bookkeeping only) and chunked prefill starts
        at the first uncached token."""
        # every argument below is host numpy at its final dtype: the
        # jitted call's argument path transfers it, where an eager jnp
        # scalar or array constructor would be a program of its own
        slot_ = np.int32(slot)
        restore = bool(state_plan) and state_plan["restore"] >= 0
        # the stage's jitted calls, a fixed number whatever `cached` is:
        # the sampler row and the window's tail in one program, and the
        # restore where the family has a second-kind cache
        self._clock.stage("seed", launches=1 + restore)
        w = self.config.repeat_window
        (self.sampling, self.window, self.wlen, self.counts) = (
            self._admit_seed_fn(
                self.sampling, self.window, self.wlen, self.counts,
                _seed_record(slot, upd, ids[max(0, cached - w):cached], w)))
        _SEED_LAUNCHES.inc(model=self.cfg.name)
        img_flat = self._image_embeds(images) if images else None
        img_tok = self.cfg.vision_cfg.image_token if images else -1
        # counts[slot] is cleared INSIDE prefill_fn / prefill_chunk_fn —
        # no host-side clear here (it would be a dead full-row rewrite)
        row = _host_i32(row_list, len(row_list))
        saves = state_plan["saves"] if state_plan else []
        if restore:
            self.cache = self._state_restore_fn(
                self.cache, slot_, np.int32(state_plan["restore"]),
                np.int32(cached))
            _SEED_LAUNCHES.inc(model=self.cfg.name)
        if (self._use_mixed or cached
                or (self._use_chunked and len(ids) > self._chunk_len)):
            # chunked prefill: repeated invocations of ONE fixed-shape
            # program against the growing cached prefix — no per-length
            # traces, no padding to a distant bucket (VERDICT.md #4). An
            # engine with a mixed step admits every prompt this way
            c = self._chunk_len
            for s0 in range(cached, len(ids), c):
                part = ids[s0 : s0 + c]
                final = s0 + c >= len(ids)
                # an image prompt keeps the one width: a second splice
                # program would compile inside a user's request
                width = (self._chunk_width(len(ids) - s0, s0)
                         if img_flat is None else c)
                # one stretch a launch: its host arrays, its arguments
                # placed, the jitted call returning
                self._clock.stage("chunk", width=width, start=s0)
                padded = _host_i32(part, width)
                embeds = None
                if img_flat is not None:
                    off = sum(1 for t in ids[:s0] if t == img_tok)
                    embeds = self._splice_fn(
                        self.params, padded, img_flat, np.int32(off)
                    )
                if self._use_mixed:
                    self._clock.annotate(**self._expert_meta(
                        "chunk", width + self.config.max_slots))
                _CHUNK_LAUNCHES.inc(model=self.cfg.name, width=str(width))
                _CHUNK_TOKENS.inc(len(part), model=self.cfg.name, kind="real")
                _CHUNK_TOKENS.inc(width, model=self.cfg.name, kind="padded")
                if self._use_mixed:
                    # ragged mixed step (ISSUE 6): this chunk AND one
                    # decode token for every active slot share a single
                    # launch — running streams keep generating while the
                    # prompt prefills; the decode rows ride _inflight and
                    # are ingested like any other block
                    self._dispatch_mixed_chunk(
                        padded, s0, len(part), slot, row, final, embeds,
                        self._state_io(saves, s0, len(part)),
                    )
                    continue
                (self.cache, self.counts, self.window, self.wlen,
                 self.tokens, self.active, self.sampling) = (
                    self._prefill_chunk_fn(
                        self.params, padded, self.cache, self.counts,
                        self.window, self.wlen, self.tokens, self.active,
                        self.sampling, np.int32(s0), np.int32(len(part)),
                        slot_, row, np.bool_(final), embeds=embeds,
                    )
                )
                self._clock.fed()
        else:
            width = self._bucket_for(len(ids))
            self._clock.stage("chunk", width=width, start=0)
            padded = _host_i32(ids, width)
            embeds = None
            if img_flat is not None:
                embeds = self._splice_fn(
                    self.params, padded, img_flat, np.int32(0)
                )
            (self.cache, self.counts, self.window, self.wlen, self.tokens,
             self.active, self.sampling) = self._prefill_fn(
                self.params, padded, self.cache, self.counts,
                self.window, self.wlen, self.tokens, self.active,
                self.sampling, np.int32(len(ids)), slot_, row,
                embeds=embeds,
            )
            self._clock.fed()
        # the last launch's width and the tokens that went through the
        # model, on the gridllm.dispatch_prefill span (free off a capture)
        self._clock.annotate(width=width, tokens=len(ids) - cached)

    def apply_plan_op(self, rec: dict[str, Any]) -> None:
        """Follower-side replay of one liaison plan record (multi-host
        SPMD lockstep — see plan_sink). Must be called in record order
        from ONE thread. Followers never fetch results; their dispatches
        pace themselves against the shared collectives."""
        op = rec["op"]
        if op == "admit":
            self._dispatch_prefill(
                int(rec["slot"]), [int(i) for i in rec["ids"]],
                [int(p) for p in rec["row"]], dict(rec["sp"]),
                images=list(rec.get("images") or []) or None,
                cached=int(rec.get("cached", 0)),
            )
            self._inflight.clear()  # ragged mixed blocks: replay never fetches
        elif op == "block":
            self._dispatch_block(int(rec["k"]))
            self._inflight.clear()  # replay never fetches
        elif op == "verify":
            # drafts are plain host ints in the record, so follower device
            # state evolves bit-identically to the liaison's
            self._dispatch_verify(
                np.asarray(rec["drafts"], np.int32),
                np.asarray(rec["dlen"], np.int32),
            )
            self._inflight.clear()  # replay never fetches
        elif op == "verify_tree":
            # the record carries the tree topology, so the follower
            # rebuilds the exact program regardless of its own env
            valid = np.asarray(rec["valid"], bool)
            self._dispatch_verify_tree(
                np.asarray(rec["drafts"], np.int32), valid,
                np.asarray(rec["parents"], np.int32),
                np.zeros(len(valid), np.int32),  # nothing is ingested here
            )
            self._inflight.clear()  # replay never fetches
        elif op == "deact":
            self.active = self._deactivate_fn(
                self.active, np.int32(rec["slot"]))
        elif op == "embed":
            self._embed_fn(self.params, np.asarray(rec["tok"], np.int32),
                           np.asarray(rec["lens"], np.int32))  # result unused
        elif op == "reset":
            self.reset_device_state()
        else:
            raise ValueError(f"unknown plan op: {op!r}")

    # ------------------------------------------------------------ stepping

    def _ingest(self, slot: int, st: _Slot, tok: int) -> None:
        """Record one sampled token; emit text; finish the slot if done."""
        if st.shadow >= 0:
            # the runner ran ahead and verified no draft: would the
            # drafter's first proposal for this token have been accepted?
            self._spec_hits += tok == st.shadow
            st.shadow = -1
        if st.export_only:
            # disaggregated prefill (ISSUE 7): the first host-visible token
            # proves the whole prompt's KV is written — finish NOW with
            # reason "export" so _finish registers the prompt's full pages
            # in the prefix cache (the export source). The sampled token is
            # deliberately discarded (not detokenized, not streamed): the
            # decode worker re-prefills the prompt tail and samples it
            # itself, which is what keeps the streams bit-identical.
            st.generated.append(tok)
            st.ids.append(tok)
            self._finish(slot, st, "export")
            return
        st.generated.append(tok)
        st.ids.append(tok)
        done_reason = None
        if tok in st.eos_ids:
            st.generated.pop()  # EOS is not part of the visible output
            st.ids.pop()
            done_reason = "stop"
        else:
            st.text += st.detok.delta(self.tokenizer, st.generated)
            for s in st.stop_seqs:  # stop sequences: trim at first match
                i = st.text.find(s)
                if i >= 0:
                    st.text = st.text[:i]
                    done_reason = "stop"
                    break
        if done_reason is None:
            if 0 <= st.num_predict <= len(st.generated):
                done_reason = "length"
            elif st.prompt_len + len(st.generated) >= st.capacity:
                # capacity is allocated in full at admission (alloc never
                # returns partial); growing the page table here would race
                # in-flight decode blocks holding the old table (their
                # writes at grown positions were sentinel-dropped already)
                done_reason = "length"
        if done_reason is not None:
            self._finish(slot, st, done_reason)
            return
        # the token SURVIVED (no finish) — publish it on the resume
        # watermark at the request's cadence (every write copies the full
        # generated list, so per-token would be O(n^2)). Finishing tokens
        # are deliberately excluded: a resume must always have at least
        # one token left to generate, or the replacement worker could
        # overshoot num_predict/EOS.
        cadence = st.req.snapshot_every
        if cadence > 0 and len(st.generated) % cadence == 0:
            st.snapshot = (list(st.generated), st.text)
        # emit finalized text only: hold back anything that may yet turn
        # into a stop sequence (emitted chunks cannot be retracted)
        safe = len(st.text) - st.holdback()
        if safe > st.emitted_len and st.req.on_chunk:
            delta = st.text[st.emitted_len : safe]
            st.emitted_len = safe
            self._clock.stage("emit")
            st.req.on_chunk(delta, False, None)
            self._clock.stage(None)

    def _finish(self, slot: int, st: _Slot, reason: str, error: str = "") -> None:
        now = time.perf_counter_ns()
        last_delta = st.text[st.emitted_len :]
        st.emitted_len = len(st.text)
        # final page count (decode growth included) for page-occupancy
        # attribution; the admission-time count is the floor
        with self._alloc_lock:
            try:
                st.pages_held = max(st.pages_held,
                                    self.alloc.pages_owned(slot))
            except Exception:
                pass
        res = GenerationResult(
            id=st.req.id,
            error=error,
            text=st.text,
            token_ids=list(st.generated),
            context=list(st.ids),
            done_reason=reason,
            prompt_eval_count=st.prompt_len,
            cached_tokens=st.cached_tokens,
            prompt_eval_duration_ns=st.t_prefill_ns,
            admit_wait_ns=st.admit_wait_ns,
            eval_count=len(st.generated),
            eval_duration_ns=(now - st.t_first_decode) if st.t_first_decode else 0,
            load_duration_ns=self.load_duration_ns,
            total_duration_ns=now - st.t_start,
            spec_proposed=st.spec_proposed,
            spec_accepted=st.spec_accepted,
            decode_device_s=st.device_s,
            kv_page_s=max(st.pages_held, 1)
            * max(time.time() - st.t_admit_wall, 0.0),
        )
        with self.dispatch_lock:
            self.active = self._deactivate_fn(self.active, np.int32(slot))
            if self.plan_sink is not None:  # after-success; see _try_admit
                self.plan_sink({"op": "deact", "slot": slot})
        # Release pages into the prefix-cache reuse LRU, registering full
        # pages of the final context (prompt + generated). The LAST token
        # is excluded: a token's KV is written when it is INPUT to the next
        # decode step, and for the final sampled token that step may not
        # have been dispatched — every earlier position is provably written
        # (its successor was sampled and ingested). An "error" finish may
        # leave poisoned device state, so its pages are never registered
        # (reset_device_state rebuilds the allocator wholesale anyway).
        # Vision requests never register either: their KV encodes spliced
        # pixel embeddings that identical token ids (image-token runs) do
        # not capture, so a token-chain key would collide across images.
        register = reason != "error" and not st.req.images
        with self._alloc_lock:
            self.alloc.free(slot, st.ids[:-1] if register else None)
        del self._slots[slot]
        self._update_kv_gauges()
        self._free_slots.append(slot)
        if self._drafter is not None and hasattr(self._drafter, "reset_slot"):
            # drafters keep a per-slot view of the history (the draft
            # model's KV prefix, the n-gram drafter's int32 copy); the
            # next request reusing this slot starts from scratch
            self._drafter.reset_slot(slot)
        _FLIGHTREC.record("engine", "finish", model=self.cfg.name,
                          request=st.req.id, slot=slot, reason=reason,
                          tokens=len(st.generated))
        if not self._perf_armed and reason in ("stop", "length"):
            # first naturally completed request ⇒ the prefill/decode
            # programs its shapes needed are compiled — steady state from
            # here; new signatures are flagged (legit new-bucket compiles
            # still happen, bounded by |buckets|, and stay under the
            # storm budget)
            self._perf_armed = True
            self.perf.arm()
        if st.req.on_chunk:
            # (a cancel finishes a stream from ctl: not a stage of that)
            self._clock.stage("emit", of="ingest")
            st.req.on_chunk(last_delta, True, res)
            self._clock.stage(None)

    def _dispatch_block(self, k: int) -> None:
        """Dispatch one fused k-step decode block (no host sync)."""
        with self.dispatch_lock:
            _BATCH_OCCUPANCY.observe(len(self._slots), model=self.cfg.name)
            self._gen += 1
            if self._gen % _FLIGHT_SAMPLE == 0:  # sampled step-loop record
                _FLIGHTREC.record("engine", "block", model=self.cfg.name,
                                  gen=self._gen, k=k,
                                  slots=len(self._slots),
                                  pending=len(self._pending))
            (out, self.tokens, self.cache, self.counts, self.window,
             self.wlen, self.sampling) = self._decode_block_fn(
                self.params, self.cache, self.tokens, self.active,
                self.counts, self.window, self.wlen, self.sampling, k=k,
            )
            self._inflight.append((self._gen, out, k, None))
            self._clock.fed()
            if self.plan_sink is not None:  # after-success; see _try_admit
                self.plan_sink({"op": "block", "k": k})

    def _state_io(self, saves: list[tuple[int, int]], start: int,
                  length: int):
        """What a hybrid family's chunk launch over [start, start +
        length) is told of the admission's saves: the positions it passes
        and their snapshot entries, padded with -1; None for a family
        without a state."""
        if not self._second:
            return None
        mine = [s for s in saves if start < s[0] <= start + length]
        pos = np.full((self.mod.SAVES,), -1, np.int32)
        idx = np.full((self.mod.SAVES,), -1, np.int32)
        for i, (b, e) in enumerate(mine):     # SAVES at most: _plan_state
            pos[i], idx[i] = b, e
        return pos, idx

    def _dispatch_mixed_chunk(self, padded, start: int, length: int,
                              slot: int, row, is_final: bool,
                              embeds, state_io=None) -> None:
        """Dispatch one ragged mixed step (chunk + decode, ISSUE 6). Runs
        under dispatch_lock (called from _dispatch_prefill). The [2, S]
        decode-token block joins _inflight with its own generation —
        fetched later by the normal block drains, no host sync here."""
        self._gen += 1
        (out, self.cache, self.counts, self.window, self.wlen, self.tokens,
         self.active, self.sampling) = self._mixed_chunk_fn(
            self.params, padded, self.cache, self.counts, self.window,
            self.wlen, self.tokens, self.active, self.sampling,
            np.int32(start), np.int32(length), np.int32(slot), row,
            np.bool_(is_final), embeds=embeds, state_io=state_io,
        )
        self._inflight.append((self._gen, (out, None), 1, None))
        self._clock.fed()

    def _fetch_oldest(self) -> int:
        """Fetch + ingest the oldest in-flight block, a decode / mixed
        block or a verify launch each by its own rule — the ONE copy of
        the block fetch protocol: step()'s sync path, _pump_once's
        pipelined pop, the speculative step's own launch and the
        admission-block drains all go through here, so blocks are
        ingested in dispatch order whatever their kinds. Observes
        per-fused-step duration (fetch+ingest wall over the block's step
        count). Returns the block's generation."""
        gen, out, blk, dlen = self._inflight.popleft()
        t0 = time.perf_counter()
        self._clock.mark("fetch", stage="wait")
        # the ONE declared block-fetch sync point (host-sync-discipline),
        # a stage each: until the launch has finished, then its tokens' way
        # to the host. The copy device_get begins with is queued ahead of
        # the wait, as it was before the two were split: it starts when
        # the launch ends, with no wake-up of this thread in between
        for leaf in jax.tree.leaves(out if dlen is None else out[0]):
            leaf.copy_to_host_async()
        jax.block_until_ready(out)  # sync-ok
        ready = self._clock.stage("copy")
        if not self._inflight:
            self._clock.starve(ready)
        if dlen is None:
            # (tokens, a routed family's decode-block statistics or None)
            raw, stats = jax.device_get(out)  # sync-ok
            self._mark_ingest()
            if stats is not None:
                self._count_step_stats(stats)
            self._ingest_block(gen, raw)
        else:
            block, n_emit = out
            raw = np.asarray(jax.device_get(block))  # sync-ok
            n_np = np.asarray(jax.device_get(n_emit))  # sync-ok
            self._fetch_waits.append(
                (self._mark_ingest(), bool(self._inflight)))
            self._count_step_stats(n_np[self.config.max_slots:])
            self._ingest_spec(gen, raw, n_np, dlen)
        _STEP_DURATION.observe(
            (time.perf_counter() - t0) / max(blk, 1), model=self.cfg.name)
        return gen

    def _dispatch_verify(self, drafts: np.ndarray, dlen: np.ndarray) -> None:
        """Dispatch one speculative verify block: [S, K] host drafts (+
        per-slot valid count) against the device's committed last tokens.
        No host sync — the fetch is _fetch_oldest's."""
        with self.dispatch_lock:
            _BATCH_OCCUPANCY.observe(len(self._slots), model=self.cfg.name)
            self._gen += 1
            if self._gen % _FLIGHT_SAMPLE == 0:
                _FLIGHTREC.record("engine", "verify", model=self.cfg.name,
                                  gen=self._gen, k=int(drafts.shape[1]),
                                  slots=len(self._slots),
                                  drafted=int(dlen.sum()),
                                  pending=len(self._pending))
            (block, n_emit, self.tokens, self.cache, self.counts,
             self.window, self.wlen, self.sampling) = self._verify_fn(
                self.params, self.cache, self.tokens, self.active,
                self.counts, self.window, self.wlen, self.sampling,
                drafts, dlen,
                k1=int(drafts.shape[1]) + 1,  # from the record: follower
            )                                 # replay may differ from env K
            self._inflight.append((self._gen, (block, n_emit), 1, dlen))
            self._clock.fed()
            if self.plan_sink is not None:  # after-success; see _try_admit
                self.plan_sink({"op": "verify", "drafts": drafts.tolist(),
                                "dlen": dlen.tolist()})

    def _dispatch_verify_tree(self, drafts: np.ndarray, valid: np.ndarray,
                              parents: np.ndarray, dlen: np.ndarray) -> None:
        """Dispatch one TREE verify block (ISSUE 18): [S, N-1] drafted
        node tokens + [S, N] per-slot node validity against the static
        topology `parents`; `dlen` is the chain depth proposed a slot, for
        the ingest's accounting alone. No host sync — the fetch is
        _fetch_oldest's. The plan record carries the topology, so a
        multi-host follower replays the identical program without any
        env agreement (mirrors the chain path's k-from-record rule)."""
        with self.dispatch_lock:
            _BATCH_OCCUPANCY.observe(len(self._slots), model=self.cfg.name)
            self._gen += 1
            if self._gen % _FLIGHT_SAMPLE == 0:
                _FLIGHTREC.record("engine", "verify_tree",
                                  model=self.cfg.name, gen=self._gen,
                                  nodes=int(len(parents)),
                                  slots=len(self._slots),
                                  drafted=int(valid[:, 1:].sum()),
                                  pending=len(self._pending))
            fn = self._tree_fn_for(parents)
            (block, n_emit, self.tokens, self.cache, self.counts,
             self.window, self.wlen, self.sampling) = fn(
                self.params, self.cache, self.tokens, self.active,
                self.counts, self.window, self.wlen, self.sampling,
                drafts, valid,
            )
            self._inflight.append((self._gen, (block, n_emit), 1, dlen))
            self._clock.fed()
            if self.plan_sink is not None:  # after-success; see _try_admit
                self.plan_sink({
                    "op": "verify_tree", "drafts": drafts.tolist(),
                    "valid": valid.tolist(),
                    "parents": [int(p) for p in parents],
                })

    def _step_spec_tree(self, k: int) -> None:
        """One draft-model TREE iteration (ISSUE 18): batched device
        drafting over every live slot, one tree-masked verify dispatch,
        fetch, ragged ingest. The shape of _step_spec's series — the
        next step's drafts depend on this step's emitted tokens — but
        the draft pass itself is one device batch instead of per-slot
        host loops, so it never runs ahead."""
        width = self._tree_width
        parents = tree_topology(k, width)
        n = len(parents)
        s = self.config.max_slots
        drafts = np.zeros((s, n - 1), np.int32) if n > 1 else np.zeros(
            (s, 0), np.int32)
        valid = np.zeros((s, n), bool)
        dlen = np.zeros((s,), np.int32)
        todo: dict[int, list[int]] = {}
        budget: dict[int, int] = {}
        for slot, st in list(self._slots.items()):
            if st.joined_gen > self._gen:
                continue  # first token still device-side
            # don't draft past num_predict (chain-path rule): accepting
            # the whole depth-b chain plus the bonus token lands exactly
            # on the remaining allowance
            b = k if st.num_predict < 0 else max(
                st.num_predict - len(st.generated) - 1, 0)
            todo[slot] = st.ids
            budget[slot] = b
            # every live slot verifies at least the root — a slot the
            # drafter skips (pool overflow / zero budget) still emits its
            # one corrected token, exactly a plain decode step
            valid[slot, 0] = True
        props = self._drafter.draft_batch(todo, k, width) if todo else {}
        # drafter overhead is host+device wall time inside draft_batch,
        # cumulative (bench reads the per-arm delta)
        self.spec_stats["draft_ns"] = int(
            getattr(self._drafter, "draft_ns", 0))
        for slot, (chain, alts) in props.items():
            b = budget[slot]
            depth = min(len(chain), b)
            for i in range(depth):
                drafts[slot, i] = chain[i]
                valid[slot, 1 + i] = True
            if b >= 1 and k >= 1:
                # depth-1 siblings: accepting one emits at most sibling +
                # bonus = 2 tokens, the same bound as a depth-1 chain
                for j, a in enumerate(alts):
                    drafts[slot, k + j] = a
                    valid[slot, k + 1 + j] = True
            # proposed = chain depth, matching the chain drafter's
            # accounting so acceptance rates compare across drafters
            # (siblings are a free second chance, not extra proposals)
            dlen[slot] = depth
        self._mark_launch()
        self._dispatch_verify_tree(drafts, valid, parents, dlen)
        self._fetch_oldest()  # its own launch: _step_spec drained the rest

    def _step_spec(self, ahead_ok: bool = False) -> None:
        """One speculative iteration, on one of two schedules.

        In series: drain what is in flight, draft per slot from
        host-visible history, dispatch the verify block, fetch it, ingest
        the ragged accept counts. Nothing hides the fetch (the next drafts
        depend on this step's tokens); up to K+1 tokens a fetch pay for it.

        Ahead (`ahead_ok`: the runner alone, never step() nor the tree
        drafter): draftless launches, `pipeline_depth` in flight, the
        oldest ingested while the device runs the newest, by the decode
        pipeline's rules (_ingest_spec). Both conditions are observed:
        (1) no first proposal matched over the last `_AHEAD_AFTER`
        launches; the drafter is still asked after each ingest, and a
        first token the next launch then emits (`_Slot.shadow`) brings
        the series back. (2) A launch in flight delays the next
        admission's own launch by what the admission's host work does
        not outlast of it, and the series costs EVERY launch an
        iteration's host work: ahead while the first is no more than the
        second, by the medians of the last eight of each (the wait at a
        verify fetch; an admission; what the phase clock closed of
        ingest, draft and the launch call from one iteration to the next,
        read here alone: an admission, a fetch and an idle wait are other
        phases). Fetched in series, the wait is the whole launch; with
        another launch in flight, the launch less an iteration's host
        work: that work is taken off a wait in series, so both schedules
        judge the wait the launch leaves AHEAD and a switch does not
        change what is compared. What band is left is the series' wake-up
        after a launch (1-2 ms), in which the runner stays as it is.
        Readings: PERF.md 6, PR 54 and PR 60."""
        chain = not getattr(self._drafter, "tree", False)
        # the iteration behind this one, by the runner's clock: its ingest,
        # its draft and its launch call, whatever else it did
        spent = self._clock.spent("ingest", "draft", "dispatch_verify")
        self._host_works.append(spent - self._host_spent)
        self._host_spent = spent
        ahead = False
        if (ahead_ok and chain and self._spec_quiet >= _AHEAD_AFTER
                and self._admits):
            host = median(self._host_works)
            wait = median(w if behind else w - host
                          for w, behind in self._fetch_waits)
            ahead = wait - median(self._admits) <= host
        if ahead:
            depth = max(1, self.config.pipeline_depth)
            slots = self.config.max_slots
            while len(self._inflight) < depth:
                self._launch_verify(np.zeros((slots, self._spec_k), np.int32),
                                    np.zeros((slots,), np.int32), "ahead")
            # down to depth - 1: an admission's mixed launches queue here
            # too, and a new stream's first token is in the last of them
            while len(self._inflight) >= depth:
                gen = self._fetch_oldest()
            for _slot, st, prop in self._draft_live(gen):
                st.shadow = prop[0] if prop else -1
            return
        while self._inflight:
            # mixed admission blocks (and, at the switch back from running
            # ahead, verify launches): their tokens must be host-visible
            # before drafting
            self._fetch_oldest()
        k = self._spec_k
        if not chain:
            self._clock.mark("draft")
            self._step_spec_tree(k)
            return
        drafts = np.zeros((self.config.max_slots, k), np.int32)
        dlen = np.zeros((self.config.max_slots,), np.int32)
        for slot, st, prop in self._draft_live(self._gen):
            if prop and st.num_predict >= 0:
                # don't draft past num_predict: the host would discard the
                # overshoot anyway, and counting it would skew acceptance
                prop = prop[:max(st.num_predict - len(st.generated) - 1, 0)]
            if prop:
                dlen[slot] = len(prop)
                drafts[slot, :len(prop)] = prop
        self._launch_verify(drafts, dlen, "serial")
        self._fetch_oldest()  # its own launch: nothing else is in flight

    def _draft_live(self, gen: int) -> list[tuple[int, _Slot, list[int]]]:
        """Enter ``draft`` and ask the chain drafter about every stream
        the host holds a token of (one whose first token block `gen` or an
        earlier one carried): (slot, stream, proposal). One lookup a live
        slot a launch on either schedule, counted alike."""
        self._clock.mark("draft")
        props = []
        hits = history = 0
        for slot, st in self._slots.items():
            if st.joined_gen > gen:
                continue  # first token still device-side — nothing to extend
            prop = self._drafter.draft(st.ids, self._spec_k, slot)
            hits += bool(prop)
            history += len(st.ids)
            props.append((slot, st, prop))
        if hits:
            _SPEC_LOOKUPS.inc(hits, model=self.cfg.name, outcome="hit")
        if len(props) - hits:
            _SPEC_LOOKUPS.inc(len(props) - hits, model=self.cfg.name,
                              outcome="miss")
        self._clock.annotate(slots=len(props), hits=hits,
                             history_tokens=history)
        return props

    def _launch_verify(self, drafts: np.ndarray, dlen: np.ndarray,
                       mode: str) -> None:
        """One verify launch of the chain path: its ``dispatch_verify``
        mark, its count by schedule, its dispatch."""
        self._mark_launch()
        _SPEC_LAUNCHES.inc(model=self.cfg.name, mode=mode)
        self._dispatch_verify(drafts, dlen)

    def _ingest_spec(self, gen: int, tok_np: np.ndarray,
                     n_emit: np.ndarray, dlen: np.ndarray) -> None:
        """Ragged-block ingest: per slot, rows 1..n_emit[slot] of the
        fetched [K+2, S] block are real emitted tokens (row 0 is the
        block-input protocol row — a just-admitted slot's prefill sample);
        rows past n_emit are rejected-draft junk and never touch host
        state. Stop sequences / EOS / num_predict run per token inside
        _ingest, so a stop landing mid-span truncates exactly as the
        sequential path would."""
        now = time.perf_counter_ns()
        wall = time.time()
        ingested = 0
        emitted_t = 0  # verify-emitted rows only (row 0 is a prefill sample)
        proposed_t = accepted_t = 0
        for slot, st in list(self._slots.items()):
            if st.joined_gen > gen:
                continue
            first_row = 1
            if st.joined_gen == gen:
                first_row = st.first_row
                st.t_prefill_ns = now - st.t_start
            if not st.t_first_decode:
                st.t_first_decode = now
            st.t_last_ingest = wall
            n = int(n_emit[slot])
            prop = int(dlen[slot])
            acc = max(n - 1, 0)
            self._spec_hits += n >= 2  # the first proposal was accepted
            st.spec_proposed += prop
            st.spec_accepted += acc
            proposed_t += prop
            accepted_t += acc
            for r in range(first_row, min(n, tok_np.shape[0] - 1) + 1):
                self._ingest(slot, st, int(tok_np[r, slot]))
                ingested += 1
                emitted_t += 1 if r >= 1 else 0
                if slot not in self._slots:
                    break  # finished mid-span; later rows are post-stop junk
        if ingested:
            _TOKENS_TOTAL.inc(ingested, model=self.cfg.name, kind="decode")
        self._clock.annotate(tokens=ingested)
        m = self.cfg.name
        dk = getattr(self._drafter, "kind", "ngram") or "ngram"
        if proposed_t:
            _SPEC_PROPOSED.inc(proposed_t, model=m, drafter=dk)
            _SPEC_ACCEPT_RATE.observe(accepted_t / proposed_t, model=m,
                                      drafter=dk)
        if accepted_t:
            _SPEC_ACCEPTED.inc(accepted_t, model=m, drafter=dk)
        if proposed_t - accepted_t:
            _SPEC_REJECTED.inc(proposed_t - accepted_t, model=m, drafter=dk)
        stats = self.spec_stats
        stats["steps"] += 1
        stats["proposed"] += proposed_t
        stats["accepted"] += accepted_t
        # row-0 tokens are prefill samples riding the block protocol, not
        # verify output — only rows >= 1 count toward tokens-per-step
        stats["emitted"] += emitted_t
        # the schedule's signal, one reading a verify launch (_step_spec)
        self._spec_quiet = 0 if self._spec_hits else self._spec_quiet + 1
        self._spec_hits = 0

    def _ingest_block(self, gen: int, tok_np: np.ndarray) -> None:
        """Feed one fetched [k+1, S] token block through per-token
        bookkeeping. Row 0 = block-input tokens: consumed only by slots
        whose joined_gen == gen after a prefill launch of their own (their
        prefill sample; a slot a mixed launch admitted reads it from that
        launch's row 1); newer slots (slot reused after this block was
        dispatched) are skipped entirely."""
        k = tok_np.shape[0] - 1
        now = time.perf_counter_ns()
        wall = time.time()
        ingested = 0
        for slot, st in list(self._slots.items()):
            if st.joined_gen > gen:
                continue
            first_row = 1
            if st.joined_gen == gen:
                # first host-visible token: admission → now is the honest
                # prompt-eval (prefill) latency for this request
                first_row = st.first_row
                st.t_prefill_ns = now - st.t_start
            if not st.t_first_decode:
                st.t_first_decode = now
            st.t_last_ingest = wall  # decode-progress mark (batch_state)
            for r in range(first_row, k + 1):
                self._ingest(slot, st, int(tok_np[r, slot]))
                ingested += 1
                if slot not in self._slots:
                    break  # finished mid-block; later rows are post-EOS junk
        if ingested:
            _TOKENS_TOTAL.inc(ingested, model=self.cfg.name, kind="decode")
        self._clock.annotate(tokens=ingested)

    def _drain_ctl(self) -> None:
        while self._ctl:
            op, req_id = self._ctl.popleft()
            for slot, st in list(self._slots.items()):
                if st.req.id == req_id:
                    self._finish(slot, st, op)
                    break

    def step(self) -> bool:
        """One synchronous engine iteration: admit what fits, one decode
        step for all active slots, fetch + ingest. Exact per-token
        semantics (block size 1, no pipelining) — the test/sync driver.
        The serving path is the runner thread (start()/stop()), which uses
        fused blocks and pipelined dispatch. Returns False when idle."""
        try:
            self._clock.mark("ctl")
            self._drain_ctl()
            while self._try_admit():
                pass
            while self._inflight:
                # ragged mixed admission steps enqueue [2, S] blocks; sync
                # semantics = nothing left in flight before this step's
                # own dispatch
                self._fetch_oldest()
            if not self._slots:
                return bool(self._pending)
            if self._spec_k:
                self._step_spec()
                return True
            self._mark_launch()
            self._dispatch_block(1)
            self._fetch_oldest()
            return True
        finally:
            # the time between two calls of a synchronous driver is not
            # the engine's: close the open phase and flush
            self._clock.pause()

    def _mark_launch(self) -> None:
        """Enter ``dispatch_verify`` for one verify / decode-block launch
        (the runner's call sites, not the dispatch functions: a multi-host
        follower replays those off the runner). Counts the context the
        launch's ragged kernel reads: Σ over live slots of context length,
        and the same with each layer's window applied."""
        lens = [len(st.ids) for st in self._slots.values()]
        ctx = sum(lens)
        VERIFY_CTX_TOKENS_TOTAL.inc(ctx, model=self.cfg.name)
        VERIFY_WINDOW_TOKENS_TOTAL.inc(
            float(np.minimum.outer(lens, self._layer_windows).mean(axis=1)
                  .sum()) if self._windowed and lens else ctx,
            model=self.cfg.name)
        kind = "verify" if self._spec_k else "decode"
        self._clock.mark("dispatch_verify", gen=self._gen + 1,
                         slots=len(self._slots), ctx_tokens=ctx,
                         **self._expert_meta(
                             kind, self.config.max_slots * (self._spec_k + 1)))

    def _expert_meta(self, launch: str, rows: int) -> dict[str, str]:
        """The form a routed family's expert layer takes in a launch of
        `rows` rows (models/mixtral.py's rule of the shape), as span meta,
        counted in gridllm_moe_form_rows_total; {} for a dense family."""
        if not self.cfg.num_experts:
            return {}
        form = self._expert_forms.get(rows)
        if form is None:        # a handful of row counts a process
            from gridllm_tpu.models.mixtral import expert_form

            form = self._expert_forms[rows] = expert_form(
                self.cfg, rows, self.mesh)
        MOE_FORM_ROWS_TOTAL.inc(rows, model=self.cfg.name, form=form,
                                launch=launch)
        return {"expert_form": form}

    def _count_step_stats(self, stats: np.ndarray) -> None:
        """A launch's [live rows routed, experts touched] (summed over
        layers), from the launch's own fetch; empty for a dense family.
        A share of the experts (`cfg.experts_held`) adds the live rows'
        picks [on held experts, on absent ones], zero-compute experts
        (`cfg.zero_experts`) [on those]."""
        if len(stats) >= 2:
            MOE_EXPERT_ROWS_TOTAL.inc(int(stats[0]), model=self.cfg.name)
            MOE_EXPERTS_TOUCHED_TOTAL.inc(int(stats[1]), model=self.cfg.name)
        if len(stats) >= 4:
            MOE_PICKS_TOTAL.inc(int(stats[2]), model=self.cfg.name,
                                where="held")
            MOE_PICKS_TOTAL.inc(int(stats[3]), model=self.cfg.name,
                                where="absent")
        if len(stats) >= 5:
            MOE_PICKS_TOTAL.inc(int(stats[4]), model=self.cfg.name,
                                where="zero")

    def _mark_ingest(self) -> float:
        """Leave ``fetch`` for ``ingest``, right after the device_get
        returned. The fetch's measured time, returned, is the runner
        blocked on the device: usage attribution (ISSUE 16) splits it
        evenly across the slots that shared the batch (engine thread owns
        _slots — no lock needed)."""
        waited = self._clock.mark("ingest")
        if self._slots:
            share = waited / len(self._slots)
            for st in self._slots.values():
                st.device_s += share
        return waited

    # ------------------------------------------------------------- runner

    def start(self) -> None:
        """Start the dedicated engine thread (the serving driver). Replaces
        round-3's per-step asyncio.to_thread hop (VERDICT r03 #2): one
        thread owns all device dispatch; submit()/cancel() are the only
        cross-thread entry points."""
        if self._runner is not None:
            return
        self._runner_stop.clear()
        self._runner = threading.Thread(
            target=self._runner_main, name=f"engine-{self.cfg.name}",
            daemon=True
        )
        self._runner.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._runner_stop.set()
        with self._work:
            self._work.notify_all()
        r = self._runner  # local: _run may not touch self._runner (races)
        if r is not None:
            r.join(timeout)
            if not r.is_alive():
                self._runner = None
            # else: keep the reference — start() must NOT spawn a second
            # thread while the old one could still be dispatching

    @property
    def running(self) -> bool:
        return self._runner is not None and self._runner.is_alive()

    def _runner_main(self) -> None:
        """The runner thread: _run, on the phase clock from entry to exit."""
        t_run = time.perf_counter()
        self._clock.mark("idle_wait")
        try:
            with compile_owner(self.cfg.name):
                self._run()
        finally:
            self._clock.pause()
            self.runner_wall_s += time.perf_counter() - t_run

    def _run(self) -> None:
        fail_streak = 0
        while not self._runner_stop.is_set():
            with self._work:
                while not (self._pending or self._slots or self._ctl
                           or self._runner_stop.is_set()):
                    # one observation per stretch of waiting, flushed
                    # while idle so an idle worker's series keeps pace
                    self._clock.mark("idle_wait")
                    self._clock.flush()
                    self._work.wait(timeout=0.5)
            if self._runner_stop.is_set():
                break
            # once an iteration, never per mark: what the last one closed
            self._clock.flush()
            try:
                self._pump_once()
                fail_streak = 0
            except Exception as e:  # noqa: BLE001 — keep serving others
                log.error("engine block failed; aborting in-flight requests",
                          model=self.cfg.name, error=str(e))
                # 1000 chars: a Mosaic or XLA compile error names its
                # cause a few hundred characters in
                _FLIGHTREC.record("engine", "step_failure",
                                  model=self.cfg.name, error=str(e)[:1000],
                                  streak=fail_streak + 1)
                self._inflight.clear()
                self.abort_all(f"engine failure: {e}")
                try:
                    self.reset_device_state()
                except Exception as re:  # noqa: BLE001
                    log.error("device state rebuild failed", error=str(re))
                fail_streak += 1
                if fail_streak >= 3:
                    # thread just exits; `running` turns False via
                    # is_alive() and the worker watchdog drops the model.
                    # (Never touch self._runner from this thread — races
                    # stop().)
                    log.error("engine unrecoverable after repeated failures;"
                              " runner exiting", model=self.cfg.name)
                    _FLIGHTREC.record("engine", "runner_dead",
                                      model=self.cfg.name,
                                      error=str(e)[:200])
                    self.abort_all("engine unrecoverable")
                    return

    def _pump_once(self) -> None:
        """One runner iteration: bounded admission, top up the dispatch
        pipeline, fetch + ingest the oldest in-flight block."""
        # engine.step fault site (faults.py): an injected raise takes the
        # runner's step-failure recovery path — abort in-flight requests,
        # rebuild device state, keep serving
        faults.inject("engine.step")
        self._clock.mark("ctl")
        self._drain_ctl()
        # idle engine admits everything (first tokens as early as possible);
        # a busy engine bounds admission so running streams never stall for
        # a whole arrival burst of prefills
        budget = (
            self.config.admit_per_block if self._slots
            else self.config.max_slots
        )
        admitted = 0
        while admitted < budget and self._try_admit():
            admitted += 1
        if not self._slots:
            return
        if self._spec_k:
            # speculative serving: one verify block per iteration, fetched
            # immediately while drafts are being accepted (the next step's
            # drafts depend on this step's tokens — acceptance > 1
            # token/step is what pays the un-hidden fetch back), pipelined
            # like the blocks below while none is
            self._step_spec(ahead_ok=True)
            return
        k = self.config.decode_block
        while len(self._inflight) < max(1, self.config.pipeline_depth):
            self._mark_launch()
            self._dispatch_block(k)
        # fetch+ingest wall time per fused step (observed inside
        # _fetch_oldest); in steady state the fetch of block N overlaps
        # block N+1's compute, so this is the honest per-step pace the
        # pipeline sustains
        self._fetch_oldest()

    # ---------------------------------------------------------- public API

    def generate(self, req: GenerationRequest) -> GenerationResult:
        """Blocking convenience: submit and drive until THIS request is
        done. With the runner active, just waits; otherwise drives step()
        inline (tests / sync callers)."""
        box: list[GenerationResult] = []
        done_evt = threading.Event()
        user_cb = req.on_chunk

        def cb(delta: str, done: bool, res: GenerationResult | None):
            if user_cb:
                user_cb(delta, done, res)
            if done and res is not None:
                box.append(res)
                done_evt.set()

        req.on_chunk = cb
        self.submit(req)
        if self.running:
            done_evt.wait()
            return box[0]
        with compile_owner(self.cfg.name):
            while not box:
                if not self.step() and not box:
                    time.sleep(0.001)
        return box[0]

    # batch-size buckets for the embeddings path: bounded compile count
    # (|_EMBED_BATCH_BUCKETS| × |length buckets| programs max)
    _EMBED_BATCH_BUCKETS = (1, 4, 16, 32)

    def _batch_bucket(self, n: int) -> int:
        for b in self._EMBED_BATCH_BUCKETS:
            if n <= b:
                return b
        return self._EMBED_BATCH_BUCKETS[-1]

    def embed(self, texts: list[str]) -> list[list[float]]:
        """Pooled, L2-normalized embeddings. bert_embed models run the
        bidirectional encoder with their configured pooling (mean/cls);
        decoder families mean-pool final hidden states (padding masked at
        both attention and pooling via seq_lens).

        Batched: texts are grouped by length bucket and run up to
        `embed_batch` per forward (BASELINE config #5 is high-QPS batch
        embeddings — one-text-per-forward left ~B× on the table). Padding
        rows use len=1 so pooling never divides by zero; their outputs are
        discarded."""
        from gridllm_tpu.models.bert_embed import pool

        if (self.mesh is not None and self.mesh.shape.get("pp", 1) > 1
                and not self.embedding_only):
            # hidden_states has no pp schedule; GSPMD would gather the
            # pp-sharded layer stack onto every stage (the memory blow-up
            # pp exists to avoid). Loud failure > silent OOM.
            raise RuntimeError(
                f"{self.cfg.name}: decoder-model embeddings are not "
                "supported under pipeline parallelism — serve embeddings "
                "from a non-pp engine"
            )

        enc = [
            self.tokenizer.encode_for_embedding(t, self.max_context)
            for t in texts
        ]
        out: list[list[float] | None] = [None] * len(texts)
        by_bucket: dict[int, list[int]] = {}
        for i, ids in enumerate(enc):
            by_bucket.setdefault(self._bucket_for(max(len(ids), 1)), []).append(i)
        cap = max(1, self.config.embed_batch)
        for blen, idxs in sorted(by_bucket.items()):
            for start in range(0, len(idxs), cap):
                group = idxs[start : start + cap]
                bsz = min(self._batch_bucket(len(group)), cap)
                tok = np.zeros((bsz, blen), np.int32)
                lens = np.ones((bsz,), np.int32)
                for j, i in enumerate(group):
                    ids = enc[i]
                    tok[j, : len(ids)] = ids
                    lens[j] = max(len(ids), 1)
                # multi-host: the embed forward is a sharded program too —
                # it must enter the slice's serialized plan stream or its
                # collectives deadlock (embed runs on the executor thread,
                # so the shared dispatch_lock is what pins its position
                # relative to the runner's decode blocks)
                with self.dispatch_lock:
                    lens_j = jnp.asarray(lens)
                    h = self._embed_fn(self.params, jnp.asarray(tok), lens_j)
                    if self.plan_sink is not None:  # after-success
                        self.plan_sink({
                            "op": "embed",
                            "tok": tok.tolist(),
                            "lens": lens.tolist(),
                        })
                vecs = np.asarray(pool(h, lens_j, self.cfg.pooling), np.float32)
                for j, i in enumerate(group):
                    out[i] = vecs[j].tolist()
        return out  # type: ignore[return-value]

    def abort_all(self, msg: str) -> int:
        """Fail every pending and active request (driver recovery path:
        the worker pump calls this when step() raises, so waiters get an
        immediate error instead of hanging to the job timeout)."""
        n = 0
        with self._lock:
            pending, self._pending = list(self._pending), deque()
        for r in pending:
            self._fail(r, msg)
            n += 1
        for slot, st in list(self._slots.items()):
            # keep st.text: streamed deltas already sent must stay consistent
            # with the final text field; the failure rides res.error
            self._finish(slot, st, "error", error=msg)
            n += 1
        return n

    def resolve_seed(self) -> int:
        """Draw a sampler seed from the ENGINE-seeded RNG — the same
        stream admission uses for unseeded requests, so pre-resolving a
        seed worker-side (the crash-resume watermark must carry it,
        ISSUE 9) preserves EngineConfig.seed's reproducibility knob."""
        return int(self._rng.getrandbits(31))

    def _request_finish(self, req_id: str, op: str) -> bool:
        """Shared body of cancel()/suspend(): finish a pending or running
        request with done_reason=`op`.

        Thread-safe: pending removal happens here under the lock; a RUNNING
        slot is finished via the control queue at the runner's next block
        boundary (device state must only be touched by the driving thread)."""
        with self._lock:
            for i, r in enumerate(self._pending):
                if r.id == req_id:
                    del self._pending[i]
                    res = GenerationResult(id=req_id, done_reason=op)
                    if r.on_chunk:
                        r.on_chunk("", True, res)
                    return True
        for _slot, st in list(self._slots.items()):
            if st.req.id == req_id:
                self._ctl.append((op, req_id))
                if not self.running:
                    self._drain_ctl()
                else:
                    with self._work:
                        self._work.notify_all()
                return True
        return False

    def cancel(self, req_id: str) -> bool:
        """Cancel a pending or running request (reference analogue: job
        cancellation publish, JobScheduler.ts:530-536 → worker). The
        request's on_chunk gets a final done with done_reason='cancel'."""
        return self._request_finish(req_id, "cancel")

    def suspend(self, req_id: str) -> bool:
        """Suspend a pending or running request for graceful drain
        (ISSUE 9). A running slot finishes at the next block boundary
        with done_reason='suspend' and a GenerationResult carrying
        everything a resume needs (context, generated ids, text); its
        pages register in the prefix cache exactly like a normal finish —
        the export source for the drain migration. A still-pending
        request suspends empty (nothing generated yet)."""
        return self._request_finish(req_id, "suspend")

    def decode_snapshot(self, req_id: str) -> dict[str, Any] | None:
        """Last consistent resume watermark for a running request:
        ``{"tokens": [...generated ids...], "text": "..."}``. Lock-free
        read of the engine thread's atomic snapshot tuple (same contract
        as batch_state); None until the first surviving token lands."""
        for st in list(self._slots.values()):
            if st.req.id == req_id:
                snap = st.snapshot
                if snap is None:
                    return None
                toks, text = snap
                return {"tokens": list(toks), "text": text}
        return None

    # ------------------------------------------- KV-page migration (ISSUE 7)

    @property
    def free_slot_count(self) -> int:
        """Open batch slots — the decode-headroom figure heartbeats carry
        for the scheduler's decode-pool placement."""
        return 0 if self.embedding_only else len(self._free_slots)

    def kv_transfer_supported(self) -> bool:
        """Export/import needs the content-addressed prefix cache (the
        transfer unit IS cached pages) and a process-local, unsharded
        pool: a mesh shards the pool across devices and a multi-host
        plan replay would desync on any out-of-plan pool mutation."""
        return (not self.embedding_only
                and self._prefix_cache_cap != 0
                and self.mesh is None
                and self.plan_sink is None
                # the wire's header and its pages are K and V per head: a
                # latent pool is neither exported nor imported (the
                # request is served where it arrived); nor are pages whose
                # snapshot of the recurrent state would stay behind
                and self.cfg.cache_kinds == ("kv",))

    def export_prefix_pages(self, token_ids: list[int]) -> dict[str, Any] | None:
        """Gather the longest cached full-page prefix of `token_ids` as
        host arrays for the migration wire (transfer/wire.py). Returns
        {tokens, k, v, model, kvLayout, quant} with k/v
        [L, n, ps, KVH, D] sliced to the UNPADDED model head dim, or
        None when nothing is cached / transfer is unsupported here.

        The pages are refcount-pinned for the duration of the device
        gather so a concurrent admission can neither evict nor overwrite
        them; the pin is dropped before returning."""
        if not self.kv_transfer_supported():
            return None
        with self._alloc_lock:
            pages, tokens = self.alloc.pin_prefix(token_ids)
        if not pages:
            return None
        try:
            with self.dispatch_lock:
                # dispatch the gather only — it materializes its own
                # device buffers, so the (slow, size-proportional)
                # device→host copy below runs WITHOUT the lock and
                # concurrent decode dispatch never stalls on an export
                idx = jnp.asarray(pages, jnp.int32)
                d = self.cfg.head_dim_
                if self._kv_int8:
                    # int8 pool (ISSUE 11): the wire carries the engine
                    # compute dtype so fp and int8 workers interoperate —
                    # dequantize on export, requantize on install
                    dt = jnp.dtype(self.config.dtype)
                    k_dev = (
                        self.cache.k.data[:, idx][..., :d]
                        .astype(jnp.float32)
                        * self.cache.k.scale[:, idx][..., None, None]
                    ).astype(dt)
                    v_dev = (
                        self.cache.v.data[:, idx][..., :d]
                        .astype(jnp.float32)
                        * self.cache.v.scale[:, idx][..., None, None]
                    ).astype(dt)
                else:
                    k_dev = self.cache.k[:, idx][..., :d]
                    v_dev = self.cache.v[:, idx][..., :d]
            k = np.asarray(k_dev)
            v = np.asarray(v_dev)
        finally:
            with self._alloc_lock:
                self.alloc.unpin_pages(pages)
        dpool = self.cache.k.shape[-1]
        layout = "ragged" if dpool == d else "ragged-padded"
        return {
            "tokens": [int(t) for t in token_ids[:tokens]],
            "k": k, "v": v,
            "model": self.cfg.name,
            "kvLayout": layout,
            "quant": self.config.quantize,
        }

    def import_prefix_pages(self, token_ids: list[int], k: np.ndarray,
                            v: np.ndarray, meta: dict[str, Any]) -> int:
        """Install migrated KV pages into this engine's pool and register
        them in the content-addressed prefix cache (refcount allocator),
        so the request's decode-side admission shares them via the normal
        match_prefix warm path. Returns the number of tokens installed
        (contiguous from position 0; may be shorter than offered under
        pool pressure — a shorter prefix is still valid). Raises on any
        geometry/dtype mismatch; the sender treats that as a NACK and
        falls back to serving the request locally."""
        if not self.kv_transfer_supported():
            raise ValueError(
                f"{self.cfg.name}: KV import unsupported here (prefix "
                "cache off, sharded pool, or multi-host plan replay)")
        mc, c = self.cfg, self.config
        ps = c.page_size
        kvh, dpool = self.cache.k.shape[3], self.cache.k.shape[4]
        if int(meta["pageSize"]) != ps:
            raise ValueError(
                f"page-size mismatch: wire {meta['pageSize']} vs pool {ps}")
        if (int(meta["numLayers"]) != mc.num_layers
                or int(meta["kvHeads"]) != kvh
                or int(meta["headDim"]) != mc.head_dim_):
            raise ValueError(
                f"pool geometry mismatch: wire L{meta['numLayers']}/"
                f"H{meta['kvHeads']}/D{meta['headDim']} vs "
                f"L{mc.num_layers}/H{kvh}/D{mc.head_dim_}")
        # int8 pools (ISSUE 11) exchange fp pages on the wire (export
        # dequantizes, install requantizes) — the contract dtype is the
        # engine compute dtype, not the pool storage dtype
        wire_dtype = (jnp.dtype(c.dtype) if self._kv_int8
                      else self.cache.k.dtype)
        if jnp.dtype(str(meta["dtype"])) != wire_dtype:
            raise ValueError(
                f"dtype mismatch: wire {meta['dtype']} vs pool "
                f"{wire_dtype}")
        n = min(int(k.shape[1]), len(token_ids) // ps)
        keys = self.alloc.chain_keys(token_ids, n_pages=n)
        # claim pool pages under the allocator lock; claimed pages come
        # back PINNED and UNREGISTERED — the chain key only becomes
        # matchable AFTER the device write lands, so a concurrent
        # admission can never match (and decode over) an unwritten page
        writes: list[tuple[int, int, bytes]] = []  # (page, wire idx, key)
        installed = 0
        with self._alloc_lock:
            for i, key in enumerate(keys):
                if self.alloc.peek_key(key) is not None:
                    # identical content already cached here (possibly
                    # pinned by a live request) — skip the write, keep it
                    installed = i + 1
                    continue
                page = self.alloc.claim_page()
                if page is None:
                    break  # pool exhausted: keep the shorter prefix
                writes.append((page, i, key))
                installed = i + 1
        if writes:
            try:
                self._write_imported_pages(
                    [(p, i) for p, i, _ in writes], k, v, dpool)
                with self._alloc_lock:
                    for page, _i, key in writes:
                        self.alloc.register_claimed(page, key)
            finally:
                with self._alloc_lock:
                    self.alloc.unpin_pages([p for p, _, _ in writes])
        self._update_kv_gauges()
        _FLIGHTREC.record("engine", "kv_import", model=self.cfg.name,
                          pagesInstalled=len(writes),
                          pagesShared=installed - len(writes),
                          tokens=installed * ps)
        return installed * ps

    _IMPORT_PAGE_BLOCK = 8  # pages per jitted install (fixed shape)

    def _write_imported_pages(self, writes: list[tuple[int, int]],
                              k: np.ndarray, v: np.ndarray,
                              dpool: int,
                              k_rowscale: np.ndarray | None = None,
                              v_rowscale: np.ndarray | None = None) -> None:
        """Scatter imported page data into the pool in fixed-size blocks
        (sentinel-padded so ONE compiled program serves any count), with
        buffer donation so the pool is updated in place.

        int8 pools (ISSUE 11): ``k``/``v`` either arrive as int8 with
        per-row scales (``k_rowscale``/``v_rowscale`` [L, n, ps] — a
        host-tier restore of an int8 spill) or as fp pages (a KV
        migration), which requantize per row host-side here."""
        if self._kv_int8 and k_rowscale is None:
            from gridllm_tpu.ops.kvtier import quantize_rows_np

            k, k_rowscale = quantize_rows_np(k)
            v, v_rowscale = quantize_rows_np(v)
        if dpool != k.shape[-1]:  # lane-padded pool: zero-pad the lanes
            pad = [(0, 0)] * (k.ndim - 1) + [(0, dpool - k.shape[-1])]
            k, v = np.pad(k, pad), np.pad(v, pad)
        if self._kv_install_fn is None:
            if self._kv_int8:
                @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
                def install_fn(kd, ksc, vd, vsc, idx, k_new, ks_new,
                               v_new, vs_new):
                    return (kd.at[:, idx].set(k_new, mode="drop"),
                            ksc.at[:, idx].set(ks_new, mode="drop"),
                            vd.at[:, idx].set(v_new, mode="drop"),
                            vsc.at[:, idx].set(vs_new, mode="drop"))
            else:
                @partial(jax.jit, donate_argnums=(0, 1))
                def install_fn(k_pages, v_pages, idx, k_new, v_new):
                    return (k_pages.at[:, idx].set(k_new, mode="drop"),
                            v_pages.at[:, idx].set(v_new, mode="drop"))

            # armable=False: imports legitimately first compile long after
            # the engine arms (the first migration can land any time)
            self._kv_install_fn = self.perf.wrap("kv_install", install_fn,
                                                 armable=False)
        block = self._IMPORT_PAGE_BLOCK
        sentinel = self.config.num_pages  # out of bounds → mode="drop"
        dt = self.cache.k.dtype
        for s0 in range(0, len(writes), block):
            grp = writes[s0:s0 + block]
            idx = np.full((block,), sentinel, np.int32)
            kb = np.zeros((k.shape[0], block) + k.shape[2:], dtype=k.dtype)
            vb = np.zeros_like(kb)
            if self._kv_int8:
                ksb = np.ones((k.shape[0], block, self.config.page_size),
                              np.float32)
                vsb = np.ones_like(ksb)
            for j, (page, src) in enumerate(grp):
                idx[j] = page
                kb[:, j] = k[:, src]
                vb[:, j] = v[:, src]
                if self._kv_int8:
                    ksb[:, j] = k_rowscale[:, src]
                    vsb[:, j] = v_rowscale[:, src]
            with self.dispatch_lock:
                if self._kv_int8:
                    kd, ksc, vd, vsc = self._kv_install_fn(
                        self.cache.k.data, self.cache.k.scale,
                        self.cache.v.data, self.cache.v.scale,
                        jnp.asarray(idx), jnp.asarray(kb, dt),
                        jnp.asarray(ksb), jnp.asarray(vb, dt),
                        jnp.asarray(vsb))
                    new_k = QuantPages(kd, ksc)
                    new_v = QuantPages(vd, vsc)
                else:
                    new_k, new_v = self._kv_install_fn(
                        self.cache.k, self.cache.v, jnp.asarray(idx),
                        jnp.asarray(kb, dt), jnp.asarray(vb, dt))
                self.cache = PagedKVCache(
                    k=new_k, v=new_v, page_table=self.cache.page_table,
                    lengths=self.cache.lengths,
                    page_size=self.cache.page_size)

    # ----------------------------------------- tiered KV cache (ISSUE 11)

    def _spill_page_to_host(self, page: int, key: bytes) -> None:
        """Allocator spill hook: copy one about-to-be-evicted prefix-cache
        page into the host tier (fires under _alloc_lock, from inside the
        allocator's eviction paths). Best-effort — a failure (or the
        ``kvtier.spill`` fault site) just loses the page from the tier
        and the later match degrades to a cold prefill."""
        tier = self.host_tier
        if tier is None or self.plan_sink is not None:
            return
        if key in tier:
            return  # content-addressed: the existing host copy is valid
        if faults.check("kvtier.spill"):
            return
        # one synchronous device→host round trip per NEW page, under the
        # caller's _alloc_lock; re-evictions short-circuit above, so only
        # first-time spills pay it. Batching an alloc()'s whole eviction
        # set into one indexed gather (the export_prefix_pages shape)
        # needs an allocator-side evict-N hook — deliberate future work.
        d = self.cfg.head_dim_
        # TRACED index gather (same pattern as export_prefix_pages): a
        # static python-int slice would compile one XLA program per
        # distinct page id — an eviction storm over a big pool would
        # serialize fresh compiles on the admission path
        idx = jnp.asarray([page], jnp.int32)
        with self.dispatch_lock:
            # dispatch the gather only; the device→host copy below runs
            # without the lock (same discipline as export_prefix_pages)
            if self._kv_int8:
                k_dev = self.cache.k.data[:, idx][..., :d]
                v_dev = self.cache.v.data[:, idx][..., :d]
                ks_dev = self.cache.k.scale[:, idx]
                vs_dev = self.cache.v.scale[:, idx]
            else:
                k_dev = self.cache.k[:, idx][..., :d]
                v_dev = self.cache.v[:, idx][..., :d]
        k = np.asarray(k_dev)                    # [L, 1, ps, KVH, D]
        v = np.asarray(v_dev)
        if self._kv_int8:
            tier.put(key, k, v,
                     k_scale=np.asarray(ks_dev),
                     v_scale=np.asarray(vs_dev),
                     quant="int8-rows")
        else:
            tier.put(key, k, v)

    def _restore_page_from_host(self, key: bytes) -> int | None:
        """Allocator restore hook (consulted by match_prefix under
        _alloc_lock on a chain miss): page one spilled page back into a
        fresh pool page, register it under its chain key at refcount 0,
        and return the page id so the match keeps walking. None = tier
        miss / injected fault / pool pressure / integrity failure — the
        admission degrades to a cold prefill, never a wedged request."""
        tier = self.host_tier
        if tier is None or self.plan_sink is not None:
            return None
        rec = tier.get(key)
        if rec is None:
            return None
        if faults.check("kvtier.restore"):
            tier.note_restore_failure()
            return None
        with self._alloc_lock:
            page = self.alloc.claim_page()
        if page is None:
            tier.note_restore_failure()  # pool pressure: nowhere to land
            return None
        k, v, ks, vs, quant = rec
        try:
            self._install_restored_page(page, k, v, ks, vs, quant)
        except Exception as e:  # noqa: BLE001 — degrade to cold prefill
            log.warning("host-tier restore install failed",
                        model=self.cfg.name, error=str(e))
            tier.note_restore_failure()
            with self._alloc_lock:
                self.alloc.unpin_pages([page])
            return None
        with self._alloc_lock:
            self.alloc.register_claimed(page, key)
            self.alloc.unpin_pages([page])
            out = self.alloc.peek_key(key)
        tier.mark_restored(key)
        return out

    def _install_restored_page(self, page: int, k: np.ndarray,
                               v: np.ndarray, ks: np.ndarray | None,
                               vs: np.ndarray | None,
                               quant: str | None) -> None:
        """Decode one spill record to the pool's dtype/layout and write it
        into ``page`` (the import install program, reused)."""
        from gridllm_tpu.ops.kvtier import dequantize_page

        dpool = self.cache.k.shape[-1]
        if self._kv_int8:
            if quant == "int8-rows":
                # int8 spill of an int8 pool: rows + scales land verbatim
                # (ks/vs [L, 1, ps])
                self._write_imported_pages(
                    [(page, 0)], k, v, dpool,
                    k_rowscale=np.asarray(ks, np.float32),
                    v_rowscale=np.asarray(vs, np.float32))
                return
            if quant == "int8-page":
                k, v = dequantize_page(k, ks), dequantize_page(v, vs)
            self._write_imported_pages(
                [(page, 0)], np.asarray(k, np.float32),
                np.asarray(v, np.float32), dpool)
            return
        if quant == "int8-page":
            k, v = dequantize_page(k, ks), dequantize_page(v, vs)
        elif quant == "int8-rows":
            k = np.asarray(k, np.float32) * ks[..., None, None]
            v = np.asarray(v, np.float32) * vs[..., None, None]
        self._write_imported_pages([(page, 0)], k, v, dpool)

    def park_to_host(self, token_ids: list[int]) -> int:
        """Suspend-to-host (ISSUE 11): move the cached full-page prefix
        of ``token_ids`` into the host tier and FREE its HBM pages, so a
        suspended decode stops occupying device memory entirely. The
        later resume admission restores the pages through the normal
        match_prefix warm path. Pages still shared with a live request
        are copied but NOT freed — a pinned shared page never leaves HBM
        mid-decode. Returns the number of tokens whose pages now live in
        the host tier (contiguous from position 0)."""
        tier = self.host_tier
        if tier is None or self.plan_sink is not None or len(token_ids) < 2:
            return 0
        with self._alloc_lock:
            pages, _covered = self.alloc.pin_prefix(token_ids)
        if not pages:
            return 0
        keys = self.alloc.chain_keys(token_ids, n_pages=len(pages))
        parked = 0
        try:
            for pg, key in zip(pages, keys):
                self._spill_page_to_host(pg, key)
                if key in tier:
                    parked += 1
                else:
                    break  # keep the parked prefix contiguous
        finally:
            with self._alloc_lock:
                self.alloc.unpin_pages(pages)
                self.alloc.evict_cached(
                    [pg for pg, key in zip(pages, keys) if key in tier])
        self._update_kv_gauges()
        _FLIGHTREC.record("engine", "kv_park", model=self.cfg.name,
                          pages=parked,
                          tokens=parked * self.config.page_size)
        return parked * self.config.page_size

    @property
    def active_requests(self) -> int:
        return len(self._slots)

    @property
    def queued_requests(self) -> int:
        return len(self._pending)

    def batch_state(self) -> dict[str, Any]:
        """Point-in-time batch snapshot for hang diagnoses and flight
        recorder dumps (obs/flightrec.py engine probes): which request
        holds which slot, how far it got, and how long since its last
        host-visible token. Reads mutable state without the dispatch lock
        — a wedged runner holding that lock is exactly when this must
        still answer; a torn read is a cosmetic risk, a blocked dump a
        fatal one."""
        now_ns = time.perf_counter_ns()
        wall = time.time()
        slots = {}
        for slot, st in list(self._slots.items()):
            slots[str(slot)] = {
                "request": st.req.id,
                "phase": "decode" if st.t_first_decode else "prefill",
                "promptTokens": st.prompt_len,
                "generated": len(st.generated),
                "ageS": round((now_ns - st.t_start) / 1e9, 3),
                "sinceLastTokenS": (
                    round(wall - st.t_last_ingest, 3)
                    if st.t_last_ingest else None),
            }
        return {
            "model": self.cfg.name,
            "running": self.running,
            "embeddingOnly": self.embedding_only,
            "slots": slots,
            "pending": len(self._pending),
            "inflightBlocks": len(self._inflight),
            "dispatchGen": self._gen,
            "freeSlots": len(self._free_slots),
            "kvPagesFree": self.alloc.free_pages
            if not self.embedding_only else None,
            "kvPagesCached": self.alloc.cached_pages
            if not self.embedding_only else None,
            "prefixCache": {
                "hits": self.alloc.hits, "misses": self.alloc.misses,
                "evictions": self.alloc.evictions,
                "cowCopies": self.alloc.cow_copies,
            } if not self.embedding_only else None,
            "hostTier": (self.host_tier.stats()
                         if not self.embedding_only
                         and self.host_tier is not None else None),
            "specDecode": {
                "k": self._spec_k,
                "drafter": getattr(self._drafter, "kind", "ngram"),
                "treeWidth": (self._tree_width
                              if getattr(self._drafter, "tree", False)
                              else 1),
                **self.spec_stats,
            } if self._spec_k else None,
            "jit": self.perf.state(),
            # what the engine is from start to stop (the launch spans
            # carried these until PR 59): its mesh ("" unmeshed), the
            # router's width and the experts held here (None: all of
            # them), the layers with a sliding window, what a slot holds
            # of its past and the form each kind is read in (latent rows:
            # absorbed in every region; a state: the delta rule)
            "shape": {
                "mesh": self.mesh_axes,
                "experts": self.cfg.num_experts,
                "expertsHeld": self.cfg.experts_held,
                "windowLayers": self._windowed,
                "cacheRow": "+".join(self.cfg.cache_kinds),
                # a state without the delta (keys a group) is a scan
                "attnForm": "+".join(
                    "scan" if k == "state" and self.cfg.linear_groups
                    else _ATTN_FORMS[k] for k in self.cfg.cache_kinds),
            },
            # the runner's wall time so far, phase by phase (a wedged
            # runner shows in the dump as one phase that stopped growing)
            "runnerPhaseSeconds": {p: round(v, 3) for p, v
                                   in self._clock.seconds.items()},
            # the runner's own CPU time in the same phases: wall less CPU
            # is what a phase spent blocked (a lock, the runtime); empty
            # where the host has no thread clock finer than a millisecond
            "runnerPhaseCpuSeconds": {p: round(v, 3) for p, v
                                      in self._clock.cpu_seconds.items()},
        }

    def memory_arrays(self) -> dict[str, Any]:
        """Live device buffers + allocator math for the memory probe
        (obs/perf.py memory_snapshot): weight and KV-pool arrays by
        identity (the snapshot classifies jax.live_arrays() against
        them), plus JSON-safe page-pool accounting. Reads mutable state
        without the dispatch lock, same contract as batch_state()."""
        weights = [a for a in jax.tree_util.tree_leaves(self.params)
                   if hasattr(a, "nbytes")]
        out: dict[str, Any] = {"weights": weights, "kv": [], "alloc": None}
        if self.embedding_only:
            return out
        cache = self.cache
        if isinstance(cache.k, QuantPages):
            out["kv"] = [cache.k.data, cache.k.scale, cache.v.data,
                         cache.v.scale, cache.page_table, cache.lengths]
        else:
            out["kv"] = [a for a in (cache.k, cache.v, cache.page_table,
                                     cache.lengths) if a is not None]
        # a hybrid family's second cache: the slots' state and the
        # snapshot pool are KV-class device memory too
        out["kv"] += jax.tree.leaves((cache.rec, cache.win))
        c, mc = self.config, self.cfg
        kv_bytes = cache.pool_nbytes
        bpp = kv_bytes / max(c.num_pages, 1)
        used = c.num_pages - self.alloc.free_pages - self.alloc.cached_pages
        live_tokens = sum(len(st.ids) for st in list(self._slots.values()))
        dpool = cache.k.shape[-1]
        capacity_tokens = used * c.page_size
        out["alloc"] = {
            "numPages": c.num_pages,
            "pageSize": c.page_size,
            "pagesUsed": used,
            "pagesCached": self.alloc.cached_pages,
            "pagesFree": self.alloc.free_pages,
            "bytesPerPage": int(bpp),
            "usedBytes": int(used * bpp),
            "cachedBytes": int(self.alloc.cached_pages * bpp),
            "freeBytes": int(self.alloc.free_pages * bpp),
            # lane padding multiplies KV bytes for d<128 models under the
            # kernel path (_pool_head_dim) — this is that overhead's share
            # (0 for an unpadded pool, kvLayout "ragged")
            "lanePadOverheadBytes": int(
                kv_bytes * (1 - mc.cache_dim / dpool)) if dpool else 0,
            # "ragged" = an unpadded pool (a head of whole lane tiles, or
            # no compiled kernels); "ragged-padded" = a narrower head
            # stored at 128 lanes for the kernels
            "kvLayout": (
                "ragged" if dpool == mc.cache_dim else "ragged-padded"),
            # what a token's row of one layer is: K and V per KV head, or
            # one latent row shared by every head (no V array)
            "cacheRow": "+".join(mc.cache_kinds),
            "rowBytes": int(bpp / max(c.page_size * mc.cache_layers, 1)),
            "stateBytes": (None if not self._second else {
                "slots": (cache.rec or cache.win).slot_nbytes,
                "snapshots": (cache.rec or cache.win).snap_nbytes,
                "snapshotsUsed": self.alloc.snapshots_used,
                "snapshotsCapacity": self.alloc.snapshots}),
            "liveTokens": live_tokens,
            # internal fragmentation of the live allocation: capacity
            # reserved at admission (num_predict headroom + tail pages)
            # not yet holding tokens. Clamped at 0: prefix-cache sharing
            # counts a shared page ONCE in pagesUsed while every sharer's
            # tokens land in liveTokens, so the ratio can exceed 1 in the
            # warm steady state — that is sharing, not fragmentation.
            "fragmentation": (
                max(0.0, round(1 - live_tokens / capacity_tokens, 4))
                if capacity_tokens else 0.0),
            # tiered KV cache (ISSUE 11): int8 residency + host-tier
            # occupancy/flow, itemized per tier in /admin/memory
            "kvInt8": self._kv_int8,
            "hostTier": (self.host_tier.stats()
                         if self.host_tier is not None else None),
        }
        return out
