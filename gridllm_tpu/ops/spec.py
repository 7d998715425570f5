"""Speculative-decoding drafters (ISSUE 5).

A drafter proposes up to K candidate continuation tokens for one slot from
host-visible state (the slot's full token history, prompt + generated).
The engine verifies all K in ONE batched model forward (llama.verify_step)
and keeps the longest accepted prefix plus one corrected token — so a
drafter never affects *what* is generated, only how many model forwards it
takes (greedy streams are byte-identical spec-on vs spec-off; sampled
streams keep the rejection-sampled target distribution, ops/sampling.py
spec_accept).

Phase 1 is model-free **prompt-lookup / n-gram drafting** (arXiv:2304.04487
-class): match the last n tokens of the slot's history against the earlier
history (prompt included) and propose the continuation that followed the
most recent occurrence. It costs no extra checkpoint, runs on CPU tier-1,
and wins exactly where decode is most wasteful — repetitive/templated
output (code edits, extraction, "repeat the policy clause" workloads),
where acceptance routinely exceeds 50%. On novel text it degrades to
proposing nothing, which the engine handles as a plain decode step.

Phase 2 (ISSUE 18) is **model-based drafting + token trees**: a tiny
same-family draft model (DraftModelDrafter) loaded next to the target,
sharing the device mesh, runs one batched catch-up forward plus K greedy
decode steps per verify step against its own small paged-KV pool, and
emits a STATIC-topology token tree — a depth-K greedy chain plus
(width-1) first-level sibling alternatives whose logits come free from
the first draft step. The tree's parent/depth/ancestor arrays are fixed
per process (tree_topology), so every verify shape stays static and the
recompile tripwire stays green; per-slot raggedness travels as a boolean
node-validity mask (data, not shape). n-gram remains the default and the
fallback whenever no draft model is configured (GRIDLLM_SPEC_DRAFT_MODEL
empty) or the configured one is incompatible with the target.

The interface is deliberately tiny: the engine calls `draft(ids, k, slot)`
per slot (chain drafters) or `draft_batch(ids_by_slot, k, width)` (tree
drafters, batched over all slots in one device dispatch).
"""

from __future__ import annotations

import time
from typing import Protocol, Sequence

import numpy as np

from gridllm_tpu.utils.config import env_int, env_str


class Drafter(Protocol):
    """One method: propose up to k likely next tokens for a slot."""

    def draft(self, ids: Sequence[int], k: int,
              slot: int | None = None) -> list[int]:
        """ids: the slot's full context so far (prompt + generated, oldest
        first; the LAST element is the most recent emitted token). Returns
        0..k proposed continuation tokens — an empty list means "no
        proposal", which the engine runs as a normal decode step. The
        engine names the `slot`, so a drafter may keep what it derived
        from that slot's history; the proposal is the same without it."""
        ...


class NgramDrafter:
    """Prompt-lookup drafting: longest-suffix n-gram match over the slot's
    own history.

    For n from `max_n` down to `min_n`, find the most recent earlier
    occurrence of the history's last-n tokens and propose the tokens that
    followed it. Longest match first — a longer matched context is a
    stronger predictor, and the first hit wins (most recent occurrence, the
    llama.cpp/vLLM prompt-lookup convention).

    `lookback` bounds how far back the search reaches (0 = the whole
    history). The history is held as the bytes of an int32 array and the
    search is `bytearray.rfind` of the suffix's bytes, kept to matches that
    start on a token: compiled code that never lets go of the interpreter
    lock. Whole-array numpy compares found the same tokens as fast here,
    but a ufunc over more than 500 elements releases the lock, and on the
    chip's host the worker's other threads then ran inside the draft: 0.41
    ms a step where this form takes 0.12 (PERF.md, PR 37). A miss costs
    2-19 µs a slot between 256 and 8,192 tokens and a hit 3 µs
    (deploy/host_draft_cost.py). The walk it replaced compared one list
    slice a position in the interpreter: a miss cost 1.4 ms a slot at
    4,096 tokens, which was 7.6 ms a verify step over five 3 k-token
    documents (PERF_LEDGER, PR 36, `dsv2lite.shared_doc`).

    Called with a `slot`, the drafter keeps that slot's bytes and appends
    only the tail of `ids` it has not seen, so a step costs the search and
    not a conversion of the whole list. The engine's lists only grow at
    the tail between calls; a list that is another object, is shorter, or
    differs at the last held position is converted afresh.
    """

    kind = "ngram"

    def __init__(self, max_n: int = 4, min_n: int = 1, lookback: int = 0):
        if min_n < 1 or max_n < min_n:
            raise ValueError(f"bad n-gram range [{min_n}, {max_n}]")
        self.max_n = max_n
        self.min_n = min_n
        self.lookback = max(lookback, 0)
        # slot -> (the list last handed in, its tokens as int32 bytes)
        self._held: dict[int, tuple[Sequence[int], bytearray]] = {}

    def reset_slot(self, slot: int) -> None:
        """Drop a slot's history (request finished, slot about to be reused)."""
        self._held.pop(slot, None)

    def reset(self) -> None:
        """Drop every slot's history (the engine discarded its slots)."""
        self._held.clear()

    def _history(self, slot: int, ids: Sequence[int]) -> bytearray:
        held = self._held.get(slot)
        if held is not None and held[0] is ids:
            buf = held[1]
            n = len(buf) // _TOKEN
            if n <= len(ids) and buf[-_TOKEN:] == _int32_bytes(ids[n - 1:n]):
                if n < len(ids):
                    buf += _int32_bytes(ids[n:])
                return buf
        buf = bytearray(_int32_bytes(ids))
        self._held[slot] = (ids, buf)
        return buf

    def draft(self, ids: Sequence[int], k: int,
              slot: int | None = None) -> list[int]:
        n_ids = len(ids)
        if k <= 0 or n_ids < self.min_n + 1:
            return []
        buf = (_int32_bytes(ids) if slot is None
               else self._history(slot, ids))
        size = _TOKEN * n_ids
        lo = _TOKEN * max(n_ids - self.lookback, 0) if self.lookback else 0
        # a match ends before the last token: it starts strictly before
        # the suffix itself, and a token follows it (never an empty draft)
        end = size - _TOKEN
        at, n = _rfind_token(buf, buf[end:size], lo, end), 1
        if at < 0:
            return []  # the last token never came before, so no suffix did
        for longer in range(min(self.max_n, n_ids - 1),
                            max(self.min_n, 2) - 1, -1):
            found = _rfind_token(buf, buf[size - _TOKEN * longer:size], lo, end)
            if found >= 0:
                at, n = found, longer
                break
        if n < self.min_n:
            return []
        at += _TOKEN * n
        return np.frombuffer(buf, np.int32, min(k, (size - at) // _TOKEN),
                             at).tolist()


_TOKEN = 4  # bytes of one int32 token


def _int32_bytes(ids: Sequence[int]) -> bytes:
    return np.asarray(ids, np.int32).tobytes()


def _rfind_token(buf: bytes | bytearray, pattern: bytes | bytearray,
                 start: int, end: int) -> int:
    """Byte offset of the last occurrence of `pattern` in buf[start:end]
    that starts on a token, or -1."""
    while True:
        at = buf.rfind(pattern, start, end)
        if at < 0 or at % _TOKEN == 0:
            return at
        end = at + len(pattern) - 1  # straddles tokens: look before it


def make_drafter(kind: str | None = None) -> Drafter:
    """Host-only drafter factory (env-pluggable): GRIDLLM_SPEC_DRAFTER
    selects the implementation ("ngram"), GRIDLLM_SPEC_NGRAM_MAX / _MIN /
    GRIDLLM_SPEC_LOOKBACK tune the matcher. The model-based drafter is
    NOT built here — it needs the engine's mesh/dtype/loader context, so
    the engine constructs DraftModelDrafter directly and falls back to
    this factory when no draft model is configured."""
    kind = kind or env_str("GRIDLLM_SPEC_DRAFTER")
    if kind == "ngram":
        return NgramDrafter(
            max_n=env_int("GRIDLLM_SPEC_NGRAM_MAX"),
            min_n=env_int("GRIDLLM_SPEC_NGRAM_MIN"),
            lookback=env_int("GRIDLLM_SPEC_LOOKBACK"),
        )
    raise ValueError(f"unknown drafter: {kind!r}")


# ---------------------------------------------------------------------------
# token-tree topology (ISSUE 18)
# ---------------------------------------------------------------------------
#
# A draft tree is N nodes in topological order (parents[i] < i). Node 0 is
# the ROOT: the committed last token, matching column 0 of the chain verify
# block — its KV lags the pool exactly like a decode step's input token.
# Nodes 1..N-1 carry drafted tokens; node i's KV is written optimistically
# at storage position base + i, while its ROPE/logical position is
# base + depth[i]. The topology is FIXED per process (depth-K greedy chain
# at nodes 1..K, first-level siblings at K+1..N-1, all children of the
# root), so parents/depth/ancestor arrays are jit-time constants and only
# the per-slot node-validity mask is runtime data.


def tree_depths(parents: np.ndarray) -> np.ndarray:
    """Node depths from a topological parent array (parents[0] == -1,
    parents[i] < i). Root depth 0."""
    n = len(parents)
    depth = np.zeros(n, np.int32)
    for i in range(1, n):
        p = int(parents[i])
        if not 0 <= p < i:
            raise ValueError(f"parents must be topological; node {i} -> {p}")
        depth[i] = depth[p] + 1
    return depth


def tree_ancestor_mask(parents: np.ndarray) -> np.ndarray:
    """[N, N] bool: anc[i, j] iff node j is an ancestor of i OR i itself —
    exactly the key columns node i's query row may attend inside the
    candidate block (the root-to-i path IS the sequential prefix)."""
    n = len(parents)
    anc = np.zeros((n, n), bool)
    for i in range(n):
        j = i
        while j >= 0:
            anc[i, j] = True
            j = int(parents[j])
    return anc


def tree_ancestor_bits(parents: np.ndarray) -> np.ndarray:
    """The ancestor mask packed row-wise into int32 bitmasks (bit j of
    entry i = anc[i, j]) — the SMEM-friendly form the Pallas ragged
    kernel's group region consumes. Node budget therefore caps at 32."""
    anc = tree_ancestor_mask(parents)
    n = len(parents)
    if n > 32:
        raise ValueError(f"tree node budget {n} > 32 (bitmask packing)")
    bits = np.zeros(n, np.int32)
    for i in range(n):
        for j in range(n):
            if anc[i, j]:
                bits[i] |= 1 << j
    return bits


def tree_topology(k: int, width: int) -> np.ndarray:
    """The process-static draft topology: a depth-`k` chain (nodes 1..k,
    each the child of the previous) plus `width - 1` extra first-level
    alternatives (children of the root — their logits come free from the
    draft model's first decode step). width == 1 is the pure chain;
    k == 0 degenerates to the root alone."""
    if k < 0 or width < 1:
        raise ValueError(f"bad tree shape k={k} width={width}")
    parents = [-1] + list(range(k)) + [0] * (width - 1 if k else 0)
    return np.asarray(parents, np.int32)


class DraftModelDrafter:
    """Model-based drafting (ISSUE 18): a tiny same-family draft model with
    its own small paged-KV pool, batched over all slots.

    Per engine verify step the drafter (1) diffs each slot's host context
    against what its draft cache has consumed and rolls the cache back to
    the common prefix (pure length bookkeeping — rejected speculation and
    corrections rewind for free), (2) ingests the new tokens in fixed-width
    catch-up chunks through the draft model's verify forward, and (3) runs
    K greedy decode steps emitting the chain plus the top-(width-1)
    first-step alternatives. Drafted tokens' KV stays in the draft pool
    optimistically: accepted tokens are identical tokens at identical
    positions, so the next call's common-prefix diff keeps their KV and
    only mispredictions re-ingest.

    All device work happens in exactly two jitted programs with static
    shapes (one catch-up width, one draft depth), so the recompile
    tripwire stays green; slots whose context outgrows the draft pool
    simply stop proposing (the engine then runs plain verify steps).
    """

    kind = "model"
    tree = True

    def __init__(self, mod, cfg, params, *, max_slots: int, page_size: int,
                 max_pages_per_slot: int, mesh=None, ingest_width: int = 64,
                 dtype=None, wrap=None):
        import jax
        import jax.numpy as jnp

        from gridllm_tpu.ops.kvcache import PagedKVCache, rollback_to_length

        self.mod, self.cfg, self.params = mod, cfg, params
        self.mesh = mesh
        self.max_slots = max_slots
        self.page_size = page_size
        self.draft_ns = 0  # cumulative host wall time inside draft_batch
        self._w = max(int(ingest_width), 1)
        # every slot owns a fixed page stripe — no allocator, the table is
        # a constant (the draft pool is tiny; simplicity beats packing)
        table = np.arange(max_slots * max_pages_per_slot, dtype=np.int32)
        table = table.reshape(max_slots, max_pages_per_slot)
        self.max_context = min(cfg.max_seq_len,
                               max_pages_per_slot * page_size)

        def _new_cache():
            cache = PagedKVCache.create(
                cfg.num_layers, max_slots * max_pages_per_slot, page_size,
                cfg.num_kv_heads, cfg.head_dim_, max_slots,
                max_pages_per_slot,
                dtype=jnp.dtype(dtype) if dtype is not None
                else jnp.bfloat16,
            )
            cache = PagedKVCache(
                k=cache.k, v=cache.v,
                page_table=jnp.asarray(table, dtype=jnp.int32),
                lengths=cache.lengths, page_size=page_size,
            )
            if mesh is not None:
                from gridllm_tpu.parallel.sharding import shard_cache
                cache = shard_cache(cache, mesh)
            return cache

        self._new_cache = _new_cache
        self.cache = _new_cache()
        # host-side per-slot view of what the draft pool holds: the token
        # prefix whose KV is valid (possibly AHEAD of the engine thanks to
        # optimistic draft writes)
        self._ctx: list[list[int]] = [[] for _ in range(max_slots)]

        from functools import partial

        @partial(jax.jit, donate_argnums=(1,))
        def ingest_fn(params, cache, tokens, tlen, lengths, active):
            # fixed-width catch-up chunk: consume `tlen` new tokens per
            # slot (right-padded to the static width), writing their KV
            cache = PagedKVCache(
                k=cache.k, v=cache.v, page_table=cache.page_table,
                lengths=lengths, page_size=page_size,
            )
            logits, cache = mod.verify_step(
                params, cfg, tokens, cache, active, mesh=mesh)
            cache = rollback_to_length(
                cache, jnp.minimum(cache.lengths + tlen, self.max_context))
            # the chunk's last valid row IS the next-token distribution
            last = jnp.take_along_axis(
                logits, jnp.maximum(tlen - 1, 0)[:, None, None], axis=1
            )[:, 0]
            return last, cache

        @partial(jax.jit, static_argnames=("k", "width"),
                 donate_argnums=(1,))
        def draft_fn(params, cache, last_logits, active, *, k, width):
            # K greedy steps from the catch-up logits; the first step's
            # top-`width` alternatives ride along (alts[:, 0] == chain[0])
            alts = jax.lax.top_k(last_logits, width)[1].astype(jnp.int32)
            tok = alts[:, 0]
            chain = [tok]
            for _ in range(k - 1):
                logits, cache = mod.decode_step(
                    params, cfg, tok, cache, active, mesh=mesh)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                chain.append(tok)
            return jnp.stack(chain, axis=1), alts, cache

        wrap = wrap or (lambda name, fn: fn)
        self._ingest_fn = wrap("draft_ingest", ingest_fn)
        self._draft_fn = wrap("draft_step", draft_fn)

    def reset_slot(self, slot: int) -> None:
        """Invalidate a slot's draft context (request finished/replaced)."""
        self._ctx[slot] = []

    def reset(self) -> None:
        """Rebuild the draft pool wholesale. The jitted entries donate
        the cache, so an exception mid-call can leave self.cache
        referencing deleted buffers — same failure mode as the engine's
        reset_device_state, which calls this alongside its own rebuild."""
        self.cache = self._new_cache()
        self._ctx = [[] for _ in range(self.max_slots)]

    def draft(self, ids: Sequence[int], k: int) -> list[int]:
        """Drafter-protocol chain compatibility: slot-0 batched call."""
        out = self.draft_batch({0: list(ids)}, k, 1)
        return out.get(0, ([], []))[0]

    def draft_batch(
        self, ids_by_slot: dict[int, list[int]], k: int, width: int,
    ) -> dict[int, tuple[list[int], list[int]]]:
        """One batched draft pass. Returns per slot (chain tokens ≤ k,
        first-level alternative tokens ≤ width-1). Slots that would
        overflow the draft pool (or were not asked for) are absent."""
        import jax
        import numpy as _np

        t0 = time.perf_counter_ns()
        s = self.max_slots
        live: list[int] = []
        for slot, ids in ids_by_slot.items():
            # +k: the decode steps write chain[0..k-2] past the context;
            # +1 headroom for the padded ingest chunk's junk tail
            if len(ids) + k + 1 > self.max_context or not ids:
                self._ctx[slot] = []
                continue
            live.append(slot)
        if not live or k <= 0:
            self.draft_ns += time.perf_counter_ns() - t0
            return {}

        # host diff: longest common prefix between the draft pool's view
        # and the engine's context decides the rollback point
        base = _np.zeros(s, _np.int32)
        todo: dict[int, list[int]] = {}
        for slot in live:
            ids = ids_by_slot[slot]
            ctx = self._ctx[slot]
            n = 0
            for a, b in zip(ctx, ids):
                if a != b:
                    break
                n += 1
            base[slot] = n
            todo[slot] = ids[n:]
            self._ctx[slot] = list(ids)  # consumed after the catch-up

        active_np = _np.zeros(s, bool)
        for slot in live:
            active_np[slot] = True
        active = jax.numpy.asarray(active_np)

        # fixed-width catch-up chunks; all but the final chunk only write
        # KV, the final chunk's last-row logits seed the draft chain
        w = self._w
        rounds = max((max(len(v) for v in todo.values()) + w - 1) // w, 1)
        last_logits = None
        for r in range(rounds):
            toks = _np.zeros((s, w), _np.int32)
            tlen = _np.zeros(s, _np.int32)
            for slot in live:
                seg = todo[slot][r * w:(r + 1) * w]
                if not seg:
                    # already caught up (optimistic draft KV matched, or a
                    # later round for a short slot): re-feed the final
                    # token so this chunk still yields next-token logits
                    seg = [self._ctx[slot][-1]]
                    base[slot] -= 1
                toks[slot, :len(seg)] = seg
                tlen[slot] = len(seg)
            last_logits, self.cache = self._ingest_fn(
                self.params, self.cache, jax.numpy.asarray(toks),
                jax.numpy.asarray(tlen), jax.numpy.asarray(base + 0),
                active)
            base += tlen
        chain, alts, self.cache = self._draft_fn(
            self.params, self.cache, last_logits, active,
            k=k, width=max(width, 1))
        chain = _np.asarray(jax.device_get(chain))
        alts = _np.asarray(jax.device_get(alts))
        out: dict[int, tuple[list[int], list[int]]] = {}
        for slot in live:
            ch = [int(t) for t in chain[slot]]
            # the decode steps consumed chain[:-1] and wrote their KV
            self._ctx[slot] = self._ctx[slot] + ch[:-1]
            out[slot] = (ch, [int(t) for t in alts[slot][1:]])
        self.draft_ns += time.perf_counter_ns() - t0
        return out
