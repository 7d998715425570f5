"""Tiered KV cache (ISSUE 11): host-RAM spill, int8 KV, suspend-to-host.

Covers the spill codec round trip (page-boundary straddles, fp/int8),
allocator spill/restore hooks, the engine-level spill→restore path under
eviction pressure (tier-on vs tier-off greedy streams byte-identical on
the raw spill path), refcount pinning (a shared page never leaves HBM
mid-decode), int8 KV greedy-parity-within-tolerance, fault-injected
restore failure degrading to a cold prefill, suspend-to-host parking,
and the scheduler preemption round trip.
"""

import asyncio
import time
import uuid

import numpy as np
import pytest

from gridllm_tpu import faults
from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.ops.kvcache import PageAllocator, QuantPages, quantize_kv_rows
from gridllm_tpu.ops.kvtier import (
    HostKVTier,
    dequantize_page,
    quantize_page,
    quantize_rows_np,
)
from gridllm_tpu.transfer.wire import (
    Assembler,
    build_spill_header,
    iter_chunks,
    spill_arrays,
)

TINY = dict(
    model="tiny-llama",
    max_slots=2,
    page_size=16,
    num_pages=16,
    max_pages_per_slot=12,
    prefill_buckets=(32, 64),
    prefill_chunk=16,
    seed=7,
)

SHARED = "Policy clause: the quick brown fox jumps over the lazy dog. " * 3
LONG = ("X" * 150) + " overflow tail"


def _gen(prompt, rid=None, n=8, **opts):
    return GenerationRequest(
        id=rid or uuid.uuid4().hex,
        prompt=prompt,
        options={"temperature": 0, "num_predict": n, **opts},
    )


def _engine(**kw):
    cfg = dict(TINY)
    cfg.update(kw)
    return InferenceEngine(EngineConfig(**cfg))


# ---------------------------------------------------------------------------
# spill codec (wire)
# ---------------------------------------------------------------------------

def _page(seed=0, L=2, ps=8, kvh=2, d=16, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(L, 1, ps, kvh, d)).astype(dtype)


def test_spill_codec_raw_round_trip():
    k, v = _page(0), _page(1)
    header, payload = build_spill_header("ab" * 16, "m", k, v)
    assert header["kind"] == "kv-spill" and header["quant"] is None
    # chunk framing: reassemble through the SAME Assembler the migration
    # wire uses, chunk-by-chunk with crc checks
    asm = Assembler(dict(header))
    for _seq, frame in iter_chunks(header, payload):
        asm.feed(frame)
    k2, v2, ks, vs = spill_arrays(header, asm.payload())
    assert np.array_equal(k2, k) and np.array_equal(v2, v)
    assert ks is None and vs is None


def test_spill_codec_int8_page_bound():
    k, v = _page(2), _page(3)
    kq, ksc = quantize_page(k)
    vq, vsc = quantize_page(v)
    header, payload = build_spill_header(
        "cd" * 16, "m", kq, vq, k_scale=ksc, v_scale=vsc, quant="int8-page")
    asm = Assembler(dict(header))
    asm.feed_raw(payload)
    k2, v2, ks2, vs2 = spill_arrays(header, asm.payload())
    kd = dequantize_page(k2, ks2)
    # symmetric per-(layer, page) scale: worst case half a quant step
    step = ks2.max()
    assert np.abs(kd - k).max() <= step * 0.5 + 1e-6
    vd = dequantize_page(v2, vs2)
    assert np.abs(vd - v).max() <= vs2.max() * 0.5 + 1e-6


def test_spill_codec_rejects_corruption():
    k, v = _page(4), _page(5)
    header, payload = build_spill_header("ee" * 16, "m", k, v)
    asm = Assembler(dict(header))
    asm.feed_raw(payload[:-4] + b"\x00\x00\x00\x01")
    from gridllm_tpu.transfer.wire import WireError

    with pytest.raises(WireError):
        asm.payload()


def test_tier_lru_eviction_and_promotion():
    k, v = _page(6), _page(7)
    # capacity for ~2 raw pages
    one = len(build_spill_header("00" * 16, "m", k, v)[1])
    t = HostKVTier(one * 2 + 10, model="m", spill_int8=False)
    assert t.put(b"a" * 16, k, v)
    assert t.put(b"b" * 16, k, v)
    assert t.get(b"a" * 16) is not None  # promote a to MRU
    assert t.put(b"c" * 16, k, v)       # evicts b (LRU)
    assert b"b" * 16 not in t and b"a" * 16 in t and b"c" * 16 in t
    assert t.evictions == 1
    # a page larger than the whole tier is refused, not wedged
    small = HostKVTier(16, model="m")
    assert not small.put(b"d" * 16, k, v)


# ---------------------------------------------------------------------------
# int8 quantization primitives
# ---------------------------------------------------------------------------

def test_quantize_kv_rows_bound():
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 2, 16)),
                    jnp.float32)
    q, s = quantize_kv_rows(x)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None, None]
    # per-row symmetric quant: error bounded by half a step per element
    assert np.abs(deq - np.asarray(x)).max() <= float(np.asarray(s).max()) / 2 + 1e-6
    qn, sn = quantize_rows_np(np.asarray(x))
    assert np.array_equal(np.asarray(q), qn)
    assert np.allclose(np.asarray(s), sn)


# ---------------------------------------------------------------------------
# allocator hooks
# ---------------------------------------------------------------------------

def test_allocator_spill_and_restore_hooks():
    a = PageAllocator(4, 4, 4, cache_pages=-1)
    spilled: dict[bytes, int] = {}
    a.spill_sink = lambda page, key: spilled.__setitem__(key, page)

    ids = list(range(12))  # 3 full pages
    a.alloc(0, 12)
    a.free(0, ids)
    assert a.cached_pages == 3
    # a fresh allocation bigger than free evicts from the LRU → spills
    a.alloc(1, 16)
    assert len(spilled) >= 3  # every registered eviction offered to the sink
    a.free(1)

    # restore_source: a chain miss consults it; returning a registered
    # page id lets the match keep walking
    b = PageAllocator(8, 4, 4, cache_pages=-1)
    store: dict[bytes, bool] = {}

    def restore(key):
        store[key] = True
        page = b.claim_page()
        if page is None:
            return None
        b.register_claimed(page, key)
        b.unpin_pages([page])
        return b.peek_key(key)

    b.restore_source = restore
    matched = b.match_prefix(0, ids)
    assert matched == 8  # 2 full pages (the last token is never matched)
    assert len(store) == 2
    b.free(0)


def test_pinned_shared_page_never_evicts():
    """A page pinned by a live request is not in the LRU: eviction (and
    therefore spill-then-free) can never touch it — allocation fails
    instead."""
    a = PageAllocator(4, 4, 4, cache_pages=-1)
    spilled = []
    a.spill_sink = lambda page, key: spilled.append(page)
    a.alloc(0, 16)  # all 4 pages
    a.free(0, list(range(16)))
    # slot 1 matches + pins 3 cached pages (the last full page stays
    # unpinned — the match always stops short of the final token)
    matched = a.match_prefix(1, list(range(16)))
    assert matched == 12
    owned = a.alloc(1, 16)
    assert owned is not None
    pinned = owned[:3]
    # the fresh 4th page legitimately evicted (and spilled) the UNPINNED
    # cached page; the pinned shares must never appear in the spill log
    assert set(spilled).isdisjoint(pinned)
    # slot 2 wants pages: nothing reclaimable (all pinned) → None, and
    # still no pinned page ever spilled
    assert a.alloc(2, 8) is None
    assert set(spilled).isdisjoint(pinned)


# ---------------------------------------------------------------------------
# engine: spill → restore under eviction pressure
# ---------------------------------------------------------------------------

def _drive_pressure(engine):
    """Warm request, long-request eviction storm, same request again.
    Returns (warm result, post-eviction result)."""
    warm = engine.generate(_gen(SHARED + "Q:", rid="warm"))
    engine.generate(_gen(LONG, rid="long"))
    post = engine.generate(_gen(SHARED + "Q:", rid="post"))
    return warm, post


def test_spill_restore_round_trip_byte_identical():
    """Raw-spill tier on vs tier off: the long request evicts the warm
    prefix either way; with the tier the post request restores it (warm,
    byte-identical), without it the prefill is cold — and the STREAMS
    are byte-identical across all four runs (greedy fp16 path)."""
    on = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False)
    warm_on, post_on = _drive_pressure(on)
    st = on.host_tier.stats()
    assert on.alloc.evictions > 0
    assert st["spills"] > 0
    assert st["restores"] > 0, st
    assert post_on.cached_tokens > 0  # warm again after the storm
    on.stop()

    off = _engine(kv_host_bytes=0)
    warm_off, post_off = _drive_pressure(off)
    assert off.host_tier is None
    assert post_off.cached_tokens == 0  # the regression the tier fixes
    off.stop()

    assert post_on.text == post_off.text == warm_on.text == warm_off.text
    assert post_on.token_ids == post_off.token_ids


def test_int8_spill_restore_completes():
    """int8 spill (default): restored streams complete and stay warm;
    exact bytes are only promised by the raw spill path."""
    e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=True)
    _warm, post = _drive_pressure(e)
    assert e.host_tier.stats()["restores"] > 0
    assert post.cached_tokens > 0
    assert post.done_reason in ("stop", "length")
    e.stop()


def test_restore_page_boundary_straddle():
    """A prompt whose cached prefix ends mid-page restores only the full
    pages (the straddling tail is recomputed), and the restored prefix
    still yields a byte-identical stream."""
    e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False)
    # 40-token prompt: 2 full pages (page_size 16) + 8-token straddle
    prompt = "S" * 40
    r1 = e.generate(_gen(prompt, rid="s1", n=6))
    e.generate(_gen(LONG, rid="evict", n=4))
    r2 = e.generate(_gen(prompt, rid="s2", n=6))
    assert r2.cached_tokens == 32  # full pages only
    assert r2.text == r1.text and r2.token_ids == r1.token_ids
    e.stop()


def test_injected_restore_failure_degrades_to_cold():
    """kvtier.restore fault: the admission falls back to a cold prefill —
    correct stream, counted failure, never a wedged request."""
    e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False)
    try:
        warm, _post = _drive_pressure(e)
        # arm the fault AFTER the pressure run so the next restore fails
        e.generate(_gen(LONG + " again", rid="evict2"))
        faults.configure("kvtier.restore=1.0")
        r = e.generate(_gen(SHARED + "Q:", rid="cold"))
        assert r.cached_tokens == 0            # cold prefill, counted miss
        assert r.text == warm.text             # stream still correct
        assert e.host_tier.stats()["restoreFailures"] > 0
    finally:
        faults.reset()
        e.stop()


def test_injected_spill_failure_loses_page_quietly():
    """kvtier.spill fault: the evicted page is simply absent from the
    tier — the later match is a tier miss, not an error."""
    faults.configure("kvtier.spill=1.0")
    try:
        e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False)
        _warm, post = _drive_pressure(e)
        st = e.host_tier.stats()
        assert st["spills"] == 0 and st["restores"] == 0
        assert st["misses"] > 0
        assert post.cached_tokens == 0
        assert post.done_reason in ("stop", "length")
        e.stop()
    finally:
        faults.reset()


def test_lane_padded_pool_spill_restore(monkeypatch):
    """Lane-padded pools (interpret mode + GRIDLLM_POOL_PAD) spill the
    UNPADDED model head dim and re-pad on restore — same contract as the
    migration wire."""
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret")
    monkeypatch.setenv("GRIDLLM_POOL_PAD", "1")
    from gridllm_tpu.ops.kvcache import _env_mode

    _env_mode.cache_clear()
    try:
        e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False,
                    num_pages=12, max_slots=1)
        assert e.cache.k.shape[-1] == 128  # padded pool (d=16 model)
        prompt = "P" * 48
        r1 = e.generate(_gen(prompt, rid="lp1", n=4))
        e.generate(_gen("Y" * 150, rid="lpe", n=2))
        r2 = e.generate(_gen(prompt, rid="lp2", n=4))
        assert e.host_tier.stats()["restores"] > 0
        assert r2.cached_tokens > 0
        assert r2.text == r1.text
        e.stop()
    finally:
        _env_mode.cache_clear()


# ---------------------------------------------------------------------------
# int8 resident KV pool
# ---------------------------------------------------------------------------

def test_int8_pool_layout_and_accounting():
    e = _engine(kv_int8=True, num_pages=32)
    assert isinstance(e.cache.k, QuantPages)
    alloc = e.memory_arrays()["alloc"]
    assert alloc["kvInt8"] is True
    # int8 + f32-per-row scales: well under half the bf16 pool bytes
    fp = _engine(num_pages=32)
    assert (e.cache.k.nbytes + e.cache.v.nbytes) < (
        fp.cache.k.nbytes + fp.cache.v.nbytes)
    fp.stop()
    e.stop()


def test_int8_attention_close_to_fp():
    """ops-level tolerance contract: decode attention over an int8 pool
    holding (the quantization of) the same content as an fp pool stays
    within the per-row quant error's reach of the fp output."""
    import jax.numpy as jnp

    from gridllm_tpu.ops.attention import ragged_paged_attention

    L, P, ps, kvh, d, s = 2, 6, 8, 2, 16, 3
    rng = np.random.default_rng(0)
    kf = jnp.asarray(rng.normal(size=(L, P, ps, kvh, d)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(L, P, ps, kvh, d)), jnp.float32)

    def to_quant(x):
        q, sc = quantize_kv_rows(x.reshape(L, P * ps, kvh, d))
        return QuantPages(q.reshape(L, P, ps, kvh, d),
                          sc.reshape(L, P, ps))

    kq, vq = to_quant(kf), to_quant(vf)
    pt = jnp.asarray(np.arange(P).reshape(s, 2), jnp.int32)
    lengths = jnp.asarray([10, 13, 5], jnp.int32)
    q = jnp.asarray(rng.normal(size=(s, 4, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(s, kvh, d)), jnp.float32)
    li = jnp.int32(1)
    group = dict(q_group=q[:, None], page_table=pt, group_lengths=lengths,
                 k_group=kc[:, None], v_group=vc[:, None], layer=li,
                 use_pallas=False)
    _, of = ragged_paged_attention(kf, vf, ps, **group)
    _, oq = ragged_paged_attention(kq, vq, ps, **group)
    assert float(jnp.abs(of - oq).max()) < 0.05


def test_int8_greedy_parity_within_tolerance():
    """Greedy streams on the tiny model: int8 KV must agree with the fp
    pool on a substantial shared PREFIX — after the first divergent
    sample the streams legitimately fork, so positional overlap past it
    proves nothing."""
    fp = _engine(num_pages=32)
    r_fp = fp.generate(_gen(SHARED + "Go:", rid="fp", n=12))
    fp.stop()
    q8 = _engine(kv_int8=True, num_pages=32)
    r_q8 = q8.generate(_gen(SHARED + "Go:", rid="q8", n=12))
    q8.stop()
    prefix = 0
    for a, b in zip(r_fp.token_ids, r_q8.token_ids):
        if a != b:
            break
        prefix += 1
    assert prefix >= 4, (r_fp.token_ids, r_q8.token_ids)
    assert r_q8.done_reason in ("stop", "length")


def test_int8_pool_spill_restore_and_prefix_cache():
    """int8 pool + host tier: spills carry the int8 rows + per-row
    scales verbatim, restores land them back exactly (the restored
    stream is byte-identical to the warm one on the SAME int8 engine)."""
    e = _engine(kv_int8=True, kv_host_bytes=1 << 22)
    warm, post = _drive_pressure(e)
    assert e.host_tier.stats()["restores"] > 0
    assert post.cached_tokens > 0
    assert post.text == warm.text and post.token_ids == warm.token_ids
    e.stop()


def test_int8_migration_export_import_round_trip():
    """KV migration between int8 pools rides the fp wire: export
    dequantizes, import requantizes per row — decode-side match warm."""
    src = _engine(kv_int8=True, num_pages=32)
    res = src.generate(_gen(SHARED + "M:", rid="m1", n=6))
    export = src.export_prefix_pages(res.context[:-1])
    assert export is not None
    src.stop()
    from gridllm_tpu.transfer.wire import build_header

    header, payload = build_header(
        "m1", "tiny-llama", export["tokens"], export["k"], export["v"],
        kv_layout=export["kvLayout"], quant=export["quant"])
    asm = Assembler(dict(header))
    asm.feed_raw(payload)
    tokens, k, v = asm.arrays()
    dst = _engine(kv_int8=True, num_pages=32)
    installed = dst.import_prefix_pages(tokens, k, v, header)
    assert installed == len(tokens)
    r2 = dst.generate(_gen(SHARED + "M:", rid="m2", n=6))
    assert r2.cached_tokens > 0
    dst.stop()


# ---------------------------------------------------------------------------
# suspend-to-host
# ---------------------------------------------------------------------------

def test_park_to_host_frees_hbm_and_resumes_exactly():
    e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False, num_pages=32)
    r1 = e.generate(_gen(SHARED + "Park:", rid="p1", n=10))
    cached = e.alloc.cached_pages
    assert cached > 0
    parked = e.park_to_host(r1.context[:-1])
    assert parked > 0
    assert e.alloc.cached_pages == 0           # HBM actually freed
    assert e.host_tier.stats()["pages"] >= parked // e.config.page_size
    r2 = e.generate(_gen(SHARED + "Park:", rid="p2", n=10))
    assert r2.cached_tokens > 0                # restored from host
    assert r2.text == r1.text and r2.token_ids == r1.token_ids
    e.stop()


def test_park_never_frees_shared_pinned_pages():
    """park_to_host while another request still shares the prefix: the
    shared pages are copied to host but STAY resident (refcount-pinned),
    and the live decode is unaffected."""
    e = _engine(kv_host_bytes=1 << 22, kv_spill_int8=False, num_pages=32,
                max_slots=2)
    r1 = e.generate(_gen(SHARED + "A:", rid="sh1", n=6))
    # a second request pins the shared prefix pages and stays "live":
    # drive it manually so it holds the slot while we park
    e.start()
    import threading

    done = threading.Event()
    box = []

    def cb(_d, d, res):
        if d:
            box.append(res)
            done.set()

    e.submit(GenerationRequest(id="sh2", prompt=SHARED + "A:",
                               options={"temperature": 0, "num_predict": 200},
                               on_chunk=cb))
    t0 = time.time()
    while not e.active_requests and time.time() - t0 < 20:
        time.sleep(0.01)
    pinned_before = e.alloc.cached_pages
    e.park_to_host(r1.context[:-1])
    # shared pages were pinned by sh2's admission → not freed
    assert e.alloc.cached_pages <= pinned_before
    done.wait(60)
    assert box and box[0].done_reason in ("stop", "length")
    # the parked copy never corrupted the live stream's shared prefix:
    # same prompt, greedy → sh2's stream extends r1's exactly
    common = min(len(box[0].text), len(r1.text))
    assert box[0].text[:common] == r1.text[:common]
    e.stop()


def test_tier_disabled_without_prefix_cache():
    e = _engine(kv_host_bytes=1 << 22, prefix_cache=False)
    assert e.host_tier is None
    e.stop()


# ---------------------------------------------------------------------------
# scheduler preemption (suspend-to-host priority)
# ---------------------------------------------------------------------------

async def test_preemption_round_trip():
    """A queued high-priority generation preempts a running low-priority
    one: the victim suspends to the host tier, the interactive job runs,
    the victim resumes exactly-once and completes."""
    from gridllm_tpu.bus.memory import InMemoryBus
    from gridllm_tpu.scheduler import JobScheduler, WorkerRegistry
    from gridllm_tpu.utils.config import Config, WorkerConfig
    from gridllm_tpu.utils.types import InferenceRequest, Priority
    from gridllm_tpu.worker.service import WorkerService

    # 512 positions a request: the batch job decodes its 400 tokens for
    # about a second, warm (at 256 it was cut at 230 and done in half a
    # second, and only the chunk program's compile inside its admission
    # kept it running until the preempt trigger; since ISSUE 39 the warm-up
    # request builds that program)
    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=1, page_size=16, num_pages=48,
        max_pages_per_slot=32, prefill_buckets=(32, 64), prefill_chunk=16,
        kv_host_bytes=1 << 22, kv_spill_int8=False, seed=3))
    bus = InMemoryBus()
    await bus.connect()
    cfg = Config()
    # fast sweep so the preempt trigger fires well before the tiny
    # batch decode (≈2 s warm) drains on its own
    sched_cfg = cfg.scheduler.model_copy(
        update={"preempt_after_ms": 100, "sweep_interval_ms": 200})
    registry = WorkerRegistry(bus, sched_cfg)
    scheduler = JobScheduler(bus, registry, sched_cfg)
    await registry.initialize()
    await scheduler.initialize()
    worker = WorkerService(bus, {"tiny-llama": eng}, WorkerConfig(),
                           stream_flush_ms=5)
    await worker.start()
    await asyncio.sleep(0.2)

    def req(prompt, prio, n):
        return InferenceRequest(
            id=uuid.uuid4().hex, model="tiny-llama", prompt=prompt,
            request_type="generate", priority=prio,
            options={"temperature": 0, "num_predict": n}, stream=False)

    try:
        # warm compiles so the batch job is decoding when preempted
        await scheduler.submit_and_wait(req("warmup", Priority.medium, 4),
                                        timeout_ms=180_000)
        batch = req("count: one two three four", Priority.low, 400)
        t_batch = asyncio.ensure_future(
            scheduler.submit_and_wait(batch, timeout_ms=180_000))
        await asyncio.sleep(0.15)
        r_inter = await asyncio.wait_for(
            scheduler.submit_and_wait(
                req("hello there", Priority.high, 8), timeout_ms=120_000),
            120)
        r_batch = await asyncio.wait_for(t_batch, 240)
        jt = scheduler._jobs_total
        assert r_inter.success
        assert r_batch.success
        assert int(jt.value(event="preempt_requested")) >= 1
        assert int(jt.value(event="preempted")) >= 1
        # exactly-once: the resumed batch stream reports its FULL token
        # count (resume folded prior tokens into generated state)
        assert r_batch.response.eval_count > 50
        # the victim's KV really took the host round trip
        st = eng.host_tier.stats()
        assert st["spills"] >= 1 and st["restores"] >= 1
    finally:
        await worker.stop()
        await scheduler.shutdown()
        await registry.shutdown()
        await bus.disconnect()
