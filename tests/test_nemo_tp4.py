"""mistral-nemo's shape under ``tp:4`` (PR 27): the preset the benchmark's
four-chip cell rehearses with, the engine's weights born sharded, the served
path against the benchmark's plain float32 reference, the counter of
XLA compilations that jax itself feeds, and (ISSUE 32) the narrow width of a
prompt's last chunk over the same four devices."""
import importlib.util
import json
import logging
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.engine import EngineConfig, GenerationRequest, InferenceEngine
from gridllm_tpu.engine import engine as engine_mod
from gridllm_tpu.engine.engine import _model_module
from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.obs.perf import XLA_COMPILE_SECONDS, compile_owner
from gridllm_tpu.ops.kvcache import PagedKVCache
from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh
from gridllm_tpu.parallel.sharding import param_shardings, shard_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# benchmark/tests/test_sharded_init.py's five, which stays where it is
CASES = [("tiny-mistral", dict(tp=4)), ("tiny-mixtral", dict(tp=4)),
         ("tiny-mixtral", dict(ep=2, tp=2)), ("tiny-gemma2", dict(tp=4)),
         ("tiny-qwen3", dict(tp=4))]


def four_chip_mesh(**axes):
    """A mesh over four of the suite's eight host devices."""
    return build_mesh(MeshConfig(**axes), devices=jax.devices()[:4])


@pytest.fixture
def four_devices(monkeypatch):
    """The engine builds its mesh from ``jax.devices()``; a four-chip host
    has four. Steered here, not through an option of the program."""
    monkeypatch.setattr(engine_mod, "build_mesh",
                        partial(build_mesh, devices=jax.devices()[:4]))


def bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


# -- (a) the preset ----------------------------------------------------------

def test_tiny_nemo_has_nemos_shape():
    tiny, nemo = get_config("tiny-nemo"), get_config("mistral-nemo:12b")
    for cfg in (tiny, nemo):
        assert cfg.num_heads * cfg.head_dim_ != cfg.hidden_size
        assert cfg.family == "llama" and not cfg.tie_embeddings
        assert cfg.sliding_window == 0
        assert cfg.num_kv_heads % 4 == 0 and cfg.vocab_size % 4 == 0
        assert cfg.num_heads % cfg.num_kv_heads == 0
    # tp:4 really splits the KV heads: the pool's head axis is sharded
    mesh = four_chip_mesh(tp=4)
    cache = shard_cache(PagedKVCache.create(
        tiny.num_layers, 8, 8, tiny.num_kv_heads, tiny.head_dim_, 2, 4,
        dtype=jnp.float32), mesh)
    assert cache.k.addressable_shards[0].data.shape[3] == tiny.num_kv_heads // 4
    spec = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "mistral-nemo-12b-tp4.json")))
    assert spec["rehearse_base"] == "tiny-nemo" and spec["reduced"] == {}


# -- (b) the engine's weights are born sharded -------------------------------

@pytest.mark.parametrize("preset,axes", CASES + [("tiny-nemo", dict(tp=4))])
def test_engine_weights_are_born_sharded(preset, axes, four_devices, monkeypatch):
    cfg = get_config(preset)
    seen = {}
    real_jit = jax.jit

    def spying_jit(fn, *a, **kw):
        out = real_jit(fn, *a, **kw)
        if kw.get("out_shardings") is not None and "init" not in seen:
            seen["init"] = kw["out_shardings"]
        return out

    monkeypatch.setattr(engine_mod.jax, "jit", spying_jit)
    monkeypatch.setattr(engine_mod, "shard_params", lambda *a, **k: pytest.fail(
        "the synthetic tree was built whole and resharded"))
    eng = InferenceEngine(EngineConfig(
        model=preset, mesh=MeshConfig(**axes), max_slots=2, num_pages=16,
        page_size=8, max_pages_per_slot=8, prefill_buckets=(16,)))
    monkeypatch.undo()
    assert eng.load_source == "init" and eng.mesh.devices.size == 4

    def init():
        return _model_module(cfg).init_params(
            cfg, jax.random.PRNGKey(0), jnp.bfloat16)

    want_sh = param_shardings(jax.eval_shape(init), eng.mesh)
    want = jax.jit(init, out_shardings=want_sh)()
    # the jitted program's outputs ARE the sharded leaves: at no point a
    # whole stacked leaf on one device
    assert jax.tree.structure(seen["init"]) == jax.tree.structure(want_sh)
    split = 0
    for (path, got), w, s_out, s_want in zip(
            jax.tree_util.tree_leaves_with_path(eng.params),
            jax.tree.leaves(want), jax.tree.leaves(seen["init"]),
            jax.tree.leaves(want_sh)):
        assert s_out == s_want and got.sharding == s_want, path
        assert np.array_equal(bits(got), bits(w)), path      # bit-identical
        assert len(got.devices()) == 4, path
        shard = got.addressable_shards[0].data
        assert shard.shape == s_want.shard_shape(got.shape), path
        split += shard.size < got.size
    assert split >= 4       # the projections and the FFN, not just one leaf


def test_unmeshed_engine_keeps_the_eager_tree():
    """ROADMAP D12 stays open: one chip's weights are the eager call's, bit
    for bit, so the one-chip cells' byte-level goldens cannot move."""
    cfg = get_config("tiny-nemo")
    eng = InferenceEngine(EngineConfig(
        model="tiny-nemo", max_slots=2, num_pages=16, page_size=8,
        max_pages_per_slot=8, prefill_buckets=(16,)))
    want = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    assert eng.mesh is None and eng.mesh_axes == ""
    assert all(np.array_equal(bits(a), bits(b)) for a, b in zip(
        jax.tree.leaves(eng.params), jax.tree.leaves(want)))


# -- (c) the served path against the plain reference -------------------------

# float32 weights and float32 arithmetic on both sides: what differs is the
# order of the sums (flash/ragged blocks, the paged cache, and under tp:4 the
# partial products of wo and w_down added across four devices): rounding of
# logits of size 0.3, measured 3.3e-7 unmeshed and 4.6e-7 under tp:4. The
# same served path on the same weights rounded to bfloat16 is off by 1e-2,
# so computing in the precision below float32 fails this 500 times over.
TOLERANCE = 2e-5
PS, N, P, HIT, CHUNK_END = 8, 40, 20, 16, 28


def load_reference():
    spec = importlib.util.spec_from_file_location("llama_f32", os.path.join(
        ROOT, "benchmark", "reference", "llama_f32.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref


def served_logits(cfg, params, mesh, toks) -> dict[int, np.ndarray]:
    """{position: logits} from the programs a request is served by: a
    bucketed prefill of the first P tokens; on a second slot that shares
    the first HIT tokens' pages (a prefix-cache hit) the chunk program
    over HIT..CHUNK_END; one verify block and then decode steps through
    the paged cache for the rest, teacher-forced."""
    cache = PagedKVCache.create(cfg.num_layers, 16, PS, cfg.num_kv_heads,
                                cfg.head_dim_, 2, 6, dtype=params["embed"].dtype)
    if mesh is not None:
        cache = shard_cache(cache, mesh)
    toks = jnp.asarray(toks, jnp.int32)
    out = {}
    row0 = jnp.array([0, 1, 2, -1, -1, -1], jnp.int32)
    lg, cache = jax.jit(partial(llama.prefill, cfg=cfg, mesh=mesh))(
        params, tokens=jnp.pad(toks[:P], (0, 32 - P)), length=jnp.int32(P),
        cache=cache, slot=jnp.int32(0), table_row=row0)
    out[P - 1] = lg
    # slot 1: pages 0-1 are slot 0's (HIT tokens cached), 3-5 its own
    row1 = jnp.array([0, 1, 3, 4, 5, -1], jnp.int32)
    n = CHUNK_END - HIT
    lg, cache = jax.jit(partial(llama.prefill_chunk, cfg=cfg, mesh=mesh))(
        params, tokens=jnp.pad(toks[HIT:CHUNK_END], (0, 16 - n)),
        start=jnp.int32(HIT), length=jnp.int32(n), cache=cache,
        slot=jnp.int32(1), table_row=row1)
    out[CHUNK_END - 1] = lg
    active = jnp.array([False, True])
    block = jnp.stack([jnp.zeros(5, jnp.int32), toks[CHUNK_END:CHUNK_END + 5]])
    lg, cache = jax.jit(partial(llama.verify_step, cfg=cfg, mesh=mesh))(
        params, tokens=block, cache=cache, active=active)
    verify = {CHUNK_END + j: lg[1, j] for j in range(5)}
    step = jax.jit(partial(llama.decode_step, cfg=cfg, mesh=mesh))
    for pos in range(CHUNK_END, N):
        lg, cache = step(params, tokens=jnp.array([0, toks[pos]], jnp.int32),
                         cache=cache, active=active)
        out[pos] = lg[1]
    return {"steps": {k: np.asarray(v) for k, v in out.items()},
            "verify": {k: np.asarray(v) for k, v in verify.items()}}


@pytest.mark.parametrize("meshed", [False, True], ids=["unmeshed", "tp4"])
def test_served_path_agrees_with_the_plain_reference(meshed):
    ref, cfg = load_reference(), get_config("tiny-nemo")
    mesh = four_chip_mesh(tp=4) if meshed else None
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    sizes = {"num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
             "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
             "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
             "tie_word_embeddings": cfg.tie_embeddings}
    toks = np.random.default_rng(27).integers(0, cfg.vocab_size, N)
    want = np.asarray(ref.logits(params, sizes, toks))     # [N, V], one pass
    if meshed:
        params = jax.device_put(params, param_shardings(params, mesh))
        assert params["layers"]["wk"].addressable_shards[0].data.shape[-1] \
            == cfg.num_kv_heads * cfg.head_dim_ // 4
    got = served_logits(cfg, params, mesh, toks)
    checked = sorted(got["steps"])
    assert checked == [P - 1, CHUNK_END - 1, *range(CHUNK_END, N)]
    for kind in ("steps", "verify"):
        for pos, lg in got[kind].items():
            assert np.abs(lg - want[pos]).max() < TOLERANCE, (kind, pos)
    # the tolerance sees a lower precision: the same programs in bfloat16
    low = served_logits(cfg, jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), params), mesh, toks)
    worst = max(np.abs(lg.astype(np.float32) - want[pos]).max()
                for pos, lg in low["steps"].items())
    assert worst > 50 * TOLERANCE


# -- (d) the compile counter and the init's log record -----------------------

def test_compile_counter_rises_on_a_first_call_only():
    @jax.jit
    def fresh(x):
        return x * 3 + 1

    x, y = jnp.arange(7.0), jnp.arange(9.0)     # made out here: they compile too
    with compile_owner("test-model"):
        fresh(x).block_until_ready()
        first = XLA_COMPILE_SECONDS.count(model="test-model")
        spent = XLA_COMPILE_SECONDS.sum(model="test-model")
        fresh(x).block_until_ready()
        assert XLA_COMPILE_SECONDS.count(model="test-model") == first >= 1
        assert spent > 0
        with compile_owner("other"):
            fresh(y).block_until_ready()                # a new shape
        assert XLA_COMPILE_SECONDS.count(model="other") == 1
        assert XLA_COMPILE_SECONDS.count(model="test-model") == first
    from gridllm_tpu.obs import default_registry

    text = default_registry().render()
    assert 'gridllm_xla_compile_seconds_count{model="test-model"}' in text


def test_engine_books_its_compiles_and_logs_the_init(four_devices, caplog):
    before = XLA_COMPILE_SECONDS.count(model="tiny-nemo")
    logger = logging.getLogger("gridllm_tpu")    # does not propagate
    logger.addHandler(caplog.handler)
    try:
        eng = InferenceEngine(EngineConfig(
            model="tiny-nemo", mesh=MeshConfig(tp=4), max_slots=2,
            num_pages=16, page_size=8, max_pages_per_slot=8,
            prefill_buckets=(16,)))
    finally:
        logger.removeHandler(caplog.handler)
    built = XLA_COMPILE_SECONDS.count(model="tiny-nemo")
    assert built > before and eng.mesh_axes == "tp:4"
    rec = next(r for r in caplog.records if "weights ready" in r.getMessage())
    text = rec.getMessage()
    total = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    for field in ("initCompileS", "initRunS", "paramBytesMaxDevice",
                  '"mesh": "tp:4"', '"source": "init"', '"devices": 4'):
        assert field in text, (field, text)
    held = int(text.split('"paramBytesMaxDevice": ')[1].split(",")[0].rstrip("}"))
    assert total / 4 <= held < total / 2        # a quarter, plus the norms
    # prewarm runs its first request twice under a mesh: the state starts on
    # one device and every step leaves it laid out over the mesh, so the
    # first request's programs compile a second time on their second call
    # with no new Python signature. After prewarm nothing compiles.
    eng.prewarm()
    warm = XLA_COMPILE_SECONDS.count(model="tiny-nemo")
    assert warm > built
    opts = {"temperature": 0, "num_predict": 4}
    for i, prompt in enumerate(("hello there", "hello again", "and a third")):
        eng.generate(GenerationRequest(id=str(i), prompt=prompt, options=opts))
        assert XLA_COMPILE_SECONDS.count(model="tiny-nemo") == warm, prompt


# -- (e) a prompt's last chunk at the narrow width, under the mesh -----------

def test_narrow_last_chunk_under_tp4_costs_one_program_and_no_token(four_devices):
    """Chunks of 32 with the narrow width 16 against chunks of 32 alone,
    both under tp:4: prewarm builds exactly one executable more (the narrow
    chunk program, once: by the time it first runs the state lies where the
    mesh leaves it), nothing compiles after it for any admission kind, and
    the greedy tokens are the one-width engine's."""
    kw = dict(model="tiny-nemo", mesh=MeshConfig(tp=4), max_slots=2,
              num_pages=64, page_size=8, max_pages_per_slot=13,
              prefill_buckets=(16, 32), prefill_chunk=32, prefix_cache=True)
    opts = {"temperature": 0, "num_predict": 4}
    built, tokens = [], []
    # the first engine also pays what the process builds once
    for narrow in (32, 16, 32):
        eng = InferenceEngine(EngineConfig(**kw, prefill_chunk_narrow=narrow))
        assert eng.mesh_axes == "tp:4"
        n0 = XLA_COMPILE_SECONDS.count(model="tiny-nemo")
        eng.prewarm()
        warm = XLA_COMPILE_SECONDS.count(model="tiny-nemo")
        built.append(warm - n0)
        out = []
        # one bucket; a cached tail; chunk + narrow tail, cold and re-asked;
        # a tail the narrow width does not hold; a chunk boundary
        for n in (9, 20, 40, 40, 70, 70, 90, 64, 97):
            res = eng.generate(GenerationRequest(
                id=f"n{n}", raw=True, options=opts,
                prompt_ids=[3 + (n + 5 * i) % 200 for i in range(n)]))
            assert res.done_reason in ("stop", "length"), res.error
            out.append((n, res.cached_tokens, res.token_ids))
        assert XLA_COMPILE_SECONDS.count(model="tiny-nemo") == warm, narrow
        assert eng.perf.state()["mixed_chunk"]["signatures"] == 1 + (narrow < 32)
        tokens.append(out)
    assert built[1] == built[2] + 1, built
    assert tokens[1] == tokens[2] == tokens[0]
    assert [c for _, c, _ in tokens[1]] == [0, 0, 0, 32, 0, 64, 0, 0, 0]
