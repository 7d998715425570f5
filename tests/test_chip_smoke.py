"""Bring-up on the chip (ISSUE 21): what can be pinned without one.

- ``chip_smoke.py`` in its CPU rehearsal mode passes end to end (three
  processes, real HTTP requests, kernels interpreted), and WITHOUT the
  switch a machine with no TPU is refused — nonzero exit, a message that
  names the missing TPU, no result line;
- the engine sizes its KV pool against the device's memory (faked here:
  the CPU backend reports none) and an allocation that cannot fit fails
  at construction with the figures; a second engine gets what the first
  left; a reset frees the old pool before it builds the new one;
- the worker prewarms at start-up, not on a later load;
- the persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says or at one fixed path inside the checkout, and two constructions
  never produce two paths.
"""

import json
import os
import subprocess
import sys

import pytest

from gridllm_tpu.engine import EngineConfig, InferenceEngine
from gridllm_tpu.engine import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *flags):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("GRIDLLM_MESH_SHAPE", None)
    return subprocess.run(
        [sys.executable, SMOKE, "--log-dir", str(tmp_path), *flags],
        env=env, capture_output=True, text=True, timeout=600)


def test_cpu_rehearsal_passes(tmp_path):
    out = _run_smoke(tmp_path, "--cpu")
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    # every op on the path was built on the (interpreted) kernel
    assert '"jnp"' not in out.stdout.split("kernel dispatch")[1].split("\n")[0]


def test_without_the_switch_no_tpu_is_refused(tmp_path):
    out = _run_smoke(tmp_path)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert '"ok"' not in out.stdout       # no result line
    assert not os.path.exists(tmp_path / "worker.log")   # served nothing


TINY = dict(model="tiny-llama", max_slots=2, page_size=8,
            max_pages_per_slot=8, prefill_buckets=(16,))
PAGE_BYTES = 2 * (2 * 8 * 2 * 16 * 2)     # K+V x [L=2, ps=8, KVH=2, D=16] bf16


def _fake_device(monkeypatch, pages_that_fit: int, in_use: int = 5 << 20):
    limit = (in_use + engine_mod.WORKSPACE_RESERVE_BYTES
             + pages_that_fit * PAGE_BYTES + PAGE_BYTES // 2)
    monkeypatch.setattr(
        engine_mod, "_device_memory_stats",
        lambda d: {"bytes_limit": limit, "bytes_in_use": in_use})


def test_pool_shrinks_to_the_device(monkeypatch):
    _fake_device(monkeypatch, pages_that_fit=100)
    eng = InferenceEngine(EngineConfig(**TINY))
    assert eng.config.num_pages == 100
    assert eng.cache.k.shape[1] == 100 and eng.alloc.free_pages == 100


def test_pool_default_where_the_device_has_room(monkeypatch):
    _fake_device(monkeypatch, pages_that_fit=5000)
    eng = InferenceEngine(EngineConfig(**TINY))
    assert eng.config.num_pages == engine_mod.DEFAULT_NUM_PAGES


def test_pool_default_without_memory_stats():
    # the CPU backend reports no statistics: the present default stands
    eng = InferenceEngine(EngineConfig(**TINY))
    assert eng.config.num_pages == engine_mod.DEFAULT_NUM_PAGES


def test_explicit_pool_that_cannot_fit_fails_with_figures(monkeypatch):
    _fake_device(monkeypatch, pages_that_fit=100)
    with pytest.raises(ValueError) as e:
        InferenceEngine(EngineConfig(**TINY, num_pages=200))
    msg = str(e.value)
    assert "200 pages of 8 tokens" in msg and "100 fit" in msg
    assert f"{PAGE_BYTES} B/page/device" in msg
    assert "GiB in use after loading weights" in msg
    assert "2.00 GiB reserved for workspace" in msg
    # one that fits is taken as given
    assert InferenceEngine(
        EngineConfig(**TINY, num_pages=64)).config.num_pages == 64


def test_sized_pool_must_hold_one_slot_at_full_context(monkeypatch):
    _fake_device(monkeypatch, pages_that_fit=3)   # max_pages_per_slot = 8
    with pytest.raises(ValueError, match="8 pages of 8 tokens.*3 fit"):
        InferenceEngine(EngineConfig(**TINY))


def test_second_engine_sizes_from_what_the_first_left(monkeypatch):
    """Pools are first come, first served: an engine sizes against what
    the process already holds, so a second engine on the same device gets
    the remainder — or, when that is less than one slot at full context,
    a construction error with the figures (not an XLA out-of-memory in
    its first request)."""
    in_use = 5 << 20
    limit = (in_use + engine_mod.WORKSPACE_RESERVE_BYTES
             + 110 * PAGE_BYTES + PAGE_BYTES // 2)
    held = {"bytes": in_use}
    monkeypatch.setattr(
        engine_mod, "_device_memory_stats",
        lambda d: {"bytes_limit": limit, "bytes_in_use": held["bytes"]})
    monkeypatch.setattr(engine_mod, "DEFAULT_NUM_PAGES", 100)
    first = InferenceEngine(EngineConfig(**TINY))
    assert first.config.num_pages == 100          # the default cap
    held["bytes"] += 100 * PAGE_BYTES             # the first one's pool
    second = InferenceEngine(EngineConfig(**TINY))
    assert second.config.num_pages == 10          # the remainder
    held["bytes"] += 10 * PAGE_BYTES
    with pytest.raises(ValueError, match="8 pages of 8 tokens.*0 fit"):
        InferenceEngine(EngineConfig(**TINY))


def test_reset_frees_the_old_pool_before_building_the_new(monkeypatch):
    """A pool sized to the device leaves no room for a second one:
    reset_device_state (the recovery from a failed step) must release
    the old buffers first — a step that failed at compile time donated
    nothing, so they are still live."""
    eng = InferenceEngine(EngineConfig(**TINY, num_pages=16))
    old = eng.cache
    make = eng._new_cache
    seen = []

    def spy(num_pages):
        seen.append((eng.cache, old.k.is_deleted(), old.v.is_deleted()))
        return make(num_pages)

    monkeypatch.setattr(eng, "_new_cache", spy)
    eng.reset_device_state()
    assert seen == [(None, True, True)]
    assert eng.cache is not None and not eng.cache.k.is_deleted()


def test_worker_prewarms_at_start_up_only(monkeypatch):
    """A starting worker registers compiled; a model loaded into a running
    worker (/api/pull, a placement swap-in) pays no inline prewarm — time
    to its first answer is what that path is measured by."""
    from gridllm_tpu.utils.config import load_config
    from gridllm_tpu.worker import main as worker_main

    monkeypatch.setenv("GRIDLLM_MODELS", "tiny-llama")
    monkeypatch.setenv("GRIDLLM_KV_PAGE_SIZE", "16")
    monkeypatch.setenv("GRIDLLM_PREFILL_BUCKETS", "32")
    monkeypatch.setenv("GRIDLLM_ALLOW_SYNTHETIC_WEIGHTS", "1")
    config = load_config()
    calls = []
    monkeypatch.setattr(InferenceEngine, "prewarm",
                        lambda self: calls.append(self.cfg.name))
    assert list(worker_main.build_engines(config)) == ["tiny-llama"]
    assert calls == ["tiny-llama"]
    worker_main.pull_engine_factory(config)("tiny-llama")
    assert calls == ["tiny-llama"]


_CACHE_PROBE = """
import jax
from gridllm_tpu.engine import EngineConfig, InferenceEngine
seen = []
for _ in range(2):
    InferenceEngine(EngineConfig(model="tiny-llama", max_slots=2, page_size=8,
                                 max_pages_per_slot=8, num_pages=16,
                                 prefill_buckets=(16,)))
    seen.append(jax.config.jax_compilation_cache_dir)
print("CACHE_DIRS=" + "|".join(seen))
"""


def _cache_dirs(env_dir):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("CACHE_DIRS=")][-1]
    return line.split("=", 1)[1].split("|")


def test_compile_cache_follows_jax_env(tmp_path):
    assert _cache_dirs(str(tmp_path)) == [str(tmp_path)] * 2
    assert os.listdir(tmp_path)           # and jax really wrote there


def test_compile_cache_fixed_path_in_checkout():
    assert _cache_dirs(None) == [os.path.join(REPO, ".jax_cache")] * 2
