"""Child process for tests/test_health.py: a REAL worker (tiny-llama
engine + WorkerService) whose sampler is silently perturbed — same
engine config (so the same engineConfigHash golden key as a healthy
peer), same latency, same advertised capabilities, wrong bytes.  Models
the silent correctness rot ISSUE 19 targets (corrupted weights, dtype
rot, a bad kernel fallback) that no liveness tier or latency baseline
can see: only the canary's golden output hash catches it.

Usage: python health_drift_child.py <broker_port> <worker_id>
"""

import asyncio
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


async def main() -> None:
    broker_port, worker_id = sys.argv[1], sys.argv[2]
    import jax.numpy as jnp
    from gridllm_tpu.bus import create_bus
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine import engine as engine_mod
    from gridllm_tpu.utils.config import WorkerConfig
    from gridllm_tpu.worker.service import WorkerService

    real_sample = engine_mod.sample_tokens

    def rotted_sample(logits, params, token_counts=None):
        # every distribution shifted one vocab slot: greedy argmax lands
        # on a neighbouring token id with identical shapes and timing —
        # the patch must precede engine construction so the jit traces
        # capture it
        return real_sample(jnp.roll(logits, 1, axis=-1), params,
                           token_counts)

    engine_mod.sample_tokens = rotted_sample

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=2, page_size=8, num_pages=32,
        max_pages_per_slot=4, prefill_buckets=(16, 32),
    ))
    bus = create_bus(f"resp://127.0.0.1:{broker_port}")
    await bus.connect()
    svc = WorkerService(
        bus, {"tiny-llama": eng},
        WorkerConfig(worker_id=worker_id, heartbeat_interval_ms=150,
                     resource_monitor_interval_ms=500),
        stream_flush_ms=5,
    )
    await svc.start()
    print("CHILD_READY", flush=True)
    await asyncio.Event().wait()  # run until killed


asyncio.run(main())
