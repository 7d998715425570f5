"""Child process for tests/test_disagg.py: a REAL worker (tiny-llama
engine + WorkerService) with a fleet role, over a RESP broker — one
prefill child + one decode child make a two-process disaggregated fleet.

Usage: python disagg_worker_child.py <broker_port> <worker_id> <role>

Engines are seeded identically everywhere (random-init weights come from
PRNGKey(0)), so token streams compare bit-for-bit across processes.
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("GRIDLLM_KVX_CHUNK_BYTES", "2048")


async def main() -> None:
    broker_port, worker_id, role = sys.argv[1], sys.argv[2], sys.argv[3]
    from gridllm_tpu.bus import create_bus
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.utils.config import WorkerConfig
    from gridllm_tpu.worker.service import WorkerService

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=2, page_size=8, num_pages=96,
        max_pages_per_slot=16, prefill_buckets=(16, 64, 128),
        prefill_chunk=16, seed=42,
    ))
    bus = create_bus(f"resp://127.0.0.1:{broker_port}")
    await bus.connect()
    svc = WorkerService(
        bus, {"tiny-llama": eng},
        WorkerConfig(worker_id=worker_id, role=role,
                     heartbeat_interval_ms=150,
                     resource_monitor_interval_ms=500),
        stream_flush_ms=5,
    )
    await svc.start()
    print("CHILD_READY", flush=True)
    await asyncio.Event().wait()  # run until killed


asyncio.run(main())
