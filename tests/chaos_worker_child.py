"""Child process for tests/test_chaos.py: a REAL worker (tiny-llama
engine + WorkerService) over a RESP broker, to be SIGKILLed mid-job.

Usage: python chaos_worker_child.py <broker_port> <worker_id>
"""

import asyncio
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


async def main() -> None:
    broker_port, worker_id = sys.argv[1], sys.argv[2]
    from gridllm_tpu.bus import create_bus
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.utils.config import WorkerConfig
    from gridllm_tpu.worker.service import WorkerService

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
