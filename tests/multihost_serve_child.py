"""Child process for the multi-host SERVING test (test_multihost.py).

Runs the REAL worker entrypoint (gridllm_tpu.worker.main.run) as one
member of a 2-process slice over 2×4 virtual CPU devices: process 0 is
the liaison (bus worker + engines + plan publisher), process 1 the
follower (same engines, replaying the liaison's step plan). The parent
drives a real /ollama/api/generate through gateway + scheduler against
the shared broker — the request's tokens are computed by jit programs
sharded across BOTH processes.

Usage: python multihost_serve_child.py <proc_id> <coord_port> <broker_port>
         <worker_id> <worker_http_port>
"""

import asyncio
import os
import sys


def main() -> None:
    pid, coord_port, broker_port, worker_id, wport = sys.argv[1:6]
    os.environ.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "GRIDLLM_COORD_ADDR": f"127.0.0.1:{coord_port}",
        "GRIDLLM_NUM_PROCS": "2",
        "GRIDLLM_PROC_ID": pid,
        "WORKER_ID": worker_id,
        "WORKER_PORT": wport,
        "GRIDLLM_BUS_URL": f"resp://127.0.0.1:{broker_port}",
        "GRIDLLM_MODELS": "tiny-llama",
        "GRIDLLM_MESH_SHAPE": "tp:8",   # wq/wo shard over both processes
        "GRIDLLM_DTYPE": "float32",
        "GRIDLLM_PREFILL_BUCKETS": "32,64",
        "HEARTBEAT_INTERVAL": "500",  # worker config reads HEARTBEAT_INTERVAL
    })
    from gridllm_tpu.worker.main import run

    print(f"[{pid}] starting worker", flush=True)
    try:
        asyncio.run(run())
    finally:
        # fail-fast exit: jax.distributed atexit teardown can hang once a
        # peer is gone (same reason worker/main.py force-exits on failure)
        os._exit(0)


if __name__ == "__main__":
    main()
