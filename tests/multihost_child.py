"""Child process for the multi-host worker-group test (test_multihost.py).

Each process: joins the jax group (CPU, 4 local devices → 8 global), proves
a cross-host collective works, then runs GroupMembership over the RESP
broker. Process 0 (liaison) registers ONE logical worker and, on slice
failure, announces `worker:disconnected` (the scheduler's orphan trigger).

Usage: python multihost_child.py <proc_id> <coord_port> <broker_port> <worker_id>
"""

import asyncio
import os
import sys


def main() -> None:
    pid, coord_port, broker_port, worker_id = (
        int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["GRIDLLM_COORD_ADDR"] = f"127.0.0.1:{coord_port}"
    os.environ["GRIDLLM_NUM_PROCS"] = "2"
    os.environ["GRIDLLM_PROC_ID"] = str(pid)

    import jax

    from gridllm_tpu.parallel.distributed import GroupConfig, initialize_group

    group = initialize_group(GroupConfig.from_env())
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    # one real cross-host collective over the slice mesh
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=8))
    total = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
        in_specs=P("tp"), out_specs=P(),
    ))(jnp.arange(8.0))
    assert float(total[0]) == 28.0, total
    print(f"[{pid}] collective ok", flush=True)

    asyncio.run(run_group(group, broker_port, worker_id))


async def run_group(group, broker_port: str, worker_id: str) -> None:
    import json

    from gridllm_tpu.bus import create_bus
    from gridllm_tpu.utils.types import ModelInfo, NodeCapabilities, WorkerInfo
    from gridllm_tpu.worker.group import GroupMembership, fail_logical_worker

    bus = create_bus(f"resp://127.0.0.1:{broker_port}", key_prefix="T:")
    await bus.connect()
    stop = asyncio.Event()

    async def on_failure(reason: str) -> None:
        if group.is_liaison:
            await fail_logical_worker(bus, worker_id, reason)
            print(f"[{group.process_id}] logical worker failed: {reason}",
                  flush=True)
        stop.set()

    membership = GroupMembership(
        bus, worker_id, group, heartbeat_interval_s=0.2,
        on_slice_failure=on_failure,
    )
    await membership.start()

    if group.is_liaison:
        info = WorkerInfo(
            workerId=worker_id,
            capabilities=NodeCapabilities(
                workerId=worker_id,
                availableModels=[ModelInfo(name="m1")],
            ),
            status="online",
        )
        await bus.hset("workers", worker_id, info.model_dump_json())
        await bus.publish("worker:registered", info.model_dump_json())

    print(f"[{group.process_id}] group ready", flush=True)
    if group.is_liaison:
        # liaison lives until the slice breaks (parent kills the follower)
        await asyncio.wait_for(stop.wait(), timeout=30)
    else:
        # follower: hold membership until the parent kills this process
        await asyncio.sleep(30)
    await membership.stop()
    await bus.disconnect()
    # fail-fast exit: jax.distributed's atexit teardown can block forever
    # once a slice member is SIGKILLed (coordinator waits on dead agents) —
    # same reason worker/main.py force-exits on slice failure
    os._exit(0)


if __name__ == "__main__":
    main()
