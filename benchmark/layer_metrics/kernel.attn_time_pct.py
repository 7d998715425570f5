"""Device time of the attention kernels (the ragged kernel and flash
prefill, by the names the trace shows: ``readers.RAGGED_OPS``,
``readers.FLASH_OPS``) over device busy time, chip 0."""
import readers

NAME, UNIT, LAYER, MOVES = "kernel.attn_time_pct", "%", "kernels", "itl_p95_ms"


def compute(run):
    found = readers.ops(run, readers.RAGGED_OPS) + readers.ops(run, readers.FLASH_OPS)
    busy = readers.first_device_busy_s(run)
    return 100.0 * sum(o["seconds"] for o in found) / busy if found and busy else None
