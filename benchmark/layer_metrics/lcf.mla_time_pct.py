"""Device time of latent attention in a LongCat block (the ragged kernel's
launches over the latent pool, two a block, and the absorb products around
them, in every step program; found as ``mla.latent_ops`` says) over device
busy time, chip 0."""
import lcf
import mla
import readers

NAME, UNIT, LAYER, MOVES = "lcf.mla_time_pct", "%", "latent attention", "itl_p95_ms"
CELLS = ["longcat.long_doc"]


def compute(run):
    found = mla.latent_ops(run) if lcf.shapes(run["config"]) else []
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
