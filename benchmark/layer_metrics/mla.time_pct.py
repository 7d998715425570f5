"""Device time of latent attention (the ragged kernel's launches over the
latent pool and the absorb products around them, in every step program;
found as ``mla.py`` says) over device busy time, chip 0."""
import mla
import readers

NAME, UNIT, LAYER, MOVES = "mla.time_pct", "%", "latent attention", "itl_p95_ms"
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    found = mla.latent_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
