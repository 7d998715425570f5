"""Device time of the low-rank query (``q = W_qb RMSNorm(W_qa h)``, scope
``mla_qlora``: the two products and the norm between them, two a block;
found as ``lcf.qlora_ops`` says, in every step program) over device busy
time, chip 0: what the 56.6 MB of W_qa and W_qb a sublayer cost beside the
latent reads."""
import lcf
import readers

NAME, UNIT, LAYER, MOVES = "lcf.qlora_time_pct", "%", "latent attention", "itl_p95_ms"
CELLS = ["longcat.long_doc"]


def compute(run):
    found = lcf.qlora_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
