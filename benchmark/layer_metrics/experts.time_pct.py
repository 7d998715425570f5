"""Device time of a deepseek_v2 expert layer (the router, the routed
products and the shared experts' products, in every step program; found
as ``mla.expert_pattern`` says, by this family's published keys) over
device busy time, chip 0: the ``moe.time_pct`` of this family, whose
accepted entry names SmallThinker's cell and keys."""
import mla
import readers

NAME, UNIT, LAYER, MOVES = "experts.time_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    found = mla.expert_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
