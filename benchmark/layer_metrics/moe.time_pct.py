"""Device time of the routed expert layer (the router and the three
grouped products, in every step program: verify / decode, prefill, mixed
chunk; found as ``moe.py`` says) over device busy time, chip 0."""
import moe
import readers

NAME, UNIT, LAYER, MOVES = "moe.time_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["smallthinker21b.chat", "smallthinker21b.long_doc"]


def compute(run):
    found = moe.expert_ops(run) + moe.router_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
