"""Device time of the gated delta-rule layers' own part (the two kernels,
the convolutions, the norms and the gate around them, the blocks'
triangular systems, the copies of the state and of the pending rows;
found as ``gdn.py`` says, in every step program; the projections are
plain products and left out by the hidden size in their line) over device
busy time, chip 0."""
import gdn
import readers

NAME, UNIT, LAYER, MOVES = "gdn.time_pct", "%", "recurrent state", "itl_p95_ms"
CELLS = ["olmohybrid7b.agent_turns"]


def compute(run):
    found = gdn.layer_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
