"""Device time of the HELD routed experts' products (the stacked weights
of the experts this chip holds, the all-experts intermediate, or the
sorted form's ``ragged-dot``; found as ``kda.held_pattern`` says, in every
step program) over device busy time, chip 0: what a share of the experts
costs the chip that holds it."""
import kda
import readers

NAME, UNIT, LAYER, MOVES = "held.time_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    found = kda.held_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
