"""Device time of the HELD routed experts' products in a LongCat block
(the ``grouped_experts`` kernel, the sorted form's ``ragged-dot`` or the
stacked held experts; found as ``lcf.held_ops`` says, in every step
program) over device busy time, chip 0: what 16 of 512 experts cost the
chip that holds them. The zero-compute picks are no product and are not
in it."""
import lcf
import readers

NAME, UNIT, LAYER, MOVES = "lcf.held_time_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["longcat.long_doc"]


def compute(run):
    found = lcf.held_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
