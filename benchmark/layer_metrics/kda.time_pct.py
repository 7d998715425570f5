"""Device time of the Kimi Delta Attention layers' own part (the two
kernels, the convolutions, the norms and the gate around them, the blocks'
pair terms and triangular systems, the copies of the state and of the
pending rows; found as ``kda.py`` says, in every step program; the
projections and the latent layers' operations left out) over device busy
time, chip 0."""
import kda
import readers

NAME, UNIT, LAYER, MOVES = "kda.time_pct", "%", "recurrent state", "itl_p95_ms"
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    found = kda.layer_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
