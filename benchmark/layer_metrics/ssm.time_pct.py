"""Device time of the Mamba-2 layers' own part (the two kernels, the
convolution, the gate and the norm, the skip, a block's pair terms, the
copies of the state and of the pending rows; found as ``ssm.py`` says, in
every step program; the projections are plain products and left out by
the hidden size in their line) over device busy time, chip 0. Cannot pass
100: the operations counted are some of those whose time makes up busy
time, each once (a dict by the operation's key)."""
import readers
import ssm

NAME, UNIT, LAYER, MOVES = "ssm.time_pct", "%", "recurrent state", "itl_p95_ms"
CELLS = ["granite4hmicro.long_answers"]


def compute(run):
    found = ssm.layer_ops(run)
    busy = readers.first_device_busy_s(run)
    if not found or not busy:
        return None
    return 100.0 * sum(o["seconds"] for o in found) / busy
