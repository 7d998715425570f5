"""The recurrent step's share of its roofline inside the verify / decode
programs, in the traced window: ``kda_step_bytes`` (each LIVE slot's state
read once and written once, its K + 1 rows' q, k, v and decay; every KDA
layer; live slots from the batch-occupancy histogram over the capture)
over the chip's memory bandwidth, over the device time of the ``kda_step``
kernel a launch. Bound named: memory."""
import kda
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("kda.step_roofline_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    _, n = phases.verify_launches(run)
    secs = sum(o["seconds"] for o in kda.kernel_ops(
        run, kda.STEP_OP, readers.VERIFY_PROGRAMS))
    live, count, peak = (kda.live_slots_per_launch(run), kda.count(run),
                         phases.hbm_bytes_per_s(run))
    if not n or not secs or live is None or count is None or peak is None:
        return None
    least = count.kda_step_bytes(run["config"], live, kda.verify_rows(run)) / peak
    return 100.0 * least / (secs / n)
