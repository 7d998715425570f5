"""The recurrent step's share of its roofline inside the verify / decode
programs, in the traced window: ``gdn_step_bytes`` (each LIVE slot's
state read once and written once, its K + 1 rows' q, k, v; every linear
layer; live slots from the batch-occupancy histogram over the capture)
over the chip's memory bandwidth, over the device time of the
``gdn_step`` kernel a launch. Bound named: memory."""
import gdn
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("gdn.step_roofline_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["olmohybrid7b.agent_turns"]


def compute(run):
    _, n = phases.verify_launches(run)
    secs = sum(o["seconds"] for o in gdn.kernel_ops(
        run, gdn.STEP_OP, readers.VERIFY_PROGRAMS))
    live, count, peak = (gdn.live_slots_per_launch(run), gdn.count(run),
                         phases.hbm_bytes_per_s(run))
    if not n or not secs or live is None or count is None or peak is None:
        return None
    least = count.gdn_step_bytes(run["config"], live, gdn.verify_rows(run)) / peak
    return 100.0 * least / (secs / n)
