"""The scan's step kernel's share of its roofline inside the verify /
decode programs, in the traced window: ``ssd_step_bytes`` (each LIVE
slot's state read once and written once, its K + 1 rows' x, B, C, dt;
every Mamba-2 layer; live slots from the batch-occupancy histogram over
the capture, a program counter) over the chip's memory bandwidth, over the
device time of the ``ssd_step`` kernel a launch. Bound named: memory.
Cannot pass 100: the kernel moves every byte counted (a live slot's state
in and out) and more (the rows as blocks of eight at the packed width),
and no kernel moves bytes faster than the published bandwidth."""
import phases
import readers
import ssm

NAME, UNIT, LAYER, MOVES = ("ssm.step_roofline_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["granite4hmicro.long_answers"]


def compute(run):
    _, n = phases.verify_launches(run)
    secs = sum(o["seconds"] for o in ssm.kernel_ops(
        run, ssm.STEP_OP, readers.VERIFY_PROGRAMS))
    live, count, peak = (ssm.live_slots_per_launch(run), ssm.count(run),
                         phases.hbm_bytes_per_s(run))
    if not n or not secs or live is None or count is None or peak is None:
        return None
    least = count.ssd_step_bytes(run["config"], live, ssm.verify_rows(run)) / peak
    return 100.0 * least / (secs / n)
