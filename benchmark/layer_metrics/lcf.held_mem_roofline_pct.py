"""The held experts' share of their memory roofline inside the verify (or
decode) program of a LongCat block, in the traced window: the bytes of the
held experts a launch's live rows TOUCH (``phases.touched_per_launch``:
``gridllm_moe_experts_touched_total`` over the capture a launch, which
counts held experts only and none for a zero-compute pick, times
``one_expert_bytes``) over the chip's memory bandwidth, over the device
time a launch of the operations ``lcf.held_time_pct`` counts inside
``readers.VERIFY_PROGRAMS``. Bound named: memory (80 rows against 75.5 MB
an expert)."""
import costs
import lcf
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("lcf.held_mem_roofline_pct", "%", "routed experts",
                            "itl_p95_ms")
CELLS = ["longcat.long_doc"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak = phases.hbm_bytes_per_s(run)
    secs = sum(o["seconds"] for o in lcf.held_ops(run, readers.VERIFY_PROGRAMS))
    touched = phases.touched_per_launch(run)
    if not n or not secs or peak is None or touched is None:
        return None
    need = costs.of(run["config"]).held_expert_bytes(run["config"], touched)
    return 100.0 * (need / peak) / (secs / n)
