"""The held experts' share of their memory roofline inside the verify (or
decode) program, in the traced window: the bytes of the held experts a
launch's live rows TOUCH (``phases.touched_per_launch``:
``gridllm_moe_experts_touched_total`` over the capture a launch, which
counts held experts only, times one expert's bytes: what a launch has to
read of them; the all-experts form reads every held expert whatever the
rows) over the chip's memory bandwidth, over the device time a launch of
the operations ``held.time_pct`` counts inside
``readers.VERIFY_PROGRAMS``. Bound named: memory."""
import kda
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("held.mem_roofline_pct", "%", "routed experts",
                            "itl_p95_ms")
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak, count = phases.hbm_bytes_per_s(run), kda.count(run)
    secs = sum(o["seconds"] for o in kda.held_ops(run, readers.VERIFY_PROGRAMS))
    touched = phases.touched_per_launch(run)
    if not n or not secs or peak is None or count is None or touched is None:
        return None
    need = count.held_expert_bytes(run["config"], touched)
    return 100.0 * (need / peak) / (secs / n)
