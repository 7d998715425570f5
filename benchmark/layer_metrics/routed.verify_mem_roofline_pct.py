"""A Laguna expert layer's share of its memory roofline inside the verify
(or decode) program, over the capture: the bytes of the routed experts a
launch's live rows TOUCHED (``phases.touched_per_launch``:
``gridllm_moe_experts_touched_total`` over the launches, both between the
capture's two ends, times one expert's ``expert_bytes``: what the model
needs read, whichever form reads it) over the chip's memory bandwidth,
over the device time a launch of the operations ``routed.time_pct``
counts inside ``readers.VERIFY_PROGRAMS``. Bound named: memory (8 of 256 experts a row: an expert's 6.3 MB is read
for a handful of rows)."""
import costs
import phases
import readers
import routed

NAME, UNIT, LAYER, MOVES = ("routed.verify_mem_roofline_pct", "%",
                            "routed experts", "itl_p95_ms")
CELLS = ["laguna-xs2.agent_turns"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak, share = phases.hbm_bytes_per_s(run), phases.chip_share(run)
    count = costs.of(run["config"])
    secs = sum(o["seconds"]
               for o in routed.layer_ops(run, readers.VERIFY_PROGRAMS))
    touched = phases.touched_per_launch(run)
    if (not n or not secs or peak is None or not share or touched is None
            or not hasattr(count, "expert_bytes")
            or routed.shapes(run["config"]) is None):
        return None
    need = touched * count.expert_bytes(run["config"]) / share["weights"]
    return 100.0 * (need / peak) / (secs / n)
