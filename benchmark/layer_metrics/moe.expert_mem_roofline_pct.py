"""The grouped expert products' share of their memory roofline inside the
verify (or decode) program, over the capture: the bytes of the experts a
launch's live rows TOUCHED (``phases.touched_per_launch``:
``gridllm_moe_experts_touched_total`` over the launches, both between the
capture's two ends, times ``one_expert_bytes`` of the configuration's
costs: what the model needs read, whichever form reads it) over the
chip's memory bandwidth, over the products' device time a launch
(``moe.expert_ops`` inside ``readers.VERIFY_PROGRAMS``). The all-experts
form reads every held expert whatever the rows, so it reads here at its
share of the bandwidth times ``moe.experts_touched_pct``. A capture
without the counter is charged every expert of every layer
(``expert_bytes``), AT MOST what a launch reads. Bound named: memory (6 of
64 experts a row: each expert's 5.9 MB is read for a handful of rows)."""
import costs
import moe
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("moe.expert_mem_roofline_pct", "%",
                            "routed experts", "itl_p95_ms")
CELLS = ["smallthinker21b.chat", "smallthinker21b.long_doc"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak, share = phases.hbm_bytes_per_s(run), phases.chip_share(run)
    count = costs.of(run["config"])
    secs = sum(o["seconds"]
               for o in moe.expert_ops(run, readers.VERIFY_PROGRAMS))
    if not n or not secs or peak is None or not share or not hasattr(
            count, "expert_bytes"):
        return None
    touched = phases.touched_per_launch(run)
    need = (count.expert_bytes(run["config"]) if touched is None
            else touched * count.one_expert_bytes(run["config"]))
    return 100.0 * (need / share["weights"] / peak) / (secs / n)
