"""A deepseek_v2 expert layer's share of its memory roofline inside the
verify (or decode) program, in the traced window: the bytes of the held
router, routed and shared experts of every routed layer
(``expert_layer_bytes`` of the configuration's costs: an upper bound on
what a launch has to read, which the all-experts form reads whatever the
rows; ``experts.touched_pct`` says how much of it the rows needed) over
the chip's memory bandwidth, over the device time a launch of the
operations ``experts.time_pct`` counts (``mla.expert_ops`` inside
``readers.VERIFY_PROGRAMS``). Bound named: memory (6 of 64 experts a row:
each expert's 17 MB is read for a handful of rows)."""
import costs
import mla
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("experts.mem_roofline_pct", "%", "routed experts",
                            "itl_p95_ms")
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak, share = phases.hbm_bytes_per_s(run), phases.chip_share(run)
    count = costs.of(run["config"])
    secs = sum(o["seconds"]
               for o in mla.expert_ops(run, readers.VERIFY_PROGRAMS))
    if not n or not secs or peak is None or not share or not hasattr(
            count, "expert_layer_bytes"):
        return None
    need = count.expert_layer_bytes(run["config"]) / share["weights"]
    return 100.0 * (need / peak) / (secs / n)
