"""The grouped expert products' share of their memory roofline inside the
verify (or decode) program, in the traced window: the bytes of the held
experts a launch has to read (``expert_bytes`` of the configuration's
costs: every expert of every layer, an upper bound that a launch of 80
routed rows over 64 experts all but reaches; ``moe.experts_touched_pct``
says how nearly) over the chip's memory bandwidth, over the products'
device time a launch (``moe.expert_ops`` inside ``readers.VERIFY_PROGRAMS``).
Bound named: memory (6 of 64 experts a row: each expert's 5.9 MB is read
for a handful of rows)."""
import costs
import moe
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("moe.expert_mem_roofline_pct", "%",
                            "routed experts", "itl_p95_ms")
CELLS = ["smallthinker21b.chat"]


def compute(run):
    _, n = phases.verify_launches(run)
    peak, share = phases.hbm_bytes_per_s(run), phases.chip_share(run)
    count = costs.of(run["config"])
    secs = sum(o["seconds"]
               for o in moe.expert_ops(run, readers.VERIFY_PROGRAMS))
    if not n or not secs or peak is None or not share or not hasattr(
            count, "expert_bytes"):
        return None
    need = count.expert_bytes(run["config"]) / share["weights"]
    return 100.0 * (need / peak) / (secs / n)
