"""Router picks that land on an expert HELD here over all picks of live
rows, in the window (``gridllm_moe_picks_total{where}``): 25 in
expectation where 64 of 256 are held; what the seeded router and its
selection bias make of it. The share of the model's expert work that this
chip of the expert-parallel group does."""
import kda
import readers

NAME, UNIT, LAYER, MOVES = "held.picks_pct", "%", "routed experts", "out_tok_s"
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    got = {w: readers.counter_delta(run, "worker", kda.PICKS, where=w)
           for w in ("held", "absent")}
    total = sum(got.values())
    return 100.0 * got["held"] / total if total > 0 else None
