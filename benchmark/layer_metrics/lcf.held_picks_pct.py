"""Router picks that land on an expert HELD here over all picks of live
rows, in the window (``gridllm_moe_picks_total{where}``): 2.1 in
expectation where 16 of the router's 768 outputs are held; the share of
the model's expert work that this chip of the 32 does."""
import lcf

NAME, UNIT, LAYER, MOVES = "lcf.held_picks_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["longcat.long_doc"]


def compute(run):
    got = lcf.picks(run)
    return None if got is None else 100.0 * got["held"] / sum(got.values())
