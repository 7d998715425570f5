"""Router picks that land on a ZERO-COMPUTE expert over all picks of live
rows, in the window (``gridllm_moe_picks_total{where}``): 33 in
expectation where 256 of the router's 768 outputs are identity experts and
routing is even; what the seeded router and its selection bias make of it.
The share of a token's twelve picks that costs no product."""
import lcf

NAME, UNIT, LAYER, MOVES = "lcf.zero_picks_pct", "%", "routed experts", "itl_p95_ms"
CELLS = ["longcat.long_doc"]


def compute(run):
    got = lcf.picks(run)
    return None if got is None else 100.0 * got["zero"] / sum(got.values())
