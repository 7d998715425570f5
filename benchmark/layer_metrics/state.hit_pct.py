"""Admissions whose recurrent state was restored where their page match
ended (``gridllm_state_prefix_total{outcome="hit"}``) over all whose
prompt matched cached pages (``hit`` + ``short`` + ``miss``), in the
window: a re-asked context that is not run through the model again."""
import gdn
import readers

NAME, UNIT, LAYER, MOVES = "state.hit_pct", "%", "recurrent state", "ttft_p50_ms"
CELLS = ["olmohybrid7b.agent_turns"]


def compute(run):
    got = {o: readers.counter_delta(run, "worker", gdn.PREFIX, outcome=o)
           for o in ("hit", "short", "miss")}
    total = sum(got.values())
    return 100.0 * got["hit"] / total if total > 0 else None
