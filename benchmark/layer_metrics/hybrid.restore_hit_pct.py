"""Admissions whose recurrent states were restored where their LATENT
page match ended (``gridllm_state_prefix_total{outcome="hit"}``) over all
whose prompt matched cached pages (``hit`` + ``short`` + ``miss``), in the
window: the one prefix cache holding both kinds of a hybrid's past (latent
rows of the MLA layers in pages, a snapshot of the KDA layers' states and
convolution rows beside them)."""
import kda
import readers

NAME, UNIT, LAYER, MOVES = "hybrid.restore_hit_pct", "%", "KV pool", "itl_p95_ms"
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    if kda.shapes(run["config"]) is None:
        return None
    got = {o: readers.counter_delta(run, "worker", kda.PREFIX, outcome=o)
           for o in ("hit", "short", "miss")}
    total = sum(got.values())
    return 100.0 * got["hit"] / total if total > 0 else None
