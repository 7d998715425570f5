"""Jitted calls of the ``dispatch_prefill`` phase's stage ``seed`` an
admission: the one program that writes the sampler row and rebuilds the
repeat-penalty window from the cached span's last tokens, and a state
restore where the family has a second-kind cache and the admission found
one. The change over the window of
``gridllm_engine_seed_launches_total`` over
``gridllm_engine_phase_seconds_count{phase="admit"}`` (admissions tried):
1-2 whatever is cached, where a launch for every chunk of the cached span
made it 4-10 in the cells that re-ask a context. A program without the
counter (the parent of the PR that added it) gives nothing."""
import phases
import readers
import stack

NAME, UNIT, LAYER, MOVES = "admit.seed_launches_per_request", "launches", "engine admission", "itl_p95_ms"
SERIES = "gridllm_engine_seed_launches_total"


def compute(run):
    if not stack.metric_values(run["worker_after"], SERIES):
        return None
    n = phases.window(run).get("admit", (0.0, 0.0))[1]
    if n <= 0:
        return None
    return readers.counter_delta(run, "worker", SERIES) / n
