"""Of the n-gram drafter's lookups over the window (one a live slot a
verify step), the share that proposed tokens:
``gridllm_spec_draft_lookups_total{outcome="hit"}`` over hit + miss. A
miss is the lookup's expensive case and proposes nothing, so the share is
what ``runner.draft_ms_per_step`` pays for and the ceiling of what
speculation can accept. Nothing where no lookup was counted (speculation
off, a draft model, or a program without the counter)."""
import readers

NAME, UNIT, LAYER, MOVES = "spec.draft_hit_pct", "%", "engine runner (host loop)", "itl_p95_ms"
SERIES = "gridllm_spec_draft_lookups_total"


def compute(run):
    hit, miss = (readers.counter_delta(run, "worker", SERIES, outcome=o)
                 for o in ("hit", "miss"))
    if hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
