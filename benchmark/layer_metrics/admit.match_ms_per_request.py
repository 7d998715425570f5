"""The ``admit`` phase's stage ``match`` an admission: the prefix cache's
hash chain a page (``match_prefix``), the pages' allocation and a hybrid
family's state plan, under the allocator's lock.
``gridllm_engine_stage_seconds{phase="admit",stage="match"}``, ``_sum``
over ``_count`` of the window."""
import stages

NAME, UNIT, LAYER, MOVES = "admit.match_ms_per_request", "ms", "engine admission", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "admit", "match")
