"""The ``draft`` phase a launch: speculation's per-slot host drafting
(n-gram lookup over each live slot's history) before a verify launch."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.draft_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p == "draft")
