"""Share of the runner's wall time (Σ all phases, ``idle_wait`` with
them) in which the runner was busy and NO launch was in flight:
``gridllm_engine_unfed_seconds_total`` over the window. The host starving
the chip; with ``runner.no_work_pct`` the whole-window split of
``device.idle_pct``, which sees five seconds of a traced run."""
import stages

NAME, UNIT, LAYER, MOVES = "runner.unfed_pct", "%", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return stages.share_of_wall_pct(run, stages.unfed_s(run))
