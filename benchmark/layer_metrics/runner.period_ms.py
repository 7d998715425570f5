"""The engine runner's period: its wall time in every phase but
``idle_wait`` over the verify / decode launches of the window
(``gridllm_engine_phase_seconds``, change of ``_sum`` over change of
``_count{phase="dispatch_verify"}``). Unlike ``step.host_pace_ms`` (fetch
and ingest only) it holds drafting, the dispatches, admission and the
control drain, so it is the whole time from one launch to the next."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.period_ms", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p != phases.IDLE)
