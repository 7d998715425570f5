"""Mean wall time of an engine step as the host loop sees it (fetch and
ingest included): ``gridllm_engine_step_duration_seconds``, sum over count
of its change over the window. Host clock, not device time."""
import readers

NAME, UNIT, LAYER, MOVES = "step.host_pace_ms", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return readers.hist_mean(run, "worker", "gridllm_engine_step_duration_seconds", 1e3)
