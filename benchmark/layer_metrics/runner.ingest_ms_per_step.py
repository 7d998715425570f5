"""The ``ingest`` phase a launch: stop checks, detokenisation and the
stream callbacks of the tokens a step emitted."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.ingest_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p == "ingest")
