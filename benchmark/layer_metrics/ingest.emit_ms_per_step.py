"""The ``ingest`` phase's stage ``emit`` a verify / decode launch: the
time inside the streams' ``on_chunk`` callbacks, the hand-over to the
worker's event loop. ``gridllm_engine_stage_seconds{phase="ingest",
stage="emit"}`` ``_sum`` over ``gridllm_engine_phase_seconds_count{phase=
"dispatch_verify"}`` of the window; the rest of
``runner.ingest_ms_per_step`` is stop checks and detokenising."""
import phases
import stages

NAME, UNIT, LAYER, MOVES = "ingest.emit_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "ingest", "emit", per=phases.LAUNCH)
