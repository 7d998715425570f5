"""The ``fetch`` phase's stage ``copy`` a verify / decode launch: from the
oldest launch's outputs ready on the device to its tokens on the host
(the ``device_get`` calls and ``np.asarray``), which no launch hides in
series. ``gridllm_engine_stage_seconds{phase="fetch",stage="copy"}``
``_sum`` over ``gridllm_engine_phase_seconds_count{phase=
"dispatch_verify"}`` of the window."""
import phases
import stages

NAME, UNIT, LAYER, MOVES = "fetch.copy_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "fetch", "copy", per=phases.LAUNCH)
