"""The ``dispatch_prefill`` phase's stage ``chunk`` a chunk launch: the
host arrays built, the arguments placed and the prefill / chunk /
mixed-chunk jitted call returning. One stretch a launch, so ``_sum`` over
``_count`` of ``gridllm_engine_stage_seconds{phase="dispatch_prefill",
stage="chunk"}`` over the window is the host's price of one launch."""
import stages

NAME, UNIT, LAYER, MOVES = "admit.chunk_call_ms_per_launch", "ms", "engine admission", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "dispatch_prefill", "chunk")
