"""Mean wait in the engine's pending queue, ``submit()`` to popped for
admission (behind ``admit_per_block``, a full batch or an exhausted pool):
``gridllm_engine_admit_wait_seconds``, sum over count of its change over
the window."""
import readers

NAME, UNIT, LAYER, MOVES = "engine.admit_wait_mean_ms", "ms", "engine admission", "ttft_p50_ms"


def compute(run):
    return readers.hist_mean(run, "worker", "gridllm_engine_admit_wait_seconds", 1e3)
