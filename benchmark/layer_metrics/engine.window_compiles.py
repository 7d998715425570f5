"""Executables jax built inside the window, XLA compilations and loads
from the persistent cache alike: the change over the window of
``gridllm_xla_compile_seconds_count``, which the program feeds from
``jax.monitoring``'s backend-compile event, so a mesh's layout recompile
(no new Python signature: ``gridllm_recompiles_total`` cannot see it) is
counted too. 0 in a sound warm run. A program without the counter (the
parent of the PR that added it) gives nothing."""
import readers
import stack

NAME, UNIT, LAYER, MOVES = "engine.window_compiles", "compiles", "engine set-up", "ttft_p50_ms"
SERIES = "gridllm_xla_compile_seconds_count"


def compute(run):
    if not stack.metric_values(run["worker_after"], SERIES):
        return None
    return readers.counter_delta(run, "worker", SERIES)
