"""The latest a 50 ms timer fired in the worker's event loop over the
window, as the upper edge of the highest bucket of
``gridllm_worker_loop_lag_seconds`` that rose; 0 if none rose. A stream's
frames leave through that loop: its lag is added to every gap."""
import stages

NAME, UNIT, LAYER, MOVES = "worker.loop_lag_max_ms", "ms", "HTTP API / worker", "itl_p95_ms"


def compute(run):
    return stages.highest_risen_ms(run, stages.LOOP_LAG)
