"""Median wait in the scheduler's queue: the gateway's
``gridllm_scheduler_queue_wait_seconds`` histogram, its change over the
window, interpolated inside the bucket (no finer than the buckets)."""
import readers
import stack

NAME, UNIT, LAYER, MOVES = "sched.queue_wait_p50_ms", "ms", "scheduler", "ttft_p50_ms"


def compute(run):
    h = readers.hist_delta(run, "gateway", "gridllm_scheduler_queue_wait_seconds")
    q = stack.histogram_quantile(h, 0.5)
    return None if q is None else q * 1e3
