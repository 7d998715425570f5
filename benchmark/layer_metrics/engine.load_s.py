"""Seconds the worker's engines spent making their weights and device
state (``gridllm_model_load_seconds``, every model and source summed) as
its last scrape has them: the part of ``setup_s`` that a change to how
weights are born moves. A program without the series gives nothing."""
import stack

NAME, UNIT, LAYER, MOVES = "engine.load_s", "s", "engine set-up", "setup_s"
SERIES = "gridllm_model_load_seconds"


def compute(run):
    text = run["worker_after"]
    if stack.metric_sum(text, SERIES + "_count") <= 0:
        return None
    return stack.metric_sum(text, SERIES + "_sum")
