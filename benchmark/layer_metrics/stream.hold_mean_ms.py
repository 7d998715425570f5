"""Mean time a stream frame's oldest token waited in the worker, from the
engine's ``on_chunk`` on the runner thread to the frame's hand-over to the
bus: ``gridllm_worker_stream_hold_seconds``, sum over count of its change
over the window. What the frame pacing (the jitter buffer's hold, and the
event loop's wake-up) costs a token; nothing where the program has no
such series."""
import readers

NAME, UNIT, LAYER, MOVES = "stream.hold_mean_ms", "ms", "HTTP API / worker", "itl_p95_ms"


def compute(run):
    return readers.hist_mean(run, "worker", "gridllm_worker_stream_hold_seconds", 1e3)
