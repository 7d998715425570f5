"""Prompt tokens over launch widths through the chunk program in the
window: the change of ``gridllm_engine_chunk_tokens_total{kind="real"}``
over that of ``{kind="padded"}`` (a launch's width, padding included). 100
would be no padding at all; a prompt's last chunk padded to 1,024 for a
re-ask's hundred tokens pulls it down. A program without the counter (the
parent of the PR that added it) or a window with no chunk launch gives
nothing."""
import readers
import stack

NAME, UNIT, LAYER, MOVES = "engine.chunk_fill_pct", "%", "engine admission", "ttft_p50_ms"
SERIES = "gridllm_engine_chunk_tokens_total"


def compute(run):
    if not stack.metric_values(run["worker_after"], SERIES):
        return None
    real = readers.counter_delta(run, "worker", SERIES, kind="real")
    padded = readers.counter_delta(run, "worker", SERIES, kind="padded")
    return 100.0 * real / padded if padded > 0 else None
