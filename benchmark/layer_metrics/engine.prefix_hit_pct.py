"""Prompt pages served from the prefix cache over prompt pages admitted,
in the window (``gridllm_prefix_cache_hits_total`` and ``_misses_total``;
a page is ``pageSize`` prompt tokens)."""
import readers

NAME, UNIT, LAYER, MOVES = "engine.prefix_hit_pct", "%", "engine admission", "ttft_p85_ms"


def compute(run):
    hits = readers.counter_delta(run, "worker", "gridllm_prefix_cache_hits_total")
    miss = readers.counter_delta(run, "worker", "gridllm_prefix_cache_misses_total")
    return 100.0 * hits / (hits + miss) if hits + miss > 0 else None
