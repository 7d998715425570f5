"""The longest single collection of the window, as the upper edge of the
highest bucket of ``gridllm_process_gc_pause_seconds`` that rose (no finer
than the program's buckets); 0 if none rose. A long gap under a busy
phase is a collection only if this reaches it."""
import stages

NAME, UNIT, LAYER, MOVES = "host.gc_pause_max_ms", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return stages.highest_risen_ms(run, stages.GC_PAUSE)
