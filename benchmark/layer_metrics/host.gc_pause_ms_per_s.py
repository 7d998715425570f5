"""The cyclic collector's pauses in the worker process, ms a second of
the runner's wall time: ``gridllm_process_gc_pause_seconds_sum`` (every
generation) over the window. Whatever thread collects holds the
interpreter, so the runner waits through each."""
import stages

NAME, UNIT, LAYER, MOVES = "host.gc_pause_ms_per_s", "ms/s", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return stages.sum_ms_per_s(run, stages.GC_PAUSE)
