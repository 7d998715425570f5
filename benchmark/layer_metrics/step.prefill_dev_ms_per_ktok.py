"""Device time of the prefill and chunk programs over the thousands of
prompt tokens the engine dispatched while the trace ran
(``gridllm_engine_tokens_total{kind="prefill"}`` at the capture's two ends)."""
import readers
import stack

NAME, UNIT, LAYER, MOVES = "step.prefill_dev_ms_per_ktok", "ms", "programs", "ttft_p50_ms"


def compute(run):
    secs, n = readers.programs(run, readers.PREFILL_PROGRAMS)
    ends = run.get("trace_counters")
    if not n or not ends:
        return None
    toks = (stack.metric_sum(ends[1], "gridllm_engine_tokens_total", kind="prefill")
            - stack.metric_sum(ends[0], "gridllm_engine_tokens_total", kind="prefill"))
    return 1e3 * secs / (toks / 1e3) if toks > 0 else None
