"""Mean control-plane transit a request: the gateway's
``gridllm_critical_path_seconds{segment="dispatch"}`` (what of a request's
traced latency no queue, prefill or decode span covers: gateway to bus to
worker and back), sum over count of its change over the window. Nothing
unless the gateway decomposed at least 90 % of the window's finished
requests (it does so once both halves of a trace have arrived)."""
import stack
import stats

NAME, UNIT, LAYER, MOVES = "path.dispatch_mean_ms", "ms", "HTTP API / worker", "ttft_p50_ms"
SERIES = "gridllm_critical_path_seconds"


def compute(run):
    def delta(suffix):
        return (stack.metric_sum(run["gateway_after"], SERIES + suffix, segment="dispatch")
                - stack.metric_sum(run["gateway_before"], SERIES + suffix, segment="dispatch"))

    finished = sum(not stats.failed(o) for o in run["outcomes"])
    n = delta("_count")
    if n <= 0 or n < 0.9 * finished:
        return None
    return 1e3 * delta("_sum") / n
