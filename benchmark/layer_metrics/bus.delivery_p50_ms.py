"""Median publish-to-delivery latency on the bus, gateway and worker
sides together: ``gridllm_bus_delivery_latency_seconds``, its change over
the window, interpolated inside the bucket."""
import readers
import stack

NAME, UNIT, LAYER, MOVES = "bus.delivery_p50_ms", "ms", "bus", "itl_p95_ms"


def compute(run):
    name = "gridllm_bus_delivery_latency_seconds"
    g, w = readers.hist_delta(run, "gateway", name), readers.hist_delta(run, "worker", name)
    both = {"count": g["count"] + w["count"], "sum": g["sum"] + w["sum"],
            "buckets": sorted(
                (ub, dict(g["buckets"]).get(ub, 0.0) + dict(w["buckets"]).get(ub, 0.0))
                for ub in {u for u, _ in g["buckets"]} | {u for u, _ in w["buckets"]})}
    q = stack.histogram_quantile(both, 0.5)
    return None if q is None else q * 1e3
