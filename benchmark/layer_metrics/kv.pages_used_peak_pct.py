"""Most KV pages in use at any sample of the window
(``gridllm_engine_kv_pages_used``, every half second) over the pool's
size (the worker's ``kv pool sized`` record)."""
import readers

NAME, UNIT, LAYER, MOVES = "kv.pages_used_peak_pct", "%", "KV pool", "out_tok_s"


def compute(run):
    used = readers.gauge_samples(run, "gridllm_engine_kv_pages_used")
    pages = (run.get("pool") or {}).get("pages")
    return 100.0 * max(used) / pages if used and pages else None
