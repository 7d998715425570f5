"""Bytes of one token's cache row in one layer as the pool stores it over
what K and V per head would take: ``gridllm_kv_row_bytes{kind="latent"}``
over ``gridllm_kv_row_bytes_per_head_equiv`` (set once at pool creation;
the worker's ``/metrics`` after the window). 11.25 for DeepSeek-V2-Lite's
row as the equations have it (576 of 5,120 values), 12.5 as stored (640).
A family that stores K and V per head has no ``latent`` row and reads as
nothing."""
import stack

NAME, UNIT, LAYER, MOVES = "kv.latent_row_pct", "%", "KV pool", "out_tok_s"
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    text = run.get("worker_after") or ""
    row = stack.metric_sum(text, "gridllm_kv_row_bytes", kind="latent")
    equiv = stack.metric_sum(text, "gridllm_kv_row_bytes_per_head_equiv")
    if row <= 0 or equiv <= 0:
        return None
    return 100.0 * row / equiv
