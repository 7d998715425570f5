"""The verify (or decode) program's share of the chip's peak memory
bandwidth in the traced window, the benchmark's one share of a WHOLE
step's peak, the first chip's time against the first chip's share: the
bytes one launch has to read on one chip (``step_weight_bytes`` of the
configuration's costs over ``chip_share``'s weights + the mean live KV
bytes a launch, ``phases.kv_bytes_per_launch``: the engine's context-token
counter over the capture, with each layer's window applied where the costs
file has ``kv_launch_bytes``; not a sampled gauge) over the chip's memory
bandwidth, over the program's mean device time a launch. A routed family
is charged for the experts its live rows TOUCHED, not for every expert
held (``phases.touched_per_launch`` handed to a costs file whose
``step_weight_bytes`` takes ``touched``): a launch that reads fewer
experts than it holds cannot read over 100. Where the counter did not
move (a dense family) every weight is charged. Bound named: memory (a step
at 1-16 rows reads 9 GB of weights for under 1 TFLOP)."""
import inspect

import costs
import phases

NAME, UNIT, LAYER, MOVES = "step.verify_mem_mfu_pct", "%", "programs", "itl_p95_ms"


def compute(run):
    secs, n = phases.verify_launches(run)
    kv, peak = phases.kv_bytes_per_launch(run), phases.hbm_bytes_per_s(run)
    if not n or kv is None or peak is None:
        return None
    spec = run["config"]
    step_bytes, touched = costs.of(spec).step_weight_bytes, phases.touched_per_launch(run)
    if touched is None or "touched" not in inspect.signature(step_bytes).parameters:
        weights = step_bytes(spec)
    else:
        weights = step_bytes(spec, touched)
    need = weights / phases.chip_share(run)["weights"] + kv
    return 100.0 * (need / peak) / (secs / n)
