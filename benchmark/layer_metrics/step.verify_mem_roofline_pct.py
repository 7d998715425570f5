"""The verify (or decode) program's share of its memory roofline in the
traced window, the first chip's time against the first chip's share: the
bytes one launch must read on one chip (``step_weight_bytes`` of the
configuration's costs over ``chip_share``'s weights + the mean live KV
bytes a launch, ``phases.kv_bytes_per_launch``: the engine's context-token
counter over the capture, not a sampled gauge) over the chip's memory
bandwidth, over the program's mean device time a launch. Bound named:
memory (a step at 1-16 rows reads 9 GB of weights for under 1 TFLOP)."""
import costs
import phases

NAME, UNIT, LAYER, MOVES = "step.verify_mem_roofline_pct", "%", "programs", "itl_p95_ms"


def compute(run):
    secs, n = phases.verify_launches(run)
    kv, peak = phases.kv_bytes_per_launch(run), phases.hbm_bytes_per_s(run)
    if not n or kv is None or peak is None:
        return None
    spec = run["config"]
    need = (costs.of(spec).step_weight_bytes(spec)
            / phases.chip_share(run)["weights"] + kv)
    return 100.0 * (need / peak) / (secs / n)
