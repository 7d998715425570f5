"""The ragged attention kernel's share of its memory roofline in its
decode form, in the traced window, the first chip's time against the
first chip's share: the KV bytes one launch must read on one chip
(``phases.kv_bytes_per_launch``: the live contexts over every layer and
that chip's KV heads, or what the configuration's costs file says a launch
reads of them where a layer has a window) over the chip's memory bandwidth, over the device
time of the ``ragged_attention`` operations (``readers.RAGGED_OPS``) inside
the verify / decode programs, a launch. Bound named: memory (each key is read once for
1-5 query rows)."""
import re

import phases
import readers

NAME, UNIT, LAYER, MOVES = "kernel.ragged_decode_roofline_pct", "%", "kernels", "itl_p95_ms"


def compute(run):
    _, n = phases.verify_launches(run)
    kv, peak = phases.kv_bytes_per_launch(run), phases.hbm_bytes_per_s(run)
    secs = sum(o["seconds"] for o in readers.ops(run, readers.RAGGED_OPS)
               if re.search(readers.VERIFY_PROGRAMS, o["program"]))
    if not n or not secs or kv is None or peak is None:
        return None
    return 100.0 * (kv / peak) / (secs / n)
