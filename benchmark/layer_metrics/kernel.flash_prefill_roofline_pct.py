"""The flash prefill kernel's share of its compute roofline in the traced
window, the first chip's time against the first chip's share: the
floating-point operations its calls need on one chip
(``flash_prefill_flops`` of the configuration's costs at the bucket T each
call ran at, over ``chip_share``'s heads; T is read from the call's result
shape ``[T, one chip's KV heads, group, head dim]`` in the trace) over the
chip's bf16 peak, over the calls' device time. Bound named:
compute (at T = 512 the kernel's bytes over 819 GB/s are a tenth of its
operations over 197 TFLOP/s)."""
import re

import costs
import readers

NAME, UNIT, LAYER, MOVES = "kernel.flash_prefill_roofline_pct", "%", "kernels", "ttft_p95_ms"


def compute(run):
    spec, need, secs = run["config"], 0.0, 0.0
    count = costs.of(spec)
    share = count.chip_share(spec)
    if not share:
        return None
    for o in readers.ops(run, readers.FLASH_OPS):
        t, kvh, group, d = (int(x) for x in re.search(readers.FLASH_OPS, o["text"]).groups())
        if (kvh * group * share["heads"], d) != (
                spec["num_attention_heads"], costs.head_dim(spec)):
            continue
        need += o["count"] * count.flash_prefill_flops(spec, t) / share["heads"]
        secs += o["seconds"]
    if not secs:
        return None
    peak = costs.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / secs
