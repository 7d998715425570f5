"""The flash prefill kernel's share of its compute roofline in the traced
window: the floating-point operations its calls need
(``costs.flash_prefill_flops`` at the bucket T each call ran at, read from
the call's result shape ``[T, KV heads, group, head dim]`` in the trace)
over the chip's bf16 peak, over the calls' device time. Bound named:
compute (at T = 512 the kernel's bytes over 819 GB/s are a tenth of its
operations over 197 TFLOP/s)."""
import re

import costs
import readers

NAME, UNIT, LAYER, MOVES = "kernel.flash_prefill_roofline_pct", "%", "kernels", "ttft_p95_ms"


def compute(run):
    spec, need, secs = run["config"], 0.0, 0.0
    for o in readers.ops(run, readers.FLASH_OPS):
        t, kvh, group, d = (int(x) for x in re.search(readers.FLASH_OPS, o["text"]).groups())
        if (kvh * group, d) != (spec["num_attention_heads"], costs.head_dim(spec)):
            continue
        need += o["count"] * costs.flash_prefill_flops(spec, t)
        secs += o["seconds"]
    if not secs:
        return None
    peak = costs.peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / secs
