"""The chunked delta rule's share of its roofline inside the mixed-chunk
program, in the traced window: ``gdn_chunk_flops`` of the padded rows a
launch ran (the engine's counters; every linear layer) over the chip's
bf16 peak, over the device time a launch of EVERYTHING the chunked rule
runs there (``gdn.chunk_rule_ops``: the ``gdn_chunk`` kernel, the blocks'
triangular systems and the layout copies around them, which XLA runs
outside the kernel; not the kernel's time alone, which is a quarter of
it). Bound named: compute (the state of a head pack stays in VMEM across
a launch's blocks; its bytes are read once). The operations are the
EQUATIONS' (7 dk dv a token and head), not the chunked form's, and the
peak is the chip's bf16 peak although the form multiplies in float32:
both read as distance from the roofline."""
import gdn
import readers

NAME, UNIT, LAYER, MOVES = ("gdn.chunk_roofline_pct", "%", "recurrent state",
                            "ttft_p50_ms")
CELLS = ["olmohybrid7b.agent_turns"]


def compute(run):
    _, n = readers.programs(run, gdn.CHUNK_PROGRAMS)
    if not gdn.kernel_ops(run, gdn.CHUNK_OP, gdn.CHUNK_PROGRAMS):
        return None
    secs = sum(o["seconds"] for o in gdn.chunk_rule_ops(run))
    rows, count, peaks = gdn.chunk_rows_per_launch(run), gdn.count(run), gdn.peaks(run)
    if not n or not secs or rows is None or count is None or peaks is None:
        return None
    least = count.gdn_chunk_flops(run["config"], rows) / peaks["bf16_flops_per_s"]
    return 100.0 * least / (secs / n)
