"""The chunked delta rule's share of its roofline inside the mixed-chunk
program, in the traced window: ``kda_chunk_flops`` of the padded rows a
launch ran (the engine's counters; every KDA layer) over the chip's bf16
peak, over the device time a launch of EVERYTHING the chunked rule runs
there (``kda.chunk_rule_ops``: the ``kda_chunk`` kernel, the blocks' pair
terms under a decay a key channel, their triangular systems and the layout
copies, which XLA runs outside the kernel). Bound named: compute (a
head's state stays in VMEM across a launch's blocks). The operations are
the EQUATIONS' (7 dk dv a token and head), not the chunked form's, and the
peak is the chip's bf16 peak although the form multiplies in float32: both
read as distance from the roofline."""
import kda
import readers

NAME, UNIT, LAYER, MOVES = ("kda.chunk_roofline_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["kimilinear.agent_turns"]


def compute(run):
    _, n = readers.programs(run, kda.CHUNK_PROGRAMS)
    if not kda.kernel_ops(run, kda.CHUNK_OP, kda.CHUNK_PROGRAMS):
        return None
    secs = sum(o["seconds"] for o in kda.chunk_rule_ops(run))
    rows, count, peaks = kda.chunk_rows_per_launch(run), kda.count(run), kda.peaks(run)
    if not n or not secs or rows is None or count is None or peaks is None:
        return None
    least = count.kda_chunk_flops(run["config"], rows) / peaks["bf16_flops_per_s"]
    return 100.0 * least / (secs / n)
