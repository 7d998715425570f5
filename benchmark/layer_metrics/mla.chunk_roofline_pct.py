"""Latent attention's share of its roofline inside the mixed-chunk
program, in the traced window, in the form the program runs the chunk
region in (absorbed: PERF.md, PR 36): the least time the chip could take
for one launch's chunk region over the device time of the latent
operations (``mla.latent_ops`` inside ``mla.CHUNK_PROGRAMS``) a launch. A
launch's chunk is its padded width of query rows (the engine's counters:
padding is computed too) over the mean positions a launch attends
(``mla.chunk_context``: its prefix and, causally, half of its own rows).
The least time is ``mla.least_seconds`` at those rows: the longer of the
prefix rows read once over the memory bandwidth and ``latent_attn_flops``
(absorbed) + ``absorb_flops`` over the bf16 peak. Bound named: compute (512
x 16 query rows a key: 17.8 MFLOP a 1,152-byte row)."""
import mla
import readers

NAME, UNIT, LAYER, MOVES = ("mla.chunk_roofline_pct", "%",
                            "latent attention", "itl_p95_ms")
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    _, n = readers.programs(run, mla.CHUNK_PROGRAMS)
    secs = sum(o["seconds"] for o in mla.latent_ops(run, mla.CHUNK_PROGRAMS))
    ctx = mla.chunk_context(run)
    if not n or not secs or ctx is None:
        return None
    launches, _, padded = mla.chunk_launches(run)
    least = mla.least_seconds(run, None, padded / launches, ctx=ctx)
    return None if least is None else 100.0 * least / (secs / n)
