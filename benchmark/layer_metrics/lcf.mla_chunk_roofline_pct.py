"""Latent attention's share of its roofline inside the mixed-chunk program
of a LongCat block, in the traced window, in the absorbed form: the least
time the chip could take for one launch's chunk region over the 8 pool
layers (``mla.least_seconds`` through ``lcf.view``: the longer of the prefix
rows read once over the memory bandwidth and ``latent_attn_flops`` +
``absorb_flops`` of ``longcat_flash_costs`` over the bf16 peak) over the
device time of the latent operations (``mla.latent_ops`` inside
``mla.CHUNK_PROGRAMS``) a launch. A launch's chunk is its padded width of
query rows over the mean positions a launch attends (``mla.chunk_context``).
Bound named: compute (512 x 64 query rows a key: 71.3 MFLOP a 1,152-byte
row)."""
import lcf
import mla
import readers

NAME, UNIT, LAYER, MOVES = ("lcf.mla_chunk_roofline_pct", "%",
                            "latent attention", "itl_p95_ms")
CELLS = ["longcat.long_doc"]


def compute(run):
    seen = lcf.view(run)
    if seen is None:
        return None
    _, n = readers.programs(run, mla.CHUNK_PROGRAMS)
    secs = sum(o["seconds"] for o in mla.latent_ops(run, mla.CHUNK_PROGRAMS))
    ctx = mla.chunk_context(run)
    if not n or not secs or ctx is None:
        return None
    launches, _, padded = mla.chunk_launches(run)
    least = mla.least_seconds(seen, None, padded / launches, ctx=ctx)
    return None if least is None else 100.0 * least / (secs / n)
