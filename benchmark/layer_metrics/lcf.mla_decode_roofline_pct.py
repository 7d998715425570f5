"""Latent attention's share of its roofline inside the verify (or decode)
programs of a LongCat block, in the traced window: the least time the chip
could take for one launch's latent reads over the 8 pool layers
(``mla.least_seconds`` through ``lcf.view``: the longer of the live
contexts' rows read once, ``phases.kv_bytes_per_launch``, over the memory
bandwidth, and ``latent_attn_flops`` of a launch's query rows over that
context plus ``absorb_flops``, over the bf16 peak) over the device time of
the latent operations (``mla.latent_ops`` inside
``readers.VERIFY_PROGRAMS``) a launch. Bound named: compute, at the cell's
5 query rows a slot of 64 heads (a 1,152-byte row takes 1.41 ns to read and
its 5 x 64 x 2 x 1,088 operations 3.5 ns at the peak)."""
import lcf
import mla
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("lcf.mla_decode_roofline_pct", "%",
                            "latent attention", "itl_p95_ms")
CELLS = ["longcat.long_doc"]


def compute(run):
    seen = lcf.view(run)
    if seen is None:
        return None
    _, n = phases.verify_launches(run)
    kv = phases.kv_bytes_per_launch(run)
    secs = sum(o["seconds"]
               for o in mla.latent_ops(run, readers.VERIFY_PROGRAMS))
    if not n or not secs or kv is None:
        return None
    least = mla.least_seconds(seen, kv, mla.verify_rows_per_slot(run))
    return None if least is None else 100.0 * least / (secs / n)
