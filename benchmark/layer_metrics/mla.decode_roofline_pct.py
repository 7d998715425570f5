"""Latent attention's share of its roofline inside the verify (or decode)
programs, in the traced window: the least time the chip could take for one
launch's latent reads over the device time of the latent operations
(``mla.latent_ops`` inside ``readers.VERIFY_PROGRAMS``) a launch. The
least time is the longer of two (``mla.least_seconds``): the rows of the
mean live context over every layer, as the equations have them and read
once (``phases.kv_bytes_per_launch``), over the memory bandwidth; and
``latent_attn_flops`` of a launch's query rows over that context plus
``absorb_flops``, over the bf16 peak. Bound named: memory, at the cell's
1-5 query rows a slot (a 1,152-byte row takes 1.41 ns to read and its 5 x
16 x 2 x 1,088 operations 0.88 ns at the peak)."""
import mla
import phases
import readers

NAME, UNIT, LAYER, MOVES = ("mla.decode_roofline_pct", "%",
                            "latent attention", "itl_p95_ms")
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    _, n = phases.verify_launches(run)
    kv = phases.kv_bytes_per_launch(run)
    secs = sum(o["seconds"]
               for o in mla.latent_ops(run, readers.VERIFY_PROGRAMS))
    if not n or not secs or kv is None:
        return None
    least = mla.least_seconds(run, kv, mla.verify_rows_per_slot(run))
    return None if least is None else 100.0 * least / (secs / n)
