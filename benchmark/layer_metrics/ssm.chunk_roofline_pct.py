"""The chunked scan's share of its roofline inside the mixed-chunk
program, in the traced window: the larger of ``ssd_chunk_flops`` of the
padded rows a launch ran over the chip's bf16 peak and ``ssd_chunk_bytes``
of them over its memory bandwidth (the engine's counters give the rows;
every Mamba-2 layer), over the device time of the ``ssd_chunk`` kernel a
launch. BOUND NAMED: memory at a chunk of 256 to 1,024 rows (5 P N
operations a token and head against a row's 33 KB and the state's 4 MB in
and out: 1.5 GFLOP a layer at 1,024 rows is 7.7 us of the MXU's peak, the
bytes 46 us of the bandwidth); the reader takes whichever is larger, so a
wider chunk changes the bound, not the reader. Cannot pass 100: the kernel
does at least the equations' operations and moves at least their bytes,
and the peaks are the chip's published ones."""
import readers
import ssm

NAME, UNIT, LAYER, MOVES = ("ssm.chunk_roofline_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["granite4hmicro.long_answers"]


def compute(run):
    _, n = readers.programs(run, ssm.CHUNK_PROGRAMS)
    secs = sum(o["seconds"] for o in ssm.kernel_ops(
        run, ssm.CHUNK_OP, ssm.CHUNK_PROGRAMS))
    rows, count, peaks = ssm.chunk_rows_per_launch(run), ssm.count(run), ssm.peaks(run)
    if not n or not secs or rows is None or count is None or peaks is None:
        return None
    spec = run["config"]
    least = max(count.ssd_chunk_flops(spec, rows) / peaks["bf16_flops_per_s"],
                count.ssd_chunk_bytes(spec, rows) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / n)
