"""Mean device time of one launch of the verify (or decode) program, from
the device trace's ``XLA Modules`` line."""
import readers

NAME, UNIT, LAYER, MOVES = "step.verify_dev_ms", "ms", "programs", "itl_p95_ms"


def compute(run):
    secs, n = readers.programs(run, readers.VERIFY_PROGRAMS)
    return 1e3 * secs / n if n else None
