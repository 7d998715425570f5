"""The ``ctl`` phase a launch, wall: the cancel / suspend drain and the
runner loop's own bookkeeping between two iterations."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.ctl_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p == "ctl")
