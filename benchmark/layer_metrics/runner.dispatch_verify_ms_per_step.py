"""The ``dispatch_verify`` phase a launch, wall: the launch's arguments
built on the host and the verify / decode jitted call returning (under a
mesh, its arguments placed on every chip)."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.dispatch_verify_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p == phases.LAUNCH)
