"""Host work a step: the runner's wall time in every phase but
``idle_wait`` and ``fetch``, over the launches of the window. On the
serial speculative path the device cannot overlap it: it is what the chip
waits for between two verify launches."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.host_ms_per_step", "ms", "engine runner (host loop)", "itl_p95_ms"


def compute(run):
    return phases.per_launch_ms(run, lambda p: p not in (phases.IDLE, phases.FETCH))
