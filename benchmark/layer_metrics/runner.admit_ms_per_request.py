"""Runner wall time an admission costs: the ``admit`` phase (tokenise,
prefix lookup, page allocation) plus ``dispatch_prefill`` (the prefill and
chunk jitted calls returning), over ``_count{phase="admit"}``, which is
marked once a popped request. Every running stream waits this long."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.admit_ms_per_request", "ms", "engine admission", "ttft_p50_ms"


def compute(run):
    w = phases.window(run)
    n = w.get("admit", (0.0, 0.0))[1]
    if n <= 0:
        return None
    return 1e3 * (w["admit"][0] + w.get("dispatch_prefill", (0.0, 0.0))[0]) / n
