"""Share of the runner's busy wall time (every phase but ``idle_wait``)
spent in ``fetch``, blocked on the device: higher = device-bound. Over the
whole untraced-quality window, from counters: the counterpart of
``device.idle_pct``, which sees 5 s of a traced run."""
import phases

NAME, UNIT, LAYER, MOVES = "runner.fetch_wait_pct", "%", "engine runner (host loop)", "out_tok_s"


def compute(run):
    w = phases.window(run)
    busy = sum(s for p, (s, _) in w.items() if p != phases.IDLE)
    return 100.0 * w[phases.FETCH][0] / busy if phases.FETCH in w and busy > 0 else None
