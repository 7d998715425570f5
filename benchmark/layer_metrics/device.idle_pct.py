"""Share of the traced window in which no operation ran on the device:
1 - union of the ``XLA Ops`` intervals over the window, mean over chips."""
NAME, UNIT, LAYER, MOVES = "device.idle_pct", "%", "device", "out_tok_s"


def compute(run):
    return (run.get("trace") or {}).get("idle_pct")
