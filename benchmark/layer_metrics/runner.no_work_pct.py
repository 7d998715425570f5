"""Share of the runner's wall time (Σ all phases) in ``idle_wait``:
nothing pending, no live stream, nobody asked. The part of
``device.idle_pct`` that is the offered load's and not the program's;
``runner.unfed_pct`` is the other part."""
import phases
import stages

NAME, UNIT, LAYER, MOVES = "runner.no_work_pct", "%", "engine runner (host loop)", "out_tok_s"


def compute(run):
    idle = phases.window(run).get(phases.IDLE)
    return stages.share_of_wall_pct(run, idle[0] if idle else None)
