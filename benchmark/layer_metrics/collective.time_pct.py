"""Device time of the cross-chip collectives over device busy time, on the
first chip: the operations whose HLO text is an ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute``. An asynchronous pair counts once, by its ``-done``
(the ``-start`` only issues it); on the v5e the trace shows the synchronous
forms (PERF.md). A configuration with no mesh has no collectives to read:
nothing, not 0."""
import costs
import readers

NAME, UNIT, LAYER, MOVES = "collective.time_pct", "%", "collectives", "itl_p95_ms"
CELLS = ["nemo12b-tp4.chat"]
# `` all-reduce(``, `` all-gather-done(``; never `` all-gather-start(``
COLLECTIVE_OPS = (r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
                  r"collective-permute)(-done)?\(")


def compute(run):
    if costs.mesh_size(run["config"]) < 2:
        return None
    busy = readers.first_device_busy_s(run)
    if not busy:
        return None
    return 100.0 * sum(
        o["seconds"] for o in readers.ops(run, COLLECTIVE_OPS)) / busy
