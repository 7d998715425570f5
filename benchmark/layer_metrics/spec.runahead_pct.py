"""Of the chain drafter's verify launches in the window, the share the
runner dispatched without drafts while an earlier launch was still to be
fetched: ``gridllm_spec_launches_total{mode="ahead"}`` over ahead +
serial. The runner goes ahead where no first proposal was accepted over
its last launches, so the share is high where ``spec.draft_hit_pct`` is
near nothing and 0 where drafts are accepted; there its host work
(``runner.host_ms_per_step``) overlaps the device's. Nothing where no such
launch was counted (speculation off, a draft model, or a program without
the counter)."""
import readers

NAME, UNIT, LAYER, MOVES = "spec.runahead_pct", "%", "engine runner (host loop)", "ttft_p50_ms"
SERIES = "gridllm_spec_launches_total"


def compute(run):
    serial, ahead = (readers.counter_delta(run, "worker", SERIES, mode=m)
                     for m in ("serial", "ahead"))
    if serial + ahead <= 0:
        return None
    return 100.0 * ahead / (serial + ahead)
