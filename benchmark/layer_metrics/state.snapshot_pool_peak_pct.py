"""Most entries of the state-snapshot pool in use at any sample of the
window (``gridllm_state_snapshot_pool_used``, every half second) over the
pool's size (``gridllm_state_snapshot_pool_capacity``)."""
import readers

NAME, UNIT, LAYER, MOVES = ("state.snapshot_pool_peak_pct", "%",
                            "recurrent state", "out_tok_s")
CELLS = ["olmohybrid7b.agent_turns"]


def compute(run):
    used = readers.gauge_samples(run, "gridllm_state_snapshot_pool_used")
    size = readers.gauge_samples(run, "gridllm_state_snapshot_pool_capacity")
    return 100.0 * max(used) / max(size) if used and size and max(size) else None
