"""How late the load generator sent: sent - due, 95th percentile."""
import stats

NAME, UNIT, LAYER, MOVES = "gen.late_p95_ms", "ms", "load generator", "ttft_p50_ms"


def compute(run):
    late = [(o.sent - o.due) * 1e3 for o in run["outcomes"] if o.sent is not None]
    return stats.percentile(late, 0.95) if late else None
