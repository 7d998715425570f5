"""The mean first token at the client over every request of the window
(``stats.ttfts_ms``: a failed request enters at window + drain), beside
``gen.ttft_p50_ms``: where the median stands on the step between cached
and cold admissions the mean follows the work, which every seed carries
alike (PERF.md section 2 has its spreads)."""
import stats

NAME, UNIT, LAYER, MOVES = ("gen.ttft_mean_ms", "ms", "load generator",
                            "itl_p95_ms")
CELLS = ["mistral7b.shared_doc", "dsv2lite.shared_doc", "kimilinear.agent_turns"]


def compute(run):
    ttft = stats.client_ttfts_ms(run)
    return sum(ttft) / len(ttft) if ttft else None
