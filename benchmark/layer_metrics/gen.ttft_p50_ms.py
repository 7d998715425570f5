"""The median first token at the client (``stats.ttfts_ms``: due time to
first content frame, a failed request at window + drain) in the cells
whose end-to-end list does not judge it: there the median stands between
two modes of the mix (PERF.md section 2) and no bound the contract allows
holds it. The same arithmetic as ``ttft_p50_ms``, read in the traced run."""
import stats

NAME, UNIT, LAYER, MOVES = ("gen.ttft_p50_ms", "ms", "load generator",
                            "itl_p95_ms")
CELLS = ["mistral7b.shared_doc", "dsv2lite.shared_doc", "kimilinear.agent_turns"]


def compute(run):
    ttft = stats.client_ttfts_ms(run)
    return stats.percentile(ttft, 0.50) if ttft else None
