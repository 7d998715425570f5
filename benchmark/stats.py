"""Arithmetic from outcomes to the end-to-end metrics. ``percentile`` is
``bench.py``'s ``_p95`` rule (nearest rank on the sorted sample)
generalised to any q."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile's rank."""
    return n - max(1, math.ceil(q * n))


def ttfts_ms(outcomes: list, worst_ms: float) -> list[float]:
    """Due time to first content frame, one per request attempted. A
    request that failed, was refused or did not finish enters at
    `worst_ms` (window + drain), the run's largest finite value."""
    out = []
    for o in outcomes:
        ok = o.done and not o.error and o.frames
        out.append(min((o.frames[0][0] - o.due) * 1e3, worst_ms) if ok
                   else worst_ms)
    return out


def client_ttfts_ms(run: dict) -> list[float]:
    """`ttfts_ms` of a window as the readers get it (``readers.py``)."""
    return ttfts_ms(run["outcomes"],
                    (run["seconds"] + run["drain_s"]) * 1e3)


def gaps_ms(outcomes: list, worst_ms: float) -> list[float]:
    """Gaps between successive content frames, pooled over all streams;
    a failed stream adds one gap of `worst_ms`."""
    out = []
    for o in outcomes:
        ts = [t for t, _ in o.frames]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
        if not o.done or o.error:
            out.append(worst_ms)
    return out


def tokens_by(outcomes: list, deadline: float) -> float:
    """Output tokens received by the monotonic time `deadline`. A frame
    carries text, not a count; a finished stream's characters are scaled
    to its ``eval_count`` (ids below 256 are bytes and may share one)."""
    total = 0.0
    for o in outcomes:
        chars = sum(c for _, c in o.frames)
        if not chars:
            continue
        scale = (o.eval_count / chars) if (o.done and o.eval_count) else 1.0
        total += scale * sum(c for t, c in o.frames if t <= deadline)
    return total


def failed(o) -> bool:
    return bool(o.error) or not o.done or not o.frames


def malformed(o, num_predict: int) -> str | None:
    """Why a finished stream is not well-formed, or None."""
    if failed(o):
        return None
    if o.eval_count == num_predict:
        return None
    if o.eval_count is not None and o.eval_count < num_predict and (
            o.done_reason == "stop"):
        return None  # an EOS
    return (f"request {o.index}: eval_count={o.eval_count} "
            f"num_predict={num_predict} done_reason={o.done_reason}")


def end_to_end(outcomes: list, t0: float, seconds: float,
               drain_s: float) -> dict[str, float]:
    worst = (seconds + drain_s) * 1e3
    ttft = ttfts_ms(outcomes, worst)
    gaps = gaps_ms(outcomes, worst) or [worst]
    return {
        "ttft_p50_ms": percentile(ttft, 0.50),
        "ttft_p85_ms": percentile(ttft, 0.85),
        # not judged since PR 26 (in shared_doc it sits on the step between
        # three-chunk and four-chunk documents); printed in a traced run's
        # end-to-end line, where earlier records can be compared with it
        "ttft_p90_ms": percentile(ttft, 0.90),
        "ttft_p95_ms": percentile(ttft, 0.95),
        "itl_p95_ms": percentile(gaps, 0.95),
        "out_tok_s": tokens_by(outcomes, t0 + seconds) / seconds,
    }
