"""What the stage readers, the unfed clock's and the stall witnesses'
share (ISSUE 59), built on ``phases.py`` and ``stack.py`` as they stand.

``gridllm_engine_stage_seconds{model,phase,stage}`` divides a phase of
``gridllm_engine_phase_seconds`` one level down, on the same clock and by
the same flush rule (``obs/perf.py`` ``PhaseClock.stage``): ``admit`` into
``tokenize`` and ``match``, ``dispatch_prefill`` into ``seed``, ``chunk``
(one stretch a chunk launch) and ``book``, ``fetch`` into ``wait`` and
``copy``, ``ingest``'s ``emit``. ``gridllm_engine_unfed_seconds_total`` is
the runner's busy wall time with no launch in flight;
``gridllm_process_gc_pause_seconds{generation}`` and
``gridllm_worker_loop_lag_seconds`` are histograms on the buckets of the
phase series. A program without a series (the parent of the PR that added
it) gives nothing and every reader built on this returns None.
"""

from __future__ import annotations

import phases
import stack

SERIES = "gridllm_engine_stage_seconds"
UNFED = "gridllm_engine_unfed_seconds_total"
GC_PAUSE = "gridllm_process_gc_pause_seconds"
LOOP_LAG = "gridllm_worker_loop_lag_seconds"


def totals(text: str) -> dict[tuple[str, str], tuple[float, float]]:
    """{(phase, stage): (seconds, stretches)} of one ``/metrics`` text,
    over models."""
    out: dict[tuple[str, str], list[float]] = {}
    for i, suffix in enumerate(("_sum", "_count")):
        for labels, v in stack.metric_values(text, SERIES + suffix).items():
            d = dict(labels)
            out.setdefault((d.get("phase"), d.get("stage")), [0.0, 0.0])[i] += v
    return {k: (s, n) for k, (s, n) in out.items()}


def window(run: dict) -> dict[tuple[str, str], tuple[float, float]]:
    """{(phase, stage): (seconds, stretches)} over the window."""
    b = totals(run["worker_before"])
    return {k: (s - b.get(k, (0.0, 0.0))[0], n - b.get(k, (0.0, 0.0))[1])
            for k, (s, n) in totals(run["worker_after"]).items()}


def stage_ms(run: dict, phase: str, stage: str,
             per: str | None = None) -> float | None:
    """The stage's seconds over the window, in ms a stretch of its own,
    or a stretch of the PHASE `per` (``admit``: an admission;
    ``dispatch_verify``: a launch). None without the stage's series or
    where the divisor did not move."""
    w = window(run)
    if (phase, stage) not in w:
        return None
    secs, n = w[phase, stage]
    if per is not None:
        n = phases.window(run).get(per, (0.0, 0.0))[1]
    return 1e3 * secs / n if n > 0 else None


def runner_wall_s(run: dict) -> float:
    """The runner thread's wall time over the window: its phases
    partition it, so their sum is the time between the two scrapes as the
    program itself kept it."""
    return sum(s for s, _ in phases.window(run).values())


def share_of_wall_pct(run: dict, seconds: float | None) -> float | None:
    wall = runner_wall_s(run)
    if seconds is None or wall <= 0:
        return None
    return 100.0 * seconds / wall


def unfed_s(run: dict) -> float | None:
    """The unfed counter's change over the window; None where the program
    serves no such counter."""
    if not stack.metric_values(run["worker_after"], UNFED):
        return None
    return (stack.metric_sum(run["worker_after"], UNFED)
            - stack.metric_sum(run["worker_before"], UNFED))


def _risen(run: dict, series: str) -> dict | None:
    """The histogram's change over the window (summed over its label
    sets); None where the program serves no sample of it."""
    after = stack.histogram(run["worker_after"], series)
    if not after["buckets"]:
        return None
    return stack.histogram_delta(
        stack.histogram(run["worker_before"], series), after)


def highest_risen_ms(run: dict, series: str) -> float | None:
    """The upper edge, in ms, of the highest bucket of `series` that rose
    over the window: the longest single observation, no finer than the
    program's buckets (an observation past the last edge reads as that
    edge: "at least"). 0 where none rose; None without the series."""
    h = _risen(run, series)
    if h is None:
        return None
    top = below = 0.0
    for ub, cum in h["buckets"]:
        if cum > below:
            top = ub
        below = cum
    edges = [ub for ub, _ in h["buckets"] if ub != float("inf")]
    return 1e3 * min(top, edges[-1]) if edges else None


def sum_ms_per_s(run: dict, series: str) -> float | None:
    """The histogram's ``_sum`` over the window, in ms a second of the
    runner's wall time."""
    h = _risen(run, series)
    wall = runner_wall_s(run)
    if h is None or wall <= 0:
        return None
    return 1e3 * h["sum"] / wall
