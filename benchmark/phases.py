"""What the ``runner.*`` readers and the roofline readers share: the
engine runner's phase series, the verify step's context counter and the
routed families' touched-experts counter, a launch of the capture.

``gridllm_engine_phase_seconds{model,phase}`` partitions the runner
thread's wall time (``obs/perf.py`` ``PhaseClock``): ``idle_wait``, ``ctl``,
``admit``, ``dispatch_prefill``, ``draft``, ``dispatch_verify``, ``fetch``,
``ingest``. ``_count{phase="dispatch_verify"}`` is the number of verify /
decode launches. A program without the series (the parent of the PR that
added it) gives {} and every reader built on this returns None.
"""

from __future__ import annotations

import functools

import costs
import readers
import stack

SERIES = "gridllm_engine_phase_seconds"
CTX_TOKENS = "gridllm_engine_verify_ctx_tokens_total"
TOUCHED = "gridllm_moe_experts_touched_total"
IDLE, FETCH, LAUNCH = "idle_wait", "fetch", "dispatch_verify"


def totals(text: str) -> dict[str, tuple[float, float]]:
    """{phase: (seconds, stretches)} of one ``/metrics`` text, over models."""
    out: dict[str, list[float]] = {}
    for i, suffix in enumerate(("_sum", "_count")):
        for labels, v in stack.metric_values(text, SERIES + suffix).items():
            out.setdefault(dict(labels).get("phase"), [0.0, 0.0])[i] += v
    return {p: (s, n) for p, (s, n) in out.items()}


def between(before: str, after: str) -> dict[str, tuple[float, float]]:
    """{phase: (seconds, stretches)} between two ``/metrics`` texts."""
    b = totals(before)
    return {p: (s - b.get(p, (0.0, 0.0))[0], n - b.get(p, (0.0, 0.0))[1])
            for p, (s, n) in totals(after).items()}


def window(run: dict) -> dict[str, tuple[float, float]]:
    return between(run["worker_before"], run["worker_after"])


def per_launch_ms(run: dict, keep) -> float | None:
    """Σ seconds of the phases `keep(phase)` picks, a launch, in ms."""
    w = window(run)
    launches = w.get(LAUNCH, (0.0, 0.0))[1]
    if launches <= 0:
        return None
    return 1e3 * sum(s for p, (s, _) in w.items() if keep(p)) / launches


def chip_share(run: dict) -> dict | None:
    """One chip's share of the configuration's counts under its mesh
    (``costs.chip_share``); None where the costs have no rule."""
    return costs.of(run["config"]).chip_share(run["config"])


def capture_per_launch(run: dict, name: str) -> float | None:
    """A counter's change over the capture a verify / decode launch: both
    from ``trace_counters``, the worker's ``/metrics`` at the capture's two
    ends. None without them, without a launch or where the counter did not
    move (a program or a family that has no such counter)."""
    ends = run.get("trace_counters")
    if not ends:
        return None
    launches = between(*ends).get(LAUNCH, (0.0, 0.0))[1]
    moved = stack.metric_sum(ends[1], name) - stack.metric_sum(ends[0], name)
    if launches <= 0 or moved <= 0:
        return None
    return moved / launches


def touched_per_launch(run: dict) -> float | None:
    """Routed experts with at least one live row, summed over the layers,
    a verify / decode launch of the capture: what a launch has to read of
    the experts it holds, in experts (a costs file's ``one_expert_bytes``
    or ``expert_bytes`` each). None for a dense family."""
    return capture_per_launch(run, TOUCHED)


def kv_bytes_per_launch(run: dict) -> float | None:
    """Mean KV bytes one verify / decode launch has to read ON ONE CHIP,
    over the capture. A costs file that defines ``kv_launch_bytes(spec,
    per_launch)`` says what a launch READS of the cache, from the program's
    own counters (``per_launch(name)`` is ``capture_per_launch(run, name)``):
    a window, a ring or a selection leaves part of a context unread. Without
    it: the context-token counter's change a launch times the bytes of one
    position over every layer. Either way over the chips the KV heads are
    split across."""
    share, count = chip_share(run), costs.of(run["config"])
    if not share:
        return None
    if hasattr(count, "kv_launch_bytes"):
        whole = count.kv_launch_bytes(
            run["config"], functools.partial(capture_per_launch, run))
        return None if whole is None else whole / share["kv"]
    tokens = capture_per_launch(run, CTX_TOKENS)
    if tokens is None:
        return None
    return tokens * (count.kv_bytes_per_token(run["config"]) / share["kv"])


def verify_launches(run: dict) -> tuple[float, int]:
    """(device seconds, launches) of the verify / decode programs in the
    traced window, on the first chip."""
    return readers.programs(run, readers.VERIFY_PROGRAMS)


def hbm_bytes_per_s(run: dict) -> float | None:
    """The chip's published memory bandwidth; None in a CPU rehearsal,
    which has no roofline (an accelerator missing from ``peaks.json`` is
    still an error, never a default)."""
    if run["device"]["platform"] == "cpu":
        return None
    return costs.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
