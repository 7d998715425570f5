"""What the ``moe.*`` readers share: how a routed family's expert layer is
found in a run. Works for any configuration whose file carries the
published keys ``moe_num_primary_experts``, ``hidden_size`` and
``moe_ffn_hidden_size`` and whose costs file has ``expert_bytes``;
anything else (a dense configuration, a program without the scopes or
the counters, such as the parent of the PR that added them) reads as
nothing, never as an error.

The program puts ``jax.named_scope("moe_experts")`` and ``"moe_router"``
around the operations (``models/mixtral.py``), which names them in the HLO
metadata, but the profiler's ``XLA Ops`` events carry the HLO line without
its metadata (``trace_reduce.py`` keeps 240 characters of it), so the
patterns here go by what that line does show, read off the chip's trace
(PERF.md, PR 33): an expert product has the stacked expert weights
``[.., X, E, F]`` / ``[.., X, F, E]`` or the all-experts intermediate
``[rows.., X, F]`` among its shapes, or is XLA's ``ragged-dot`` custom
call or a kernel named ``grouped_experts`` (``readers.GROUPED_OPS``); the
router is what produces or sorts float32 ``[rows.., X]``.
"""

from __future__ import annotations

import re

import phases
import readers
import stack

STEP_PROGRAMS = readers.VERIFY_PROGRAMS + "|" + readers.PREFILL_PROGRAMS


def shapes(spec: dict) -> tuple[int, int, int] | None:
    try:
        return (int(spec["moe_num_primary_experts"]), int(spec["hidden_size"]),
                int(spec["moe_ffn_hidden_size"]))
    except (KeyError, TypeError, ValueError):
        return None


def expert_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    x, e, f = s
    return (rf"ragged-dot|[\[,]{x},{e},{f}\]|[\[,]{x},{f},{e}\]"
            rf"|\[(\d+,)+{x},{f}\]|" + readers.GROUPED_OPS)


def router_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    x, e, _ = s
    return rf"f32\[(\d+,)+{x}\]|[\[,]{e},{x}\]"


def expert_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    """The grouped products' operations inside the step programs."""
    pat = expert_pattern(run["config"])
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat) if re.search(programs, o["program"])]


def router_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    pat, ex = router_pattern(run["config"]), expert_pattern(run["config"])
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat)
            if re.search(programs, o["program"]) and not re.search(ex, o["text"])]


def touched(before: str, after: str) -> float:
    """Experts touched (summed over layers and launches) between two
    ``/metrics`` texts."""
    return (stack.metric_sum(after, phases.TOUCHED)
            - stack.metric_sum(before, phases.TOUCHED))
