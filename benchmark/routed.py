"""What the ``routed.*`` and ``wincache.*`` readers share: how a Laguna
expert layer and its window layers' rings are found in a run. Works for
any configuration whose file carries the published keys ``num_experts``,
``num_experts_per_tok``, ``hidden_size``, ``moe_intermediate_size`` (and
``shared_expert_intermediate_size``) and whose costs file has
``expert_bytes`` and ``expert_flops``; anything else (a configuration of
another family, a program without the operations or the counters, such as
the parent of the PR that added them) reads as nothing, never as an
error.

The profiler's events carry the HLO line without its metadata (see
``moe.py``), so the patterns go by what that line shows, read off the
chip (``deploy/tpu_moe_forms.py --ops``; PERF.md, PR 45). The all-experts
form: the stacked expert weights ``[X, E, F]`` / ``[X, F, E]`` or the
intermediate ``[rows.., X, F]`` among an operation's shapes. The sorted
form: XLA's ``ragged-dot`` custom calls, and the gather and the weighted
scatter-add around them, whose arrays have rows x top-k rows (a mixed
launch's chunk and slots, a verify launch's slots x (K + 1), a decode
block's slots). The router produces or sorts float32 ``[rows.., X]`` from
``[E, X]``; the shared expert's products carry ``[E, Fs]`` / ``[Fs, E]``.
The counts are the model's NEED (top-k experts a row), so a share reads
the same whichever form computes it.
"""

from __future__ import annotations

import re

import readers
import stack

PREFIX = "gridllm_state_prefix_total"
STEP_PROGRAMS = readers.VERIFY_PROGRAMS + "|" + readers.PREFILL_PROGRAMS
MIXED_PROGRAMS = r"mixed_chunk"
ROUTED_CHUNK = 512          # the engine's width for a routed family


def shapes(spec: dict) -> dict | None:
    try:
        if spec.get("model_type") != "laguna":
            return None
        out = {k: int(spec[k]) for k in (
            "num_experts", "num_experts_per_tok", "hidden_size",
            "moe_intermediate_size")}
    except (KeyError, TypeError, ValueError):
        return None
    out["shared"] = int(spec.get("shared_expert_intermediate_size") or 0)
    return out


def _sorted_rows(spec: dict) -> list[int]:
    """Rows x top-k of the launches a deployment runs."""
    env = spec.get("env", {})
    slots = int(env.get("GRIDLLM_MAX_BATCH_SLOTS", 8))
    k1 = int(env.get("GRIDLLM_SPEC_K", 4)) + 1
    top = int(spec["num_experts_per_tok"])
    return sorted({(ROUTED_CHUNK + slots) * top, slots * k1 * top, slots * top})


def product_pattern(spec: dict) -> str | None:
    """The routed experts' three products, in either form or in a kernel
    named ``grouped_experts`` (``readers.GROUPED_OPS``)."""
    s = shapes(spec)
    if s is None:
        return None
    x, e, f = s["num_experts"], s["hidden_size"], s["moe_intermediate_size"]
    rows = "|".join(str(n) for n in _sorted_rows(spec))
    return (rf"ragged-dot|[\[,]{x},{e},{f}\]|[\[,]{x},{f},{e}\]"
            rf"|\[(\d+,)+{x},{f}\]|\[({rows}),({e}|{f})\]|"
            + readers.GROUPED_OPS)


def layer_pattern(spec: dict) -> str | None:
    """Router, routed and shared products of an expert layer."""
    s, products = shapes(spec), product_pattern(spec)
    if s is None:
        return None
    x, e, fs = s["num_experts"], s["hidden_size"], s["shared"]
    shared = rf"|[\[,]{e},{fs}\]|[\[,]{fs},{e}\]" if fs else ""
    return products + rf"|f32\[(\d+,)+{x}\]|[\[,]{e},{x}\]" + shared


def _ops(run: dict, pat: str | None, programs: str) -> list[dict]:
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat) if re.search(programs, o["program"])]


def layer_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    return _ops(run, layer_pattern(run["config"]), programs)


def product_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    return _ops(run, product_pattern(run["config"]), programs)


def capture_delta(run: dict, name: str, **labels: str) -> float | None:
    """A counter's change over the capture (``trace_counters``)."""
    ends = run.get("trace_counters")
    if not ends:
        return None
    return (stack.metric_sum(ends[1], name, **labels)
            - stack.metric_sum(ends[0], name, **labels))
