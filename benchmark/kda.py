"""What the ``kda.*``, ``held.*`` and ``hybrid.*`` readers share: how
Kimi-Linear's delta-rule layers, its held experts and its two caches are
found in a run. Works for any configuration whose file carries
``linear_attn_config`` (``num_heads``, ``head_dim``), ``num_experts``
beside ``router_experts`` and whose costs file has ``kda_chunk_flops`` /
``kda_step_bytes`` / ``held_expert_bytes``; anything else (a configuration
of another family, a program without the kernels or the counters, such as
the parent of the PR that added them) reads as nothing, never as an error.

The two Pallas kernels are ``custom-call``s named ``%kda_chunk.N`` and
``%kda_step.N`` (``ops/linear_attn.py`` names them so; they are
``gdn_chunk`` / ``gdn_step``'s kernels, general over the decay's shape).
What runs around them is found by shapes from the published keys, as
``gdn.py`` does (the profiler's events carry the HLO line without its
metadata): the convolution and what feeds it has the q, k, v channels
side by side, ``H (2 dk + dv)`` (12,288) as a minor axis; the blocks' pair
terms, triangular systems and the layout copies around the kernels have a
head axis of ``H`` before a block of rows (``[.., H, 64, 128]``,
``[.., H, 64, 64]``, ``[.., H, 16, 16, 128]``, ``[.., H, 8, ..]``) or
before a single decay row (``[.., H, 1, 128]``); the L2 norms, the decay
and the gated norm are float32 ``[.., H, 128]``. THE LATENT LAYERS have 32
heads of 128 too (``q_nope``, the value half): their operations are told
apart by the latent's widths as a minor axis (512, 576, 640 or a query
head's 192) or its up-projection ``[512, 32, ..]`` and left out, as is every plain product (the model's hidden
size among an operation's shapes: the projections).

The held experts' products are found by their stacked weights ``[held, E,
F]`` / ``[held, F, E]``, the all-experts intermediate ``[rows.., held,
F]``, XLA's ``ragged-dot`` (the sorted form of a mixed launch), or a
kernel named ``grouped_experts`` (``readers.GROUPED_OPS``).
"""

from __future__ import annotations

import re

import costs
import gdn
import readers
import stack

CHUNK_OP = r"^%kda_chunk[.\d]* = .*custom-call\("
STEP_OP = r"^%kda_step[.\d]* = .*custom-call\("
STEP_PROGRAMS = gdn.STEP_PROGRAMS
CHUNK_PROGRAMS = gdn.CHUNK_PROGRAMS
PREFIX = "gridllm_state_prefix_total"
PICKS = "gridllm_moe_picks_total"

chunk_rows_per_launch = gdn.chunk_rows_per_launch
live_slots_per_launch = gdn.live_slots_per_launch
verify_rows = gdn.verify_rows
peaks = gdn.peaks


def shapes(spec: dict) -> tuple[int, int, int] | None:
    try:
        la = spec["linear_attn_config"]
        return int(la["num_heads"]), int(la["head_dim"]), int(la["head_dim"])
    except (KeyError, TypeError, ValueError):
        return None


def around_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    h, dk, dv = s
    c = h * (2 * dk + dv)
    return (rf"[\[,]{c}\]|f32\[(\d+,)*{h},({dk}|{dv})\]"
            rf"|,{h},(\d+,)?\d+,({dk}|{dv}|64|16|8)\]|triangular-solve")


def foreign_pattern(spec: dict) -> str:
    """Plain products (the hidden size among the shapes) and the latent
    layers' operations: the latent's widths or a query head's as a MINOR
    axis (a chunk of 512 rows is no latent), or the latent's up-projection
    ``[R, H, ..]``."""
    r = int(spec.get("kv_lora_rank") or 0)
    dr = int(spec.get("qk_rope_head_dim") or 0)
    dn = int(spec.get("qk_nope_head_dim") or 0)
    h = int(spec.get("num_attention_heads") or 0)
    lat = "|".join(str(n) for n in sorted(
        {r, r + dr, -(-(r + dr) // 128) * 128, dn + dr}) if n)
    return (rf"[\[,]{int(spec['hidden_size'])}[\],]|[\[,]({lat})\]"
            rf"|\[{r},{h},")


def kernel_ops(run: dict, which: str, programs: str) -> list[dict]:
    if shapes(run["config"]) is None:
        return []
    return [o for o in readers.ops(run, which)
            if re.search(programs, o["program"])]


def layer_ops(run: dict) -> list[dict]:
    """Both kernels and what runs around them, in every step program; no
    projection and nothing of the latent layers."""
    pat = around_pattern(run["config"])
    if pat is None:
        return []
    found = {o["key"]: o for o in readers.ops(run, CHUNK_OP + "|" + STEP_OP)}
    foreign = foreign_pattern(run["config"])
    found.update((o["key"], o) for o in readers.ops(run, pat)
                 if not re.search(foreign, o["text"]))
    return [o for o in found.values() if re.search(STEP_PROGRAMS, o["program"])]


def chunk_rule_ops(run: dict) -> list[dict]:
    """What the chunk program spends on the chunked delta rule: `layer_ops`
    of the chunk programs less the step kernel and the convolution."""
    s = shapes(run["config"])
    if s is None:
        return []
    h, dk, dv = s
    conv = rf"[\[,]{h * (2 * dk + dv)}\]"
    return [o for o in layer_ops(run)
            if re.search(CHUNK_PROGRAMS, o["program"])
            and not re.search(STEP_OP, o["text"])
            and not re.search(conv, o["text"])]


def count(run: dict):
    c = costs.of(run["config"])
    return c if hasattr(c, "kda_step_bytes") else None


def held_pattern(spec: dict) -> str | None:
    """The held experts' three products, in either form."""
    try:
        x, e, f = (int(spec["num_experts"]), int(spec["hidden_size"]),
                   int(spec["moe_intermediate_size"]))
        if "router_experts" not in spec:
            return None
    except (KeyError, TypeError, ValueError):
        return None
    return (rf"ragged-dot|[\[,]{x},{e},{f}\]|[\[,]{x},{f},{e}\]"
            rf"|\[(\d+,)+{x},{f}\]|" + readers.GROUPED_OPS)


def held_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    pat = held_pattern(run["config"])
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat) if re.search(programs, o["program"])]


def capture_delta(run: dict, name: str, **labels: str) -> float | None:
    """A counter's change over the capture (``trace_counters``)."""
    ends = run.get("trace_counters")
    if not ends:
        return None
    return (stack.metric_sum(ends[1], name, **labels)
            - stack.metric_sum(ends[0], name, **labels))
