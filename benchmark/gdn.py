"""What the ``gdn.*`` and ``state.*`` readers share: how a hybrid family's
gated delta-rule layers and its recurrent state are found in a run. Works
for any configuration whose file carries the published keys
``linear_num_key_heads``, ``linear_key_head_dim``,
``linear_value_head_dim`` and ``linear_conv_kernel_dim`` and whose costs
file has ``gdn_chunk_flops`` / ``gdn_step_bytes``; anything else (a
configuration of another family, a program without the kernels or the
counters, such as the parent of the PR that added them) reads as nothing,
never as an error.

The two Pallas kernels are ``custom-call``s named after the functions that
wrap them (``%gdn_chunk.N``, ``%gdn_step.N``: ``readers.py`` on names).
What runs around them is found by shapes from the published keys, as
``moe.py`` and ``mla.py`` do (the profiler's events carry the HLO line
without its metadata): the convolution and what feeds it has the q, k, v
channels side by side, ``H (2 dk + dv)`` (11,520) as a minor axis; the
gated norm, the L2 norms and the blocks' triangular systems have a head
axis of ``H`` before ``dk``, ``dv`` or a block of rows (``[.., H, 96]``,
``[.., H, 192]``, ``[.., H, 64, 64]``), or are XLA's ``triangular-solve``;
the packed state and what the kernels hand back have ``H dv`` (5,760) as
a minor axis. The full layers' heads are ``[.., H, 128]`` and match none
of these. The layer's PROJECTIONS carry some of the same shapes (``W_v``
and ``W_g`` give ``[rows, H dv]``, ``W_o`` takes it, their weights are
``[hidden, H dv]``) and are plain products, not the layer's own part: an
operation whose line shows the model's hidden size as an axis (its input
or output rows, a weight, a weight's copy) is left out (PR 42's review:
on one trace ``gdn.time_pct`` read 31.4 with them in and 17.5 without).
"""

from __future__ import annotations

import re

import costs
import phases
import readers
import stack

CHUNK_OP = r"^%gdn_chunk[.\d]* = .*custom-call\("
STEP_OP = r"^%gdn_step[.\d]* = .*custom-call\("
STEP_PROGRAMS = readers.VERIFY_PROGRAMS + "|" + readers.PREFILL_PROGRAMS
CHUNK_PROGRAMS = r"mixed_chunk|prefill_chunk"
PREFIX = "gridllm_state_prefix_total"


def shapes(spec: dict) -> tuple[int, int, int] | None:
    try:
        return (int(spec["linear_num_key_heads"]),
                int(spec["linear_key_head_dim"]),
                int(spec["linear_value_head_dim"]))
    except (KeyError, TypeError, ValueError):
        return None


def around_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    h, dk, dv = s
    c = h * (2 * dk + dv)
    return (rf"[\[,]{c}\]|,{h},({dk}|{dv})\]|,{h},\d+,({dk}|{dv}|64|8)\]"
            rf"|[\[,]{h * dv}\]|triangular-solve")


def projection_pattern(spec: dict) -> str:
    return rf"[\[,]{int(spec['hidden_size'])}[\],]"


def kernel_ops(run: dict, which: str, programs: str) -> list[dict]:
    if shapes(run["config"]) is None:
        return []
    return [o for o in readers.ops(run, which)
            if re.search(programs, o["program"])]


def layer_ops(run: dict) -> list[dict]:
    """Both kernels and what runs around them (the convolutions, the
    norms, the gate, the blocks' triangular systems, the copies of the
    state and of the pending rows), in every step program; no projection."""
    pat = around_pattern(run["config"])
    if pat is None:
        return []
    found = {o["key"]: o for o in readers.ops(run, CHUNK_OP + "|" + STEP_OP)}
    product = projection_pattern(run["config"])
    found.update((o["key"], o) for o in readers.ops(run, pat)
                 if not re.search(product, o["text"]))
    return [o for o in found.values() if re.search(STEP_PROGRAMS, o["program"])]


def chunk_rule_ops(run: dict) -> list[dict]:
    """What the chunk program spends on the chunked delta rule: the
    ``gdn_chunk`` kernel, the blocks' triangular systems and the layout
    copies around them (and, by shape inseparable from those, the norms
    and the gate on the same rows and the few operations on the running
    slots' decode rows): `layer_ops` of the chunk programs less the step
    kernel and less the convolution (its channels side by side)."""
    s = shapes(run["config"])
    if s is None:
        return []
    h, dk, dv = s
    conv = rf"[\[,]{h * (2 * dk + dv)}\]"
    return [o for o in layer_ops(run)
            if re.search(CHUNK_PROGRAMS, o["program"])
            and not re.search(STEP_OP, o["text"])
            and not re.search(conv, o["text"])]


def chunk_rows_per_launch(run: dict) -> float | None:
    """Padded rows a chunk launch ran, over the window (the engine's
    counters: padding is computed too)."""
    launches = readers.counter_delta(
        run, "worker", "gridllm_engine_chunk_launches_total")
    padded = readers.counter_delta(
        run, "worker", "gridllm_engine_chunk_tokens_total", kind="padded")
    return padded / launches if launches > 0 and padded > 0 else None


def live_slots_per_launch(run: dict) -> float | None:
    """Mean live slots of a verify / decode launch over the capture, from
    the batch-occupancy histogram's change between the capture's ends."""
    ends = run.get("trace_counters")
    if not ends:
        return None
    h = stack.histogram_delta(
        stack.histogram(ends[0], "gridllm_engine_batch_occupancy"),
        stack.histogram(ends[1], "gridllm_engine_batch_occupancy"))
    return h["sum"] / h["count"] if h["count"] > 0 else None


def verify_rows(run: dict) -> int:
    return int(run["config"].get("env", {}).get("GRIDLLM_SPEC_K", 4)) + 1


def count(run: dict):
    c = costs.of(run["config"])
    return c if hasattr(c, "gdn_step_bytes") else None


def peaks(run: dict) -> dict | None:
    if phases.hbm_bytes_per_s(run) is None:
        return None
    return costs.peaks(run["device"]["kind"])
