"""What the ``ssm.*`` readers share: how a hybrid family's Mamba-2
state-space layers and their recurrent state are found in a run. Works for
any configuration whose file carries the published keys ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state`` and ``mamba_d_conv`` and whose costs
file has ``ssd_chunk_flops`` / ``ssd_step_bytes``; anything else (a
configuration of another family, a program without the kernels or the
counters, such as the parent of the PR that added them) reads as nothing,
never as an error.

The two Pallas kernels are ``custom-call``s named after the functions that
wrap them (``%ssd_chunk.N``, ``%ssd_step.N``: ``readers.py`` on names).
What runs around them is found by shapes from the published keys, as
``gdn.py`` does (the profiler's events carry the HLO line without its
metadata): the convolution and what feeds it has the x, B, C channels side
by side, ``H P + 2 N`` (4,352) as a minor axis; the gate, the gated norm,
the skip and what the kernels read and hand back lie at the inner width,
``H P`` (4,096) as a minor axis, or as heads, ``[.., H, P]`` (``[.., 64,
64]``: also a block's pair terms ``[.., H, rows, rows]`` at 64 rows); the
packed state is ``[.., N, H P]``. The attention layers' heads are ``[..,
32, 64]`` / ``[.., 8, 64]`` and match none of these. The layer's
PROJECTIONS carry some of the same shapes (``W_in`` gives ``[rows, 8512]``
from ``[rows, 2048]``, ``W_out`` takes ``[rows, 4096]``) and are plain
products, not the layer's own part: an operation whose line shows the
model's hidden size as an axis is left out, as ``gdn.py`` leaves them."""

from __future__ import annotations

import re

import costs
import gdn
import readers

CHUNK_OP = r"^%ssd_chunk[.\d]* = .*custom-call\("
STEP_OP = r"^%ssd_step[.\d]* = .*custom-call\("
STEP_PROGRAMS = gdn.STEP_PROGRAMS
CHUNK_PROGRAMS = gdn.CHUNK_PROGRAMS

chunk_rows_per_launch = gdn.chunk_rows_per_launch
live_slots_per_launch = gdn.live_slots_per_launch
verify_rows = gdn.verify_rows
peaks = gdn.peaks


def shapes(spec: dict) -> tuple[int, int, int] | None:
    """(heads, head size, state size), or None for another family."""
    try:
        return (int(spec["mamba_n_heads"]), int(spec["mamba_d_head"]),
                int(spec["mamba_d_state"]))
    except (KeyError, TypeError, ValueError):
        return None


def around_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    h, p, n = s
    c = h * p + 2 * int(spec.get("mamba_n_groups", 1)) * n
    return rf"[\[,]{c}\]|,{h},{p}\]|,{h},(64|8),(64|8)\]|[\[,]{h * p}\]"


def projection_pattern(spec: dict) -> str:
    return rf"[\[,]{int(spec['hidden_size'])}[\],]"


def kernel_ops(run: dict, which: str, programs: str) -> list[dict]:
    if shapes(run["config"]) is None:
        return []
    return [o for o in readers.ops(run, which)
            if re.search(programs, o["program"])]


def layer_ops(run: dict) -> list[dict]:
    """Both kernels and what runs around them (the convolution, the gate
    and the norm, the skip, a block's pair terms, the copies of the state
    and of the pending rows), in every step program; no projection."""
    pat = around_pattern(run["config"])
    if pat is None:
        return []
    found = {o["key"]: o for o in readers.ops(run, CHUNK_OP + "|" + STEP_OP)}
    product = projection_pattern(run["config"])
    found.update((o["key"], o) for o in readers.ops(run, pat)
                 if not re.search(product, o["text"]))
    return [o for o in found.values() if re.search(STEP_PROGRAMS, o["program"])]


def count(run: dict):
    c = costs.of(run["config"])
    return c if hasattr(c, "ssd_step_bytes") else None
