"""What the layer-metric readers share: how to find things in a run.

A reader is ``layer_metrics/<metric name>.py`` with ``NAME``, ``UNIT``,
``LAYER``, ``MOVES``, optionally ``CELLS``, and ``compute(run)``, which
returns a number or None (nothing to read: the metric is left out).
``run`` is a dict the harness fills:

- ``requests``, ``outcomes``: the window's schedule and what came back;
- ``seconds``, ``t0``, ``drain_s``: the window and the drain behind it;
- ``worker_before``/``worker_after``, ``gateway_before``/``gateway_after``:
  ``/metrics`` text when the window opened and after the drain;
- ``samples``: ``[(monotonic time, worker /metrics text), ...]`` taken
  every half second of a traced window;
- ``trace``: ``trace_reduce.reduce()`` of the profiler capture, or {}:
  its ``programs`` and ``ops`` are the FIRST chip's, so a reader that sets
  them against bytes or operations takes the first chip's share of those
  (``costs.of(config).chip_share(config)``; ``phases.chip_share``): the
  first chip's time against the first chip's share;
- ``trace_counters``: worker ``/metrics`` text at the capture's start and end;
- ``memory``: the worker's ``/admin/memory``; ``pool``: its ``kv pool
  sized`` log record; ``config``: the configuration file (its ``mesh``,
  ``chips`` and ``costs`` with it); ``device``.
"""

from __future__ import annotations

import re

import stack


def hist_delta(run: dict, side: str, name: str) -> dict:
    return stack.histogram_delta(
        stack.histogram(run[f"{side}_before"], name),
        stack.histogram(run[f"{side}_after"], name))


def hist_mean(run: dict, side: str, name: str, scale: float = 1.0):
    h = hist_delta(run, side, name)
    return scale * h["sum"] / h["count"] if h["count"] > 0 else None


def counter_delta(run: dict, side: str, name: str, **labels: str) -> float:
    return (stack.metric_sum(run[f"{side}_after"], name, **labels)
            - stack.metric_sum(run[f"{side}_before"], name, **labels))


def gauge_samples(run: dict, name: str) -> list[float]:
    return [stack.metric_sum(text, name) for _, text in run.get("samples", [])]


def programs(run: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, launches) of the jitted programs whose name
    matches `pattern`, in the traced window."""
    secs, n = 0.0, 0
    for name, p in (run.get("trace") or {}).get("programs", {}).items():
        if re.search(pattern, name):
            secs, n = secs + p["seconds"], n + p["count"]
    return secs, n


def ops(run: dict, pattern: str) -> list[dict]:
    """The operations (``trace_reduce.reduce()["ops"]`` records, with their
    key as ``key``) whose HLO text matches `pattern`."""
    return [dict(o, key=k) for k, o in (run.get("trace") or {}).get("ops", {}).items()
            if re.search(pattern, o["text"])]


def first_device_busy_s(run: dict):
    devs = (run.get("trace") or {}).get("devices", {})
    return devs[sorted(devs)[0]]["busy_s"] if devs else None


# The names the trace prints today (PERF.md: stable names are the tracing
# issue's); one place to change when that issue lands. Programs are XLA
# module names; a Pallas kernel is a ``custom-call`` named after the
# Python function that wraps it: ``%ragged_attention.N``, and ``%vmap__.N``
# for the flash prefill kernel (``jax.vmap(one)`` in ``flash_prefill``),
# whose result is ``[T, KV heads, group, head dim]``: one chip's KV heads
# under a mesh, where the kernel runs inside a ``shard_map``. A grouped
# expert product that reads the touched experts alone (ROADMAP S9) is to
# be a Pallas kernel whose wrapper is named ``grouped_experts``: its
# weight operands may stand past the 240 characters ``trace_reduce.py``
# keeps of a line, so the four families' expert patterns (``moe.py``,
# ``mla.py``, ``routed.py``, ``kda.py``) know it by name; no program emits
# the name yet.
VERIFY_PROGRAMS = r"verify_block|decode_block"
PREFILL_PROGRAMS = r"prefill|mixed_chunk"
RAGGED_OPS = r"^%ragged_attention[.\d]* = .*custom-call\("
GROUPED_OPS = r"^%grouped_experts[.\d]* = .*custom-call\("
FLASH_OPS = r"^%vmap__[.\d]* = \w+\[(\d+),(\d+),(\d+),(\d+)\][^ ]* custom-call\("
