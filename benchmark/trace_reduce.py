"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-program and per-operation device time, the longest idle gaps and what
a host thread was doing in each. Read with nothing but jax
(``jax.profiler.ProfileData``), in a child process that never touches a
device (``JAX_PLATFORMS=cpu``): the parent of a run must not import jax.

    python benchmark/trace_reduce.py <file.xplane.pb>   # prints TRACE=<the reduction as JSON>

A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation that ran, its ``XLA Modules`` line one per
launch of a jitted program. Busy time is the union of the ``XLA Ops``
intervals. The traced window is the span from the first to the last
device event over all chips (under steady load the device is never idle
for long at either end of a capture).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_MIN_NS = 50_000       # host events shorter than this are not kept


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    return hits[-1] if hits else None


def program_name(module_event_name: str) -> str:
    """``jit_verify_block(123456789)`` -> ``jit_verify_block``."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into a sorted disjoint list."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def read(path: str) -> dict:
    """The planes as plain lists. A CPU trace has no device plane: its
    XLA operations sit on host threads (events that carry an
    ``hlo_module``) and are gathered into one pseudo device,
    ``/host:CPU(xla)``, so that a rehearsal exercises the same code."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    host: list[tuple[float, float, str]] = []
    pseudo = {"ops": [], "modules": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats) if ev.name[:1] != "$" else {}
                    if "hlo_module" in stats:
                        end = ev.start_ns + ev.duration_ns
                        pseudo["ops"].append((ev.start_ns, end, ev.name))
                        pseudo["modules"].append(
                            (ev.start_ns, end, str(stats["hlo_module"])))
                    elif ev.duration_ns >= HOST_MIN_NS:
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     f"{line.name.split('/')[0]}:{ev.name}"))
    if not devices and pseudo["ops"]:
        devices["/host:CPU(xla)"] = pseudo
    return {"devices": devices, "host": host}


def attribute(gap: tuple[float, float], host: list) -> str:
    """The host event that explains an idle gap: the shortest one that
    covers the whole gap, else the one overlapping it longest."""
    s, e = gap
    cover, cover_len, best, best_ov = None, None, None, 0.0
    for hs, he, name in host:
        if he <= s or hs >= e:
            continue
        if hs <= s and he >= e and (cover_len is None or he - hs < cover_len):
            cover, cover_len = name, he - hs
        ov = min(e, he) - max(s, hs)
        if ov > best_ov:
            best, best_ov = name, ov
    return cover or best or "unattributed"


def short_name(op_event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%fusion.237 = bf16[16,5,4096]{...} fusion(...)``: the part before
    `` = ``, without the ``%``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%").strip()


def program_label(module_event_name: str) -> str:
    """``jit_prefill_fn(4251231163149063257)`` -> ``jit_prefill_fn#4251``:
    two compiled shapes of one function stay apart."""
    m = re.match(r"^(.*)\((\d+)\)$", module_event_name.strip())
    return f"{m.group(1)}#{m.group(2)[:4]}" if m else module_event_name.strip()


def self_times(events: list[tuple]) -> list[float]:
    """Events of one line nest (a ``while`` holds its body's operations):
    each event's duration less that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -(events[i][1] - events[i][0])))
    own = [float(e[1] - e[0]) for e in events]
    open_: list[int] = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while open_ and events[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][1]:
            own[open_[-1]] -= e - s
        open_.append(i)
    return own


def reduce(planes: dict, top: int = 10) -> dict:
    """Seconds throughout. ``busy_s``/``idle_pct`` are per chip and
    averaged (``idle_pct_max`` is the idlest chip); programs, operations
    and idle gaps are the first chip's, so whatever a reader sets against
    them is the first chip's share (``costs.chip_share``): the first chip's
    time against the first chip's share. Under a mesh every chip runs the
    same programs in step, and a collective shows on each as an operation
    of its own. An operation's ``seconds`` are its own (``self_times``),
    keyed ``<program>#<id>/<operation>``."""
    devs = planes["devices"]
    if not devs:
        return {}
    starts = [r[0] for d in devs.values() for r in d["ops"]]
    ends = [r[1] for d in devs.values() for r in d["ops"]]
    if not starts:
        return {}
    w0, w1 = min(starts), max(ends)
    window = (w1 - w0) / 1e9
    per_dev, ops, programs, gaps = {}, {}, {}, []
    first = sorted(devs)[0]
    for name, d in sorted(devs.items()):
        merged = union([(r[0], r[1]) for r in d["ops"]])
        busy = sum(e - s for s, e in merged) / 1e9
        per_dev[name] = {"busy_s": busy,
                         "idle_pct": 100.0 * (1.0 - busy / window)}
        if name != first:
            continue
        mods = sorted(d["modules"])
        mod_starts = [m[0] for m in mods]
        for s, e, mod in mods:
            p = programs.setdefault(program_name(mod), [0.0, 0])
            p[0] += (e - s) / 1e9
            p[1] += 1
        for (s, e, text), own in zip(d["ops"], self_times(d["ops"])):
            i = bisect.bisect_right(mod_starts, s) - 1
            inside = i >= 0 and e <= mods[i][1] + 1
            label = program_label(mods[i][2]) if inside else "-"
            o = ops.setdefault(f"{label}/{short_name(text)}", {
                "seconds": 0.0, "total_seconds": 0.0, "count": 0,
                "program": program_name(mods[i][2]) if inside else "",
                "text": text[:240]})
            o["seconds"] += own / 1e9
            o["total_seconds"] += (e - s) / 1e9
            o["count"] += 1
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if w1 > edge:
            gaps.append((edge, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    n = len(per_dev)
    return {
        "window_s": window,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "idle_pct": sum(d["idle_pct"] for d in per_dev.values()) / n,
        "idle_pct_max": max(d["idle_pct"] for d in per_dev.values()),
        "devices": per_dev,
        "ops": ops,
        "programs": {k: {"seconds": v[0], "count": v[1]}
                     for k, v in programs.items()},
        "n_idle_gaps": len(gaps),
        "breakdown": {
            "device_ops": [[k, v["seconds"]] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1]["seconds"])[:top]],
            "idle_gaps": [[attribute(g, planes["host"]), (g[1] - g[0]) / 1e9]
                          for g in gaps[:top]],
        },
    }


def main() -> int:
    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_xplane(path) or path
    print("TRACE=" + json.dumps(reduce(read(path))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
