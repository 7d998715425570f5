"""The one traffic generator. A traffic mix is a data file,
``traffic/<kind>.json``; a cell's ``rate`` (requests a second) is in
``workloads/<cell>.json``. A later PR adds a mix by adding a file.

Open loop: every due time and every length is fixed before the window
opens, from ``(mix, rate, seconds, seed)`` alone.

Every seed gets the SAME cycle of groups, entered at another point: the
inter-arrival gaps are the stratified quantiles of the exponential
distribution and the lengths those of the mix's clipped distribution, both
shuffled once by the mix's own ``base_seed`` (default 0); ``--seed`` rotates
that cycle (group i of seed s is group i + s mod n of the cycle, gap and
lengths together) and draws the bytes of every prompt. So two seeds carry
the same work and the same neighbours - which long prompt arrives behind
which burst - and differ in where the window cuts the cycle and in every
byte sent. A tail such as a 95th percentile is made by such coincidences;
shuffling them anew for every seed made it swing by a factor of two between
seeds (PERF.md, PR 23), which no bound could hold.

Mix file::

    {"streams": [{
        "name": "chat", "share": 1.0,
        "group_offsets_s": [0],            # requests of one group, seconds after its arrival
        "shared_tokens": null | <dist>,    # bytes every request of a group starts with
        "own_tokens": <dist>,              # bytes distinct to each request
        "output_tokens": <dist>}],
     "bursts": null | [{"seconds": 5, "factor": 2.0}, {"seconds": 5, "factor": 0.4}]}

``<dist>`` is ``{"dist": "fixed", "value": n}`` or
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.
Group arrivals are Poisson (exponential gaps) at ``rate * share / len(group_offsets_s)``,
laid over the window less the group's last offset, so every request of
every group is due inside the window. ``bursts`` repeats its phases over
the window and multiplies the arrival intensity by each phase's factor
(normalised so the mean over a period is 1).
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist

WORDS = ("the of and to in is that for it as was with be by on not he this are "
         "or his from at which but have an had they you were their one all we "
         "can her has there been if more when will would who so no out up into "
         "time data model system request server token cache page batch").split()


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # seconds after the window opens
    stream: str
    group: int
    prompt: str
    shared_bytes: int     # leading bytes shared with the rest of its group
    num_predict: int


def quantile(dist: dict, u: float) -> int:
    if dist["dist"] == "fixed":
        return int(dist["value"])
    if dist["dist"] == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(u))
        return int(round(min(max(x, dist["min"]), dist["max"])))
    raise ValueError(f"unknown distribution {dist!r}")


def stratified(dist: dict, n: int, rng: random.Random) -> list[int]:
    """n values at the mid-stratum quantiles, shuffled by `rng`."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(vals)
    return vals


def exponential_gaps(n: int, rng: random.Random) -> list[float]:
    """n Poisson-like gaps of mean about 1: the mid-stratum quantiles of
    the exponential distribution, shuffled by `rng`."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(gaps)
    return gaps


def arrival_times(gaps: list[float], span_s: float,
                  bursts: list[dict] | None = None) -> list[float]:
    """Arrivals in (0, span_s) from relative gaps: each arrival in the
    middle of its gap, the whole scaled to the span; then warped by the
    burst phases."""
    total, acc, out = sum(gaps), 0.0, []
    for g in gaps:
        out.append((acc + g / 2.0) / total * span_s)
        acc += g
    return [warp(t, span_s, bursts) for t in out] if bursts else out


def warp(t: float, span_s: float, bursts: list[dict]) -> float:
    """Map uniform-intensity time to bursty time: the inverse of the
    cumulative intensity of the repeated phases over [0, span_s]."""
    edges, cum = [0.0], [0.0]
    while edges[-1] < span_s:
        for ph in bursts:
            end = min(edges[-1] + ph["seconds"], span_s)
            cum.append(cum[-1] + (end - edges[-1]) * ph["factor"])
            edges.append(end)
            if end >= span_s:
                break
    target = t / span_s * cum[-1]
    for i in range(1, len(edges)):
        if cum[i] >= target:
            f = (target - cum[i - 1]) / max(cum[i] - cum[i - 1], 1e-12)
            return edges[i - 1] + f * (edges[i] - edges[i - 1])
    return span_s


def text(n_bytes: int, tag: str, rng: random.Random) -> str:
    """Exactly n_bytes ASCII bytes (one token each under the byte
    tokenizer), opening with `tag` so that no two share a first page."""
    parts, size = [tag], len(tag)
    while size < n_bytes:
        w = rng.choice(WORDS) + " "
        parts.append(w)
        size += len(w)
    return "".join(parts)[:n_bytes]


def rotate(values: list, k: int) -> list:
    return values[k:] + values[:k]


def generate(mix: dict, rate: float, seconds: float, seed: int) -> list[Request]:
    """Every request due in a window of `seconds`, sorted by due time."""
    out: list[tuple[float, str, int, str, int, int]] = []
    for si, st in enumerate(mix["streams"]):
        base = random.Random(f"{mix.get('base_seed', 0)}/{si}/{st['name']}")
        rng = random.Random(f"{seed}/{si}/{st['name']}")
        offsets = [float(o) for o in st["group_offsets_s"]]
        span, size = seconds - max(offsets), len(offsets)
        if span <= 0:
            raise ValueError(f"stream {st['name']}: group offsets {offsets} "
                             f"do not fit a window of {seconds} s")
        n = max(1, round(rate * st["share"] * seconds / size))
        k = seed % n
        gaps = rotate(exponential_gaps(n, base), k)
        shared = rotate(stratified(st["shared_tokens"], n, base)
                        if st.get("shared_tokens") else [0] * n, k)
        own = rotate(stratified(st["own_tokens"], n * size, base), k * size)
        outs = rotate(stratified(st["output_tokens"], n * size, base), k * size)
        starts = arrival_times(gaps, span, mix.get("bursts"))
        for g, t0 in enumerate(starts):
            doc = text(shared[g], f"[{seed}.{si}.{g}] ", rng) if shared[g] else ""
            for j, off in enumerate(offsets):
                i = g * size + j
                own_text = text(own[i], f"<{seed}.{si}.{g}.{j}> ", rng)
                out.append((t0 + off, st["name"], g, doc + own_text,
                            len(doc), outs[i]))
    out.sort(key=lambda r: (r[0], r[1], r[2]))
    return [Request(i, *r) for i, r in enumerate(out)]
