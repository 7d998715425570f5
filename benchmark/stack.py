"""The three child processes of one run, and what is read from them.

Copied from ``chip_smoke.py`` (``Stack``, ``http_json``, ``metric_values``)
so that a later change to the smoke cannot change the
yardstick. Nothing here imports jax: the chip belongs to the worker.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request


class Failed(Exception):
    """A step of the run failed; `child` names the process whose log explains it."""

    def __init__(self, msg: str, child: str = "worker"):
        super().__init__(msg)
        self.child = child


def say(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 10.0):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def http_text(url: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def metric_values(text: str, name: str) -> dict[tuple[tuple[str, str], ...], float]:
    """Samples of one Prometheus series: {sorted label pairs: value}."""
    out = {}
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] not in ("{", " "):
            continue
        head, _, value = line.rpartition(" ")
        labels = ()
        if "{" in head:
            inner = head[head.index("{") + 1:head.rindex("}")]
            labels = tuple(sorted(
                (k, v.strip('"')) for k, v in
                (pair.split("=", 1) for pair in inner.split(",") if pair)))
        out[labels] = float(value)
    return out


def metric_sum(text: str, name: str, **want: str) -> float:
    """Sum of one series over every label set that carries `want`."""
    return sum(v for labels, v in metric_values(text, name).items()
               if all(dict(labels).get(k) == w for k, w in want.items()))


def histogram(text: str, name: str) -> dict:
    """One Prometheus histogram summed over its label sets:
    {"buckets": [(upper bound, cumulative count), ...], "sum", "count"}."""
    by_le: dict[float, float] = {}
    for labels, v in metric_values(text, name + "_bucket").items():
        le = dict(labels)["le"]
        ub = float("inf") if le == "+Inf" else float(le)
        by_le[ub] = by_le.get(ub, 0.0) + v
    return {"buckets": sorted(by_le.items()),
            "sum": metric_sum(text, name + "_sum"),
            "count": metric_sum(text, name + "_count")}


def histogram_delta(before: dict, after: dict) -> dict:
    b = dict(before["buckets"])
    return {"buckets": [(ub, c - b.get(ub, 0.0)) for ub, c in after["buckets"]],
            "sum": after["sum"] - before["sum"],
            "count": after["count"] - before["count"]}


def histogram_quantile(h: dict, q: float) -> float | None:
    """Quantile of a cumulative histogram, linear inside the bucket it
    falls in (so no finer than the program's buckets)."""
    if h["count"] <= 0:
        return None
    rank, lo, below = q * h["count"], 0.0, 0.0
    for ub, cum in h["buckets"]:
        if cum >= rank:
            if ub == float("inf"):
                return lo
            return lo + (ub - lo) * (rank - below) / max(cum - below, 1e-12)
        lo, below = ub, cum
    return lo


class Stack:
    """The child processes, their logs, and their end."""

    def __init__(self, log_dir: str, env: dict[str, str], cwd: str):
        self.log_dir, self.env, self.cwd = log_dir, env, cwd
        self.procs: dict[str, subprocess.Popen] = {}
        os.makedirs(log_dir, exist_ok=True)

    def log_path(self, name: str) -> str:
        return os.path.join(self.log_dir, f"{name}.log")

    def spawn(self, name: str, *argv: str) -> None:
        with open(self.log_path(name), "wb") as out:
            self.procs[name] = subprocess.Popen(
                [sys.executable, *argv], env=self.env, cwd=self.cwd,
                stdout=out, stderr=subprocess.STDOUT, start_new_session=True)

    def check_alive(self) -> None:
        for name, p in self.procs.items():
            if p.poll() is not None:
                raise Failed(f"{name} exited (rc={p.returncode})", name)

    def tail(self, name: str, n: int = 40) -> str:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"(no log: {e})"

    def grep(self, name: str, needle: str) -> list[str]:
        try:
            with open(self.log_path(name), errors="replace") as f:
                return [ln.rstrip() for ln in f if needle in ln]
        except OSError:
            return []

    def stop(self) -> None:
        """End every process group and wait for each: last started first
        (the worker unregisters while its broker still listens), SIGTERM,
        then SIGKILL for one that does not go."""
        for p in reversed(list(self.procs.values())):
            for sig, grace in ((signal.SIGTERM, 10.0), (signal.SIGKILL, 5.0)):
                if p.poll() is not None:
                    break
                try:
                    os.killpg(p.pid, sig)
                except ProcessLookupError:
                    pass
                try:
                    p.wait(timeout=grace)
                except subprocess.TimeoutExpired:
                    pass
