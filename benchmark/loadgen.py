"""Open-loop load from one process, one thread: every request is sent at
its due time whether or not earlier ones have finished, over streaming
``POST /ollama/api/generate``, and every frame is stamped as the client
receives it."""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time

import aiohttp


@dataclasses.dataclass
class Outcome:
    index: int
    due: float                      # monotonic seconds
    sent: float | None = None
    frames: list[tuple[float, int]] = dataclasses.field(default_factory=list)
    # (arrival, characters of text) of each content frame
    done: bool = False
    eval_count: int | None = None
    done_reason: str | None = None
    context: list[int] | None = None
    error: str | None = None


# Ollama's documented defaults, sent explicitly so that the reference
# check models the sampling the request asked for: greedy over logits
# with llama.cpp's repeat penalty over the last 64 context tokens.
REPEAT_PENALTY, REPEAT_LAST_N = 1.1, 64


def body_of(model: str, prompt: str, num_predict: int, seed: int) -> dict:
    return {"model": model, "prompt": prompt, "raw": True, "stream": True,
            "options": {"num_predict": num_predict, "temperature": 0,
                        "seed": seed, "repeat_penalty": REPEAT_PENALTY,
                        "repeat_last_n": REPEAT_LAST_N}}


async def one(session: aiohttp.ClientSession, url: str, body: dict,
              out: Outcome, keep_context: bool) -> None:
    out.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            if resp.status != 200:
                out.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.strip()
                if not line:
                    continue
                frame = json.loads(line)
                if frame.get("error"):
                    out.error = str(frame["error"])[:200]
                    return
                if frame.get("response"):
                    out.frames.append((now, len(frame["response"])))
                if frame.get("done"):
                    out.done = True
                    out.eval_count = frame.get("eval_count")
                    out.done_reason = frame.get("done_reason")
                    if keep_context:
                        out.context = frame.get("context")
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        out.error = f"{type(e).__name__}: {e}"[:200]


async def play(url: str, model: str, requests: list, t0: float,
               end_by: float, keep_context=lambda r: False,
               background=()) -> list[Outcome]:
    """Send `requests` (trafficgen.Request) at ``t0 + due_s``; stop
    waiting at the monotonic time `end_by` and cancel what is unfinished.
    `background` are coroutines run alongside (samplers, the profiler
    trigger) and cancelled at the end."""
    outcomes = [Outcome(r.index, t0 + r.due_s) for r in requests]
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        side = [asyncio.ensure_future(c) for c in background]
        tasks = []
        for r, out in zip(requests, outcomes):
            delay = out.due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(one(
                session, url, body_of(model, r.prompt, r.num_predict,
                                      1000 + r.index),
                out, keep_context(r))))
        left = end_by - time.monotonic()
        if tasks:
            _, pending = await asyncio.wait(tasks, timeout=max(left, 0.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        for s in side:
            s.cancel()
        await asyncio.gather(*side, return_exceptions=True)
    return outcomes
