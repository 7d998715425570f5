"""What the n-gram drafter's lookup costs the host, beside the walk it
replaced (PR 37). Host only: nothing here touches a jax device.

    python deploy/host_draft_cost.py [--reps 30]

For histories of 256 / 1,024 / 4,096 / 8,192 tokens and 1 / 5 / 16 live
slots it times one verify step's drafting, as the engine drives it: every
slot's list has grown by one token since the last call, then
``draft(ids, 4, slot)`` a slot. A *miss* is a history of distinct tokens
(novel text: every n from 4 to 1 is searched to the start); a *hit* is one
repeated token (what random weights collapse to: the first position tried
matches). The walk is the parent's ``NgramDrafter.draft``, kept verbatim
as the oracle of tests/test_spec_decode.py. Prints µs a slot (the median
of ``--reps`` steps) as a markdown table, and what the first call of a
request pays to copy a 4,096-token prompt into the slot's buffer. PERF.md
(section 6, PR 37) holds a reading.
"""

from __future__ import annotations

import argparse
import statistics
import time

from gridllm_tpu.ops.spec import NgramDrafter
from tests.test_spec_decode import _walk_reference

K, MAX_N, MIN_N = 4, 4, 1            # the defaults the benchmark's cells run
LENGTHS = (256, 1024, 4096, 8192)
SLOTS = (1, 5, 16)


def histories(outcome: str, length: int, slots: int) -> list[list[int]]:
    if outcome == "hit":
        return [[7] * length for _ in range(slots)]
    return [list(range(s * 100_000, s * 100_000 + length))
            for s in range(slots)]


def step_us(draft, outcome: str, length: int, slots: int, reps: int) -> float:
    """Median µs a slot over `reps` steps; each step first grows every
    slot's list by one token (novel for a miss, the same for a hit)."""
    hist = histories(outcome, length, slots)
    novel = 10_000_000
    for s, ids in enumerate(hist):
        draft(ids, s)                                   # admitted: held
    took = []
    for _ in range(reps):
        for ids in hist:
            novel += 1
            ids.append(7 if outcome == "hit" else novel)
        t0 = time.perf_counter()
        for s, ids in enumerate(hist):
            draft(ids, s)
        took.append((time.perf_counter() - t0) / slots * 1e6)
    return statistics.median(took)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()

    drafter = NgramDrafter(max_n=MAX_N, min_n=MIN_N)

    def walk(ids, slot):
        return _walk_reference(ids, K, MAX_N, MIN_N, 0)

    def lookup(ids, slot):
        return drafter.draft(ids, K, slot)

    print("| outcome | history | slots | walk us/slot | lookup us/slot "
          "| walk ms/step | lookup ms/step |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    for outcome in ("miss", "hit"):
        for length in LENGTHS:
            for slots in SLOTS:
                w = step_us(walk, outcome, length, slots, args.reps)
                drafter.reset()
                n = step_us(lookup, outcome, length, slots, args.reps)
                print(f"| {outcome} | {length} | {slots} | {w:.1f} | {n:.1f} "
                      f"| {w * slots / 1e3:.3f} | {n * slots / 1e3:.3f} |")

    prompt = list(range(4096))
    first = []
    for _ in range(args.reps):
        drafter.reset_slot(0)
        t0 = time.perf_counter()
        drafter.draft(prompt, K, 0)
        first.append((time.perf_counter() - t0) * 1e6)
    held = step_us(lookup, "miss", 4096, 1, args.reps)
    print(f"\na request's first call at 4,096 tokens (copy into the buffer "
          f"+ lookup): {statistics.median(first):.1f} us; a later call: "
          f"{held:.1f} us")


if __name__ == "__main__":
    main()
