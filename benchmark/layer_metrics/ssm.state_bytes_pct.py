"""The recurrent state's share of the bytes a verify / decode launch has
to move, over the capture, from program counters alone: the live slots'
state read once and written once (``state_bytes_per_slot`` x 2 x the mean
live slots of a launch, from the batch-occupancy histogram) over that plus
``step_weight_bytes`` plus the pages read (the context-token counter a
launch x ``kv_stored_bytes_per_token``: the 64-wide head is stored, and
read, at 128 lanes). Cannot pass 100: the numerator is one
term of the denominator. None where the histogram or the context counter
did not move over the capture."""
import phases
import ssm

NAME, UNIT, LAYER, MOVES = ("ssm.state_bytes_pct", "%", "recurrent state",
                            "itl_p95_ms")
CELLS = ["granite4hmicro.long_answers"]


def compute(run):
    count, live = ssm.count(run), ssm.live_slots_per_launch(run)
    tokens = phases.capture_per_launch(run, phases.CTX_TOKENS)
    if count is None or live is None or tokens is None:
        return None
    spec = run["config"]
    state = 2.0 * live * count.state_bytes_per_slot(spec)
    whole = (state + count.step_weight_bytes(spec)
             + tokens * count.kv_stored_bytes_per_token(spec))
    return 100.0 * state / whole
