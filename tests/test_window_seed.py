"""The repeat-penalty window a prefix-cache admission seeds (ISSUE 60): a
slot's window keeps the last ``min(total, repeat_last_n)`` tokens, so one
``window_set_slot`` call on the last ``min(cached, W)`` tokens of the cached
span with ``start=0`` leaves ``window``, ``wlen`` and ``counts`` bit-equal
to appending ``[0, cached)`` chunk by chunk, as admission did before it:
over spans shorter than, at and past the window, every ``repeat_last_n`` in
``[0, W]``, chunks narrower and wider than the window, and a slot that held
an older request's window beforehand."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gridllm_tpu.ops.sampling import window_set_slot

W = 256          # EngineConfig.repeat_window as shipped
VOCAB = 97       # small: tokens repeat inside a window, counts pass 1
SLOTS = 3
SLOT = 1

set_slot = jax.jit(window_set_slot, static_argnames="vocab")


def _padded(part: np.ndarray, width: int) -> np.ndarray:
    buf = np.zeros((width,), np.int32)
    buf[:len(part)] = part
    return buf


def _dirty_state(rng: np.random.Generator):
    """Every slot holds an older request's window, the seeded one too."""
    window = rng.integers(0, VOCAB, (SLOTS, W)).astype(np.int32)
    wlen = np.array([W, 200, 17], np.int32)
    counts = rng.integers(0, 5, (SLOTS, VOCAB)).astype(np.int32)
    return jnp.asarray(window), jnp.asarray(wlen), jnp.asarray(counts)


@pytest.mark.parametrize("c", [16, 512])
@pytest.mark.parametrize("rl", [0, 1, 64, W])
@pytest.mark.parametrize("cached", ["1", "W-1", "W", "W+1", "3c+5"])
def test_the_tail_alone_seeds_what_the_chunks_seeded(cached, rl, c):
    n = {"1": 1, "W-1": W - 1, "W": W, "W+1": W + 1, "3c+5": 3 * c + 5}[cached]
    rng = np.random.default_rng(n * 1000 + rl * 10 + c)
    ids = rng.integers(0, VOCAB, n).astype(np.int32)
    slot, rl_ = np.int32(SLOT), np.int32(rl)

    state = before = _dirty_state(rng)
    for s0 in range(0, n, c):
        part = ids[s0:s0 + c]
        state = set_slot(*state, slot, _padded(part, c), np.int32(s0),
                         np.int32(len(part)), rl_, vocab=VOCAB)
    tail = ids[max(0, n - W):]
    once = set_slot(*before, slot, _padded(tail, W), np.int32(0),
                    np.int32(len(tail)), rl_, vocab=VOCAB)

    for name, a, b in zip(("window", "wlen", "counts"), state, once):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    window, wlen, counts = (np.asarray(a) for a in once)
    # and it is the window the penalty is defined over: the span's last
    # min(cached, repeat_last_n) tokens, right-aligned, counted once each
    m = min(n, rl)
    assert wlen[SLOT] == m
    np.testing.assert_array_equal(window[SLOT, W - m:], ids[n - m:])
    assert not window[SLOT, :W - m].any()
    np.testing.assert_array_equal(
        counts[SLOT], np.bincount(ids[n - m:], minlength=VOCAB))
    # the other slots' rows are theirs still
    others = np.arange(SLOTS) != SLOT
    for a, b in zip(before, once):
        np.testing.assert_array_equal(np.asarray(a)[others],
                                      np.asarray(b)[others])
