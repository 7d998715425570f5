"""reference/llama_f32.py against the program's own cache-free forward on
tiny-mistral weights (float32 both sides: they must agree to rounding),
and the margin check against a model with one layer skipped."""
import importlib.util
import json
import os

import numpy as np
import pytest

from conftest import BENCH

# float32 on both sides, different operation order: rounding only
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def setup():
    import jax
    import jax.numpy as jnp

    from gridllm_tpu.models import llama
    from gridllm_tpu.models.configs import get_config

    spec_ = importlib.util.spec_from_file_location(
        "llama_f32", os.path.join(BENCH, "reference", "llama_f32.py"))
    ref = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(ref)
    cfg = get_config("tiny-mistral")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    sizes = {"num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
             "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
             "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
             "tie_word_embeddings": cfg.tie_embeddings}
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 48)
    served = llama.forward(params, cfg, jnp.asarray(toks)[None])[0]
    return ref, params, sizes, toks, np.asarray(served)


@pytest.mark.parametrize("window", [8, 0])
def test_reference_matches_the_programs_forward(setup, window):
    ref, params, sizes, toks, served = setup
    if window == 0:
        import dataclasses

        import jax.numpy as jnp

        from gridllm_tpu.models import llama
        from gridllm_tpu.models.configs import get_config

        cfg = dataclasses.replace(get_config("tiny-mistral"), sliding_window=0)
        served = np.asarray(llama.forward(params, cfg, jnp.asarray(toks)[None])[0])
    got = np.asarray(ref.logits(params, dict(sizes, sliding_window=window), toks))
    assert np.abs(got - served).max() < TOLERANCE


def test_a_skipped_layer_fails_the_margin(setup):
    ref, params, sizes, toks, served = setup
    n_prompt = 16
    # teacher-force the program's own greedy continuation of the prompt
    import jax.numpy as jnp

    from gridllm_tpu.models import llama
    from gridllm_tpu.models.configs import get_config

    cfg = get_config("tiny-mistral")
    seq = list(toks[:n_prompt])
    for _ in range(12):
        lg = llama.forward(params, cfg, jnp.asarray(seq)[None])[0, -1]
        seq.append(int(lg.argmax()))
    short, top = ref.margins(ref.logits(params, sizes, seq), seq, n_prompt)
    assert float(short.max()) <= 1e-4                      # the served token IS the maximum
    # a penalty the request did not ask for moves the maximum away
    short, _ = ref.margins(ref.logits(params, sizes, seq), seq, n_prompt, 1.5, 64)
    assert float(short.max()) > 0.01
    short, top = ref.margins(ref.logits(params, sizes, seq, skip_layer=1), seq, n_prompt)
    assert int((np.asarray(short) > 0.03 + 0.03 * np.asarray(top)).sum()) > 0


def test_repeat_penalty_is_llama_cpps():
    import jax.numpy as jnp

    ref = importlib.util.module_from_spec(importlib.util.spec_from_file_location(
        "llama_f32b", os.path.join(BENCH, "reference", "llama_f32.py")))
    ref.__spec__.loader.exec_module(ref)
    rows = jnp.asarray([[2.0, -1.0, 3.0, 0.5], [2.0, -1.0, 3.0, 0.5]])
    tokens = [0, 1, 3, 2]               # rows predict tokens[2] and tokens[3]
    got = np.asarray(ref.penalized(rows, tokens, 2, 2.0, 2))
    # row 0 sees tokens 0 and 1; row 1 sees the last two only: 1 and 3
    assert got.tolist() == [[1.0, -2.0, 3.0, 0.5], [2.0, -2.0, 3.0, 0.25]]
    assert ref.penalized(rows, tokens, 2, 1.0, 64) is rows


class _Shortfalls:
    """A reference whose shortfalls are given: `check` is held to its two
    limits without a model."""

    def __init__(self, sound, skipped):
        self.by_skip = {None: sound, 1: skipped}

    def logits(self, params, sizes, tokens, skip_layer=None):
        return skip_layer

    def margins(self, lg, tokens, n_prompt, penalty, last_n):
        import jax.numpy as jnp

        short = jnp.asarray(self.by_skip[lg], jnp.float32)
        return short, jnp.full(short.shape, 5.0)


LIMITS = {"margin_abs": 0.05, "margin_rel": 0.01, "margin_mean": 0.01}
RECORD = [{"index": 0, "context": list(range(8)), "n_prompt": 4}]


@pytest.mark.parametrize("sound, skipped, agrees, control_fails", [
    # bf16's noise passes; a wrong model fails at single positions
    ([0.0, 0.03, 0.0, 0.0], [0.0, 1.2, 0.4, 0.0], True, True),
    # an output settled on one token: the wrong model stays under the
    # per-position margin (0.10 here) everywhere, and the mean sees it
    ([0.0, 0.0, 0.0, 0.0], [0.06, 0.09, 0.05, 0.08], True, True),
    # one wrong served token fails though the mean is small
    ([0.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 0.2], True, True),
    # a control inside both limits is a check that sees nothing
    ([0.0, 0.0, 0.0, 0.0], [0.0, 0.02, 0.0, 0.0], True, False),
    # served tokens a little off everywhere fail the mean alone
    ([0.02, 0.03, 0.02, 0.03], [0.5, 0.5, 0.5, 0.5], False, True),
])
def test_check_holds_the_shortfalls_to_both_limits(sound, skipped, agrees,
                                                   control_fails):
    import reference_check

    ref = _Shortfalls(sound, skipped)
    got = reference_check.check(ref, None, {}, 256, LIMITS, RECORD)
    assert got["agrees"] is agrees
    assert got["mean_allowed"] == 0.01
    assert got["mean_shortfall"] == pytest.approx(sum(sound) / 4)
    assert got["records"][0]["allowed_there"] == pytest.approx(0.10)
    control = reference_check.check(ref, None, {}, 256, LIMITS, RECORD, skip_layer=1)
    assert (not control["agrees"]) is control_fails
    assert reference_check.check(ref, None, {}, 256, LIMITS, [])["agrees"] is False


def test_a_run_of_the_benchmark_does_not_wait_for_the_control():
    """`correct` is decided by the comparison; the comparison that must
    fail is `reference_check.py --control` (a test, and a reading on the
    chip), which run.py neither asks for nor reads."""
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    assert "--control" not in text and "layer_skipped" not in text
    for name in os.listdir(os.path.join(BENCH, "configs")):
        with open(os.path.join(BENCH, "configs", name)) as f:
            assert 0 < json.load(f)["reference"]["margin_mean"] <= 0.02
