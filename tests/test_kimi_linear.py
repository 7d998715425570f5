"""Kimi-Linear (Kimi Delta Attention 3:1 with latent attention, a share of
the routed experts held behind the full-width sigmoid router) against its
plain float32 reference, benchmark/reference/kimi_linear_f32.py, on seeded
tiny-kimi-linear weights: seven layers (a period of four and a short one
of three), a dense first layer, experts 4-7 of 16 held. Logits, not
tokens. What is new is held here: the delta rule with a decay a key
channel in its three forms, a recurrent state and latent rows in one cache
(chunked = recurrent, verify with n kept = n decode steps, a re-asked
prefix admitted from latent pages AND a snapshot), and the share (four
shares and the shared expert add up to the whole layer)."""

import dataclasses
from functools import partial
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import deepseek, kimi_linear as km, mixtral
from gridllm_tpu.models.configs import _config_from_hf_dict, get_config
from gridllm_tpu.ops import linear_attn as la
from gridllm_tpu.ops.kvcache import (
    PageAllocator,
    PagedKVCache,
    rollback_to_length,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny-kimi-linear")
WHOLE = dataclasses.replace(CFG, experts_held=None, experts_first=None)
# float32 on both sides in another operation order (the chunked form
# solves a block's corrections at once and splits a block's decay at
# sub-block boundaries; the reference runs token by token): rounding only.
# The largest difference seen is 4e-6 (logits up to 0.7); each broken
# mechanism reads 2e-3 to 0.3. In bfloat16 (weights and activations; the
# state stays float32) the program lies within BF16_TOL of the float32
# reference at positions behind no router's tie: 8 bits of mantissa
# through seven layers read 0.02 to 0.09 there, a thousand times TOL, so a
# float32 run computed in bfloat16 fails TOL at every position
TOL = 1e-4
BF16_TOL = 0.15
PS = 16                                  # page size of the test pools


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("benchmark/reference/kimi_linear_f32.py", "kimi_linear_f32")
SIZES = REF.sizes(CFG)


@pytest.fixture(scope="module", autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return km.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.float32)


TOKENS = np.random.default_rng(0).integers(0, CFG.vocab_size, 96)


@pytest.fixture(scope="module")
def ref_logits(params):
    return np.asarray(REF.logits(params, SIZES, list(TOKENS)))


def _cache(slots=2, rows=5, snapshots=4, dtype=jnp.float32):
    c = PagedKVCache.create(
        CFG.cache_layers, num_pages=24, page_size=PS, num_kv_heads=1,
        head_dim=CFG.cache_dim, max_slots=slots, max_pages_per_slot=8,
        dtype=dtype, latent=True)
    return dataclasses.replace(
        c, rec=km.new_state(CFG, slots, rows, snapshots, dtype))


def _rows(n_tokens=128):
    alloc = PageAllocator(24, PS, 8)
    alloc.alloc(0, n_tokens)
    alloc.alloc(1, n_tokens)
    return [jnp.asarray(alloc.table_row(s), jnp.int32) for s in (0, 1)]


def _chunks(params, toks, cache, slot, row, width, start=0, state_io=None):
    """A prompt admitted as the engine admits it, through `mixed_step`
    with no active slot, `width` rows a launch."""
    idle = jnp.zeros(cache.lengths.shape, jnp.int32)
    for s0 in range(start, len(toks), width):
        part = toks[s0:s0 + width]
        chunk = jnp.zeros((width,), jnp.int32).at[:len(part)].set(
            jnp.asarray(part))
        logits, _, cache = km.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(slot), row, idle, cache, idle > 0, state_io=state_io)
    return logits, cache


# -- the configuration -------------------------------------------------------

PUBLISHED = {       # moonshotai/Kimi-Linear-48B-A3B-Instruct config.json
    "model_type": "kimi_linear", "first_k_dense_replace": 1, "head_dim": 72,
    "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512, "mla_use_nope": True, "model_max_length": 1048576,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840,
}


def test_published_keys_read_as_the_registry_entries():
    whole = get_config("kimi-linear:48b")
    assert _config_from_hf_dict("kimi-linear:48b", PUBLISHED, "x") == whole
    assert (whole.layer_period, whole.layer_tail) == (4, 3)
    assert (whole.linear_layers, whole.cache_layers) == (20, 7)
    assert whole.cache_kinds == ("latent", "state")
    assert whole.held_experts == (0, 256)
    # one of the four chips that share each layer: 64 held of the 256 the
    # router scores, said by num_experts beside router_experts
    share = {**PUBLISHED, "num_experts": 64, "router_experts": 256}
    ep4 = get_config("kimi-linear:48b-ep4")
    assert _config_from_hf_dict("kimi-linear:48b-ep4", share, "x") == ep4
    assert ep4.held_experts == (0, 64) and ep4.num_experts == 256
    third = _config_from_hf_dict("x", {**share, "experts_first": 128}, "x")
    assert third.held_experts == (128, 64)
    # every registry entry of before holds all its experts
    assert get_config("laguna-xs2:33b").held_experts == (0, 256)
    assert get_config("mistral:7b").experts_held is None


@pytest.mark.parametrize("change,named", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"num_expert_group": 8}, "num_expert_group"),
    ({"topk_group": 4}, "num_expert_group"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"moe_router_activation_func": "softmax"}, "moe_router_activation_func"),
    ({"linear_attn_config": {**PUBLISHED["linear_attn_config"],
                             "kda_layers": [1, 2, 3]}}, "linear_attn_config"),
])
def test_what_is_not_served_is_refused_by_name(change, named):
    with pytest.raises(ValueError, match=named):
        _config_from_hf_dict("x", {**PUBLISHED, **change}, "x")


def test_the_pattern_is_whole_periods_and_perhaps_a_shorter_last_one():
    lin, full = "linear_attention", "full_attention"
    base = get_config("tiny-olmo-hybrid")
    for n, types, want in [
            (7, (lin, lin, lin, full, lin, lin, full), (4, 3)),
            (8, (lin, lin, lin, full) * 2, (4, 0)),
            (5, (lin, lin, lin, full, full), (4, 1))]:
        cfg = dataclasses.replace(base, num_layers=n, layer_types=types)
        assert (cfg.layer_period, cfg.layer_tail) == want
    for n, types in [
            (7, (lin, lin, lin, full, lin, full, lin)),      # ends linear
            (8, (lin, lin, full, lin, lin, lin, full, full)),  # a longer one
            (6, (lin, lin, lin, full, lin, lin)),            # tail not ended
            (4, (lin,) * 4)]:
        with pytest.raises(ValueError, match="whole periods"):
            dataclasses.replace(base, num_layers=n, layer_types=types).layer_period
    with pytest.raises(ValueError, match="are not among"):
        dataclasses.replace(CFG, experts_first=14)
    assert [km._is_linear(CFG, i) for i in range(7)] == [
        True, True, True, False, True, True, False]


# -- cache-free --------------------------------------------------------------


def test_forward_matches_the_reference(params, ref_logits):
    got = km.forward(params, CFG, jnp.asarray(TOKENS)[None])[0]
    assert np.abs(np.asarray(got) - ref_logits).max() < TOL


def test_bfloat16_lies_within_its_own_tolerance_and_fails_float32s(ref_logits):
    """The same seeded weights served in bfloat16 stay within BF16_TOL of
    the float32 reference of those weights, and far outside TOL: computing
    in a lower precision than float32 fails the float32 comparison."""
    p16 = km.init_params(CFG, jax.random.PRNGKey(7), dtype=jnp.bfloat16)
    ref = REF.logits(p16, SIZES, list(TOKENS))
    # a position behind a router's tie may rightly take another expert in
    # bfloat16 (one of four, its weight 2.4 / 4): the reference says which
    judged = (ref.router_gap >= 2.0 ** -3).all(axis=-1)
    _, cache = _chunks(p16, TOKENS[:70], _cache(dtype=jnp.bfloat16), 0,
                       _rows()[0], 32)
    active = jnp.asarray([True, False])
    errs = []
    for p in range(70, 96):
        lg, cache = km.decode_step(p16, CFG, jnp.asarray([TOKENS[p], 0]),
                                   cache, active)
        errs.append(float(np.abs(np.asarray(lg[0]) - np.asarray(ref[p])).max()))
    errs = np.asarray(errs)
    assert judged[70:96].sum() >= 8
    assert 10 * TOL < errs[judged[70:96]].max() < BF16_TOL


@pytest.mark.parametrize("broken", [
    {"scalar_decay": True}, {"no_decay": True}, {"no_conv": True},
    {"no_bias": True}, {"skip_layer": 3}, {"state_dtype": "bfloat16"}])
def test_a_reference_broken_in_one_mechanism_fails(params, ref_logits, broken):
    bad = np.asarray(REF.logits(params, SIZES, list(TOKENS), **broken))
    assert np.abs(bad - ref_logits).max() > 10 * TOL


# -- the delta rule with a decay a key channel -------------------------------


def _delta_rows(t, heads=4, dk=16, dv=32, seed=1, steep=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = la.l2norm(jax.random.normal(ks[0], (t, heads, dk))) * dk ** -0.5
    k = la.l2norm(jax.random.normal(ks[1], (t, heads, dk)))
    v = jax.random.normal(ks[2], (t, heads, dv))
    b = jax.nn.sigmoid(jax.random.normal(ks[3], (t, heads)))
    g = -jnp.exp(jax.random.uniform(ks[4], (t, heads, dk), minval=-6.0,
                                    maxval=1.0))
    if steep:       # rows whose every channel falls by exp(-steep)
        g = g.at[5].set(-steep).at[40].set(-steep)
    return q, k, v, b, g


def test_the_naive_split_overflows_where_a_block_does_not():
    """Two rows of log decay -60 in a block of 64: the split (k exp G_i) .
    (k exp -G_j) needs exp(120), past float32; `_pairs` forms no positive
    exponent and agrees with the pair terms taken one by one."""
    q, k, _, _, g = _delta_rows(64, steep=60.0)
    cum = jnp.cumsum(jnp.moveaxis(g, 0, 1), axis=1)          # [H, C, dk]
    qh, kh = jnp.moveaxis(q, 0, 1), jnp.moveaxis(k, 0, 1)
    naive = jnp.einsum("hik,hjk->hij", kh * jnp.exp(cum), kh * jnp.exp(-cum))
    assert not bool(jnp.isfinite(naive).all())
    kk, qk = la._pairs(qh, kh, cum)
    low = jnp.tril(jnp.ones((64, 64), bool))
    diff = jnp.where(low[..., None], cum[:, :, None] - cum[:, None], -jnp.inf)
    for got, x in ((kk, kh), (qk, qh)):
        want = jnp.einsum("hik,hjk,hijk->hij", x, kh, jnp.exp(diff))
        assert bool(jnp.isfinite(got).all())
        assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("steep", [0.0, 60.0])
@pytest.mark.parametrize("kernel", [False, True])
def test_kda_chunk_matches_recurrent(kernel, steep, monkeypatch):
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret" if kernel else "off")
    q, k, v, b, g = _delta_rows(128, steep=steep)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (4, 16, 32))
    want_o, want_s = la.kda_recurrent(s0, q, k, v, b, g)
    _, mid = la.kda_recurrent(s0, *(x[:64] for x in (q, k, v, b, g)))
    o, s1, kept = la.kda_chunk(la.pack(s0), q, k, v, b, g,
                               jnp.asarray([0, -1], jnp.int32), 64)
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(la.unpack(s1, 4) - want_s).max()) < TOL
    assert float(jnp.abs(la.unpack(kept[0], 4) - mid).max()) < TOL
    assert float(jnp.abs(kept[1]).max()) == 0.0


@pytest.mark.parametrize("kernel", [False, True])
def test_kda_step_matches_recurrent(kernel, monkeypatch):
    """Three slots, five pending rows of which 2, 5 and none count, five
    new rows; the slot that is not live keeps its state and reads zeros,
    whatever (NaN) it left pending."""
    monkeypatch.setenv("GRIDLLM_PALLAS", "interpret" if kernel else "off")
    new = [_delta_rows(5, seed=10 + i) for i in range(3)]
    pend = [_delta_rows(5, seed=20 + i, steep=0.0) for i in range(3)]

    def stack(rows, i):
        return jnp.stack([r[i] for r in rows])

    pend_t = tuple(stack(pend, i) for i in (1, 2, 3, 4))
    pend_t = tuple(z.at[2].set(jnp.nan) for z in pend_t)
    states = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 16, 128))
    n, live = jnp.asarray([2, 5, 3]), jnp.asarray([True, True, False])
    st, o = la.kda_step(states, 1, pend_t, n, *(stack(new, i) for i in range(5)),
                        live)
    assert float(jnp.abs(st[0] - states[0]).max()) == 0.0
    for sl in range(3):
        s = la.unpack(states[1, sl], 4)
        want_o = jnp.zeros((5, 4, 32))
        if bool(live[sl]):
            kept = [x[:int(n[sl])] for x in pend[sl]]
            _, s = la.kda_recurrent(s, kept[1], *kept[1:])
            want_o, _ = la.kda_recurrent(s, *new[sl])
        assert float(jnp.abs(la.unpack(st[1, sl], 4) - s).max()) < TOL
        assert float(jnp.abs(o[sl] - want_o).max()) < TOL


# -- the router's bias and the share -----------------------------------------


def _moe_layer(cfg, seed=3, rows=40):
    """One expert layer's leaves at `cfg`'s share and rows of normed input."""
    lp = km.init_params(
        dataclasses.replace(cfg, num_layers=2, layer_types=cfg.layer_types[:2]),
        jax.random.PRNGKey(seed), jnp.float32)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (rows, cfg.hidden_size))
    return lp, x


def test_the_selection_bias_changes_a_choice_and_never_a_weight():
    lp, x = _moe_layer(WHOLE)
    plain = dataclasses.replace(WHOLE, router_bias=False)
    w_b, i_b = mixtral._route(WHOLE, lp, x)
    w_p, i_p = mixtral._route(plain, lp, x)
    assert bool((jnp.sort(i_b) != jnp.sort(i_p)).any())      # a choice moved
    # a weight is the score of what was chosen over the chosen scores' sum
    scores = jax.nn.sigmoid(x @ lp["router"])
    s = jnp.take_along_axis(scores, i_b, axis=-1)
    want = s / s.sum(-1, keepdims=True) * WHOLE.routed_scaling_factor
    assert float(jnp.abs(w_b - want).max()) < 1e-6
    # a bias that moves no choice moves nothing: one value for every expert
    flat = {**lp, "router_bias": jnp.full_like(lp["router_bias"], 0.3)}
    w_f, i_f = mixtral._route(WHOLE, flat, x)
    assert bool((i_f == i_p).all()) and float(jnp.abs(w_f - w_p).max()) < 1e-6


@pytest.mark.parametrize("form", ["all_experts", "sorted", "grouped"])
def test_four_shares_and_the_shared_expert_add_up_to_the_whole_layer(
        form, interpreted_kernels):
    """The routed parts that the four shares give, plus the shared expert
    counted once, equal the uncut reference's expert layer; and each
    share's own output is the reference's of that share."""
    lp, x1 = _moe_layer(WHOLE)
    routed = {"all_experts": mixtral._moe_mlp_dense,
              "sorted": mixtral._moe_mlp_ragged,
              "grouped": partial(mixtral._moe_mlp_grouped, live=None)}[form]
    m = REF.rms_norm(x1, lp["mlp_norm"], CFG.rms_eps)
    top_w, top_i = mixtral._route(WHOLE, lp, m)
    kw = dict(eps=CFG.rms_eps, top_k=4, scaling=CFG.routed_scaling_factor,
              norm=True, bias=True)
    whole, _ = REF.experts(x1, lp, first=0, **kw)
    total = mixtral._shared_mlp(lp, m)
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(WHOLE, experts_held=4, experts_first=first)
        mine = {**lp, **{k: lp[k][first:first + 4]
                         for k in ("we_gate", "we_up", "we_down")}}
        part = routed(cfg, mine, m, top_w, top_i)
        want, _ = REF.experts(x1, mine, first=first, **kw)
        assert float(jnp.abs(x1 + part + mixtral._shared_mlp(lp, m)
                             - want).max()) < TOL
        stats = mixtral._route_stats(cfg, top_i, None)
        assert int(stats[2] + stats[3]) == 40 * 4 and int(stats[1]) <= 4
        total = total + part
    assert float(jnp.abs(x1 + total - whole).max()) < TOL


@pytest.mark.parametrize("preset", [
    "tiny-smallthinker", "tiny-deepseek-v2", "tiny-laguna"])
def test_holding_every_expert_is_the_layer_of_before(preset,
                                                     interpreted_kernels):
    """`experts_held = None` is every routed family's path of before (the
    two pinned jaxprs hold that it traces as it did); a share that holds
    them all says the same in both forms."""
    cfg = get_config(preset)
    assert cfg.experts_held is None
    e, f, X = cfg.hidden_size, cfg.expert_width, cfg.num_experts
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    lp = {"router": jax.random.normal(ks[0], (e, X)) * 0.1,
          "we_gate": jax.random.normal(ks[1], (X, e, f)) * e ** -0.5,
          "we_up": jax.random.normal(ks[2], (X, e, f)) * e ** -0.5,
          "we_down": jax.random.normal(ks[3], (X, f, e)) * f ** -0.5}
    x = jax.random.normal(ks[4], (24, e))
    top_w, top_i = mixtral._route(cfg, lp, x)
    all_held = dataclasses.replace(cfg, experts_held=X, experts_first=0)
    want = mixtral._moe_mlp_dense(cfg, lp, x, top_w, top_i)
    for form in (mixtral._moe_mlp_dense, mixtral._moe_mlp_ragged,
                 partial(mixtral._moe_mlp_grouped, live=None)):
        got = form(all_held, lp, x, top_w, top_i)
        assert float(jnp.abs(got - want).max()) < 1e-5
    for rows in (16, 80, 528):
        assert mixtral.expert_form(cfg, rows, backend="tpu") == (
            mixtral.expert_form(all_held, rows, backend="tpu"))


def test_the_rule_of_the_shape_reads_the_share():
    ep4 = get_config("kimi-linear:48b-ep4")
    assert not mixtral._use_ragged(528, False, "tpu")
    assert not mixtral._use_ragged(80, False, "tpu")
    assert mixtral.expert_form(CFG, 528) == "all_experts"    # 16 / 4 experts
    # a verify launch's rows on one chip: the held experts touched, alone;
    # a mixed launch's: each held expert against the rows that picked it
    assert mixtral.expert_form(ep4, 80, backend="tpu") == "grouped"
    assert mixtral.expert_form(ep4, 528, backend="tpu") == "grouped_sorted"
    assert mixtral.expert_form(ep4, 80) == "all_experts"


# -- through the cache -------------------------------------------------------


def test_prefill_then_decode_through_both_caches(params, ref_logits):
    """Chunked prefill then decode steps = the reference's full forward,
    logits at every position."""
    row = _rows()[0]
    lg, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    assert np.abs(np.asarray(lg) - ref_logits[69]).max() < TOL
    active = jnp.asarray([True, False])
    step = jax.jit(lambda c, t: km.decode_step(params, CFG, t, c, active))
    for p in range(70, 96):
        lg, cache = step(cache, jnp.asarray([TOKENS[p], 0]))
        assert np.abs(np.asarray(lg[0]) - ref_logits[p]).max() < TOL


@pytest.mark.parametrize("accepted", [0, 2, 4])
def test_verify_then_commit_equals_sequential_decode(params, ref_logits, accepted):
    """A verify launch of K + 1 = 5 rows of which speculation accepts
    `accepted` drafts: the rejected rows roll back out of the latent pages
    and out of the state alike, and the next step reads what that many
    decode steps leave."""
    row = _rows()[0]
    _, cache = _chunks(params, TOKENS[:70], _cache(), 0, row, 32)
    active = jnp.asarray([True, False])
    lg, after, stats = km.verify_step(
        params, CFG, jnp.asarray([TOKENS[70:75], [0] * 5]), cache, active,
        with_stats=True)
    assert np.abs(np.asarray(lg[0]) - ref_logits[70:75]).max() < TOL
    # five live rows in each of six expert layers, four picks a row
    assert int(stats[0]) == 30 and int(stats[2] + stats[3]) == 120
    n_emit = jnp.asarray([1 + accepted, 0])
    after = rollback_to_length(after, after.lengths + n_emit)
    after = km.commit_verify(after, n_emit, active)
    seq = cache
    for p in range(70, 71 + accepted):
        _, seq = km.decode_step(params, CFG, jnp.asarray([TOKENS[p], 0]), seq,
                                active)
    nxt = jnp.asarray([TOKENS[71 + accepted], 0])
    got, _ = km.decode_step(params, CFG, nxt, after, active)
    want, _ = km.decode_step(params, CFG, nxt, seq, active)
    assert np.abs(np.asarray(got[0]) - ref_logits[71 + accepted]).max() < TOL
    assert np.abs(np.asarray(got[0] - want[0])).max() < TOL
    if accepted < 4:        # a row too many is another past
        over = km.commit_verify(after, n_emit + 1, active)
        bad, _ = km.decode_step(params, CFG, nxt, over, active)
        assert np.abs(np.asarray(bad[0]) - ref_logits[71 + accepted]).max() > 10 * TOL


def test_a_chunk_launch_saves_and_a_restore_resumes_beside_latent_pages(
        params, ref_logits):
    """A chunk launch hands back the five KDA layers' states at page
    boundaries it passes; a slot restored from one, reading the first
    asker's latent pages, says what the cold admission says."""
    rows = _rows()
    io = (jnp.asarray([32, 48], jnp.int32), jnp.asarray([2, 0], jnp.int32))
    cold, cache = _chunks(params, TOKENS[:70], _cache(), 0, rows[0], 96,
                          state_io=io)
    assert np.abs(np.asarray(cold) - ref_logits[69]).max() < TOL
    shared = rows[0].at[3:].set(rows[1][3:])
    cache = dataclasses.replace(cache, rec=cache.rec.restore(1, 0))
    warm, _ = _chunks(params, TOKENS[:70], cache, 1, shared, 32, start=48)
    assert np.abs(np.asarray(warm) - np.asarray(cold)).max() < TOL
    cache = dataclasses.replace(cache, rec=cache.rec.restore(1, 2))
    wrong, _ = _chunks(params, TOKENS[:70], cache, 1, shared, 32, start=48)
    assert np.abs(np.asarray(wrong) - np.asarray(cold)).max() > 100 * TOL


def test_the_mixed_step_serves_a_chunk_beside_running_slots(params, ref_logits):
    rows = _rows()
    _, cache = _chunks(params, TOKENS[:40], _cache(), 0, rows[0], 64)
    active = jnp.asarray([True, False])
    for i, s0 in enumerate((0, 32)):
        part = TOKENS[s0:min(s0 + 32, 50)]
        chunk = jnp.zeros((32,), jnp.int32).at[:len(part)].set(jnp.asarray(part))
        cl, dl, cache = km.mixed_step(
            params, CFG, chunk, jnp.int32(s0), jnp.int32(len(part)),
            jnp.int32(1), rows[1], jnp.asarray([TOKENS[40 + i], 0]), cache,
            active)
        assert np.abs(np.asarray(dl[0]) - ref_logits[40 + i]).max() < TOL
    assert np.abs(np.asarray(cl) - ref_logits[49]).max() < TOL
    lg, _ = km.decode_step(
        params, CFG, jnp.asarray([TOKENS[42], TOKENS[50]]), cache,
        jnp.asarray([True, True]))
    assert np.abs(np.asarray(lg[0]) - ref_logits[42]).max() < TOL
    assert np.abs(np.asarray(lg[1]) - ref_logits[50]).max() < TOL


# -- the engine ---------------------------------------------------------------


def _engine(**kw):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine

    kw = {"max_slots": 2, **kw}
    return InferenceEngine(EngineConfig(
        model="tiny-kimi-linear", dtype="float32", page_size=PS,
        num_pages=48, max_pages_per_slot=12, prefill_buckets=(32, 128),
        prefill_chunk=64, prefill_chunk_narrow=32, seed=0, **kw))


def _ask(eng, rid, prompt, n=8):
    from gridllm_tpu.engine import GenerationRequest

    return eng.generate(GenerationRequest(
        id=rid, prompt=prompt, options={"temperature": 0.0, "num_predict": n}))


WORDS = ("the quick brown fox jumps over the lazy dog and keeps running "
         "through the field until night falls on the hills beyond it ")


def _count(name, **labels):
    from gridllm_tpu.obs import default_registry

    return default_registry().get(name).value(model="tiny-kimi-linear", **labels)


def test_a_reasked_prefix_is_admitted_from_latent_pages_and_a_snapshot():
    """The one prefix cache holds both kinds: the re-ask finds the latent
    pages of the first 96 tokens, restores the five states taken there,
    and says what a cold admission says. The launches' statistics reach
    the counters of the share."""
    eng, cold = _engine(), _engine(prefix_cache=False)
    doc = (WORDS * 2)[:99]
    hits = _count("gridllm_state_prefix_total", outcome="hit")
    held = _count("gridllm_moe_picks_total", where="held")
    absent = _count("gridllm_moe_picks_total", where="absent")
    first = _ask(eng, "a", doc + " one two")
    again = _ask(eng, "b", doc + " six ten")
    assert first.cached_tokens == 0 and again.cached_tokens == 96
    assert _count("gridllm_state_prefix_total", outcome="hit") == hits + 1
    assert again.token_ids == _ask(cold, "c", doc + " six ten").token_ids
    assert first.token_ids == _ask(cold, "d", doc + " one two").token_ids
    held = _count("gridllm_moe_picks_total", where="held") - held
    absent = _count("gridllm_moe_picks_total", where="absent") - absent
    assert held > 0 and absent > held        # 4 of 16 experts live here


def test_the_engine_accounts_for_both_caches():
    eng = _engine()
    eng.prewarm()
    shape = eng.batch_state()["shape"]
    assert (shape["cacheRow"], shape["attnForm"]) == ("latent+state",
                                                      "absorbed+delta")
    assert (shape["expertsHeld"], shape["experts"]) == (4, 16)
    assert eng._expert_meta("verify", 10) == {"expert_form": "all_experts"}
    assert eng.cache.v is None and eng.cache.k.shape[0] == CFG.cache_layers == 2
    rec = eng.cache.rec
    assert rec.state.shape == (5, 2, 16, 128) and rec.step_rows == 5
    assert rec.pend_g.shape == (5, 2, 5, 4, 16)     # a value a key channel
    mem = eng.memory_arrays()
    assert mem["alloc"]["cacheRow"] == "latent+state"
    assert mem["alloc"]["stateBytes"]["slots"] == rec.slot_nbytes
    assert mem["alloc"]["rowBytes"] == CFG.cache_dim * 4
    assert not eng.kv_transfer_supported()


@pytest.mark.parametrize("refused,message", [
    ({"kv_int8": True}, "int8 KV pool is not served for a latent cache"),
    ({"kv_host_bytes": 1 << 20}, "host KV tier is not served for a latent"),
])
def test_int8_pages_and_the_host_tier_are_refused(refused, message):
    with pytest.raises(ValueError, match=message):
        _engine(**refused)


def test_a_mesh_a_tree_of_drafts_and_a_checkpoint_are_refused(params):
    from gridllm_tpu.engine.loader import load_checkpoint

    with pytest.raises(ValueError, match="one device only"):
        km.validate_mesh(CFG, object())
    with pytest.raises(NotImplementedError, match="tree verification"):
        km.verify_step(params, CFG, jnp.zeros((2, 5), jnp.int32), _cache(),
                       jnp.asarray([True, False]), tree_pos=jnp.arange(5))
    with pytest.raises(NotImplementedError, match="checkpoints are not read"):
        load_checkpoint(CFG, "/nowhere")
    assert deepseek.softmax_scale(CFG) == 32 ** -0.5     # nope + rope, not 16


# -- compiled for the chip, without the chip --------------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_kda_kernels_compile_for_the_chip(one_chip):
    """Mosaic takes both kernels at Kimi-Linear's geometry (32 heads, keys
    and values of 128: one head a lane block, the decay a row [1, 128] a
    head made a diagonal in the kernel), and the step kernel updates the
    states in place."""
    def real(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    h, dk, dv, t, s = 32, 128, 128, 512, 16
    assert la.head_pack(dv, h) == 1
    jax.jit(lambda st, q, k, v, b, g, keep: la.kda_chunk(
        st, q, k, v, b, g, keep, 64, use_pallas=True)).lower(
            real((dk, h * dv)), real((t, h, dk)), real((t, h, dk)),
            real((t, h, dv)), real((t, h)), real((t, h, dk)),
            real((2,), jnp.int32)).compile()
    rows = [real((s, 5, h, dk)), real((s, 5, h, dk)), real((s, 5, h, dv)),
            real((s, 5, h)), real((s, 5, h, dk))]
    step = jax.jit(lambda st, li, pend, n, new, live: la.kda_step(
        st, li, pend, n, *new, live, use_pallas=True),
        donate_argnums=(0,)).lower(
            real((6, s, dk, h * dv)), real((), jnp.int32), tuple(rows[1:]),
            real((s,), jnp.int32), tuple(rows), real((s,), jnp.bool_)).compile()
    assert step.memory_analysis().alias_size_in_bytes >= 6 * s * dk * h * dv * 4
