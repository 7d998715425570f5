"""Llava vision-language family: goldens vs HF + engine e2e.

SURVEY.md §4 test strategy (engine numeric goldens vs HF twins) applied
to the vision path (VERDICT r03 missing #5): the torch twin is
transformers' LlavaForConditionalGeneration on the tiny-llava config.
"""

import base64
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gridllm_tpu.models import llava
from gridllm_tpu.models.configs import get_config

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def twin():
    """(our cfg, our fp32 params, HF model) with identical weights."""
    cfg = get_config("tiny-llava")
    hf_cfg = cfg.hf_config()
    torch.manual_seed(0)
    with torch.no_grad():
        model = transformers.LlavaForConditionalGeneration(hf_cfg).eval()
    params = llava.convert_hf_state_dict(cfg, model.state_dict(), jnp.float32)
    return cfg, params, model


def _pixels(n, cfg, seed=0):
    rng = np.random.default_rng(seed)
    s = cfg.vision_cfg.image_size
    return rng.normal(size=(n, 3, s, s)).astype(np.float32)


def test_vision_tower_matches_hf(twin):
    cfg, params, model = twin
    px = _pixels(2, cfg)
    ours = np.asarray(llava.vision_tower(params, cfg.vision_cfg, jnp.asarray(px)))
    with torch.no_grad():
        theirs = model.model.vision_tower(
            torch.from_numpy(px), output_hidden_states=True
        ).hidden_states[cfg.vision_cfg.feature_layer][:, 1:].numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_encode_images_matches_hf(twin):
    cfg, params, model = twin
    px = _pixels(1, cfg)
    ours = np.asarray(llava.encode_images(params, cfg, jnp.asarray(px)))
    with torch.no_grad():
        theirs = model.get_image_features(
            pixel_values=torch.from_numpy(px),
            vision_feature_layer=cfg.vision_cfg.feature_layer,
            vision_feature_select_strategy="default",
        )
    theirs = (theirs[0] if isinstance(theirs, (tuple, list)) else theirs).numpy()
    theirs = theirs.reshape(ours.shape)
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


def test_full_forward_matches_hf(twin):
    """End-to-end logits: expanded image tokens + splice == HF's
    masked-scatter of image features."""
    cfg, params, model = twin
    vc = cfg.vision_cfg
    px = _pixels(1, cfg)
    rng = np.random.default_rng(1)
    text = rng.integers(0, 240, size=(7,))
    ids = np.concatenate([
        text[:3], np.full((vc.num_patches,), vc.image_token), text[3:],
    ]).astype(np.int32)

    img = llava.encode_images(params, cfg, jnp.asarray(px))
    flat = img.reshape(-1, img.shape[-1])
    embeds = llava.splice_embeds(params, cfg, jnp.asarray(ids), flat)
    ours = np.asarray(
        llava.forward(params, cfg, jnp.asarray(ids)[None], embeds=embeds[None])
    )[0]

    with torch.no_grad():
        out = model(
            input_ids=torch.from_numpy(ids[None].astype(np.int64)),
            pixel_values=torch.from_numpy(px),
        ).logits[0].float().numpy()
    np.testing.assert_allclose(ours, out, rtol=2e-3, atol=2e-3)


def test_splice_offset_chunks_agree(twin):
    """Chunked splice (per-chunk offset) == whole-prompt splice."""
    cfg, params, _ = twin
    vc = cfg.vision_cfg
    ids = np.array(
        [1, 2] + [vc.image_token] * vc.num_patches + [3]
        + [vc.image_token] * vc.num_patches + [4, 5], np.int32)
    flat = jnp.asarray(
        np.random.default_rng(2).normal(
            size=(2 * vc.num_patches, cfg.hidden_size)).astype(np.float32))
    whole = np.asarray(llava.splice_embeds(params, cfg, jnp.asarray(ids), flat))
    c = 4
    parts = []
    for s0 in range(0, len(ids), c):
        part = ids[s0:s0 + c]
        off = int((ids[:s0] == vc.image_token).sum())
        parts.append(np.asarray(llava.splice_embeds(
            params, cfg, jnp.asarray(part), flat, offset=off)))
    np.testing.assert_allclose(np.concatenate(parts), whole, rtol=1e-6, atol=1e-6)


def test_preprocess_matches_hf_processor():
    from gridllm_tpu.engine.images import preprocess_images

    PIL = pytest.importorskip("PIL")
    from PIL import Image

    rng = np.random.default_rng(3)
    img = Image.fromarray(rng.integers(0, 255, (50, 41, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    b64 = base64.b64encode(buf.getvalue()).decode()

    ours = preprocess_images([b64], 28)[0]

    proc = transformers.CLIPImageProcessor(
        size={"shortest_edge": 28}, crop_size={"height": 28, "width": 28},
        do_convert_rgb=True,
    )
    theirs = proc(images=img, return_tensors="np")["pixel_values"][0]
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-4)


def test_engine_serves_image_request(twin):
    """Full engine path: base64 PNG in, generated tokens out; marker-free
    prompt gets the image span inserted after BOS."""
    from PIL import Image

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    rng = np.random.default_rng(4)
    img = Image.fromarray(rng.integers(0, 255, (30, 30, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    b64 = base64.b64encode(buf.getvalue()).decode()

    eng = InferenceEngine(EngineConfig(
        model="tiny-llava", max_slots=2, page_size=16, num_pages=64,
        max_pages_per_slot=8, prefill_buckets=(32, 64),
    ))
    res = eng.generate(GenerationRequest(
        id="img1", prompt="hi", images=[b64],
        options={"temperature": 0, "num_predict": 4, "seed": 1},
    ))
    assert res.done_reason in ("stop", "length")
    assert res.prompt_eval_count >= eng.cfg.vision_cfg.num_patches

    # same request again must be deterministic (seeded, temperature 0)
    res2 = eng.generate(GenerationRequest(
        id="img2", prompt="hi", images=[b64],
        options={"temperature": 0, "num_predict": 4, "seed": 1},
    ))
    assert res2.token_ids == res.token_ids


def test_engine_rejects_marker_mismatch(twin):
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    eng = InferenceEngine(EngineConfig(
        model="tiny-llava", max_slots=2, page_size=16, num_pages=64,
        max_pages_per_slot=8, prefill_buckets=(32, 64),
    ))
    vc = eng.cfg.vision_cfg
    # two markers, one image → loud failure
    res = eng.generate(GenerationRequest(
        id="bad", prompt_ids=[1, vc.image_token, 2, vc.image_token],
        images=["aGVsbG8="],  # not even a real image; rejected before decode
        options={"num_predict": 2},
    ))
    assert res.done_reason == "error"
    assert "placeholder" in (res.error or "")


def test_non_vision_model_rejects_images():
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    eng = InferenceEngine(EngineConfig(
        model="tiny-llama", max_slots=1, page_size=16, num_pages=32,
        max_pages_per_slot=4, prefill_buckets=(32,),
    ))
    res = eng.generate(GenerationRequest(
        id="noimg", prompt="x", images=["aGVsbG8="],
        options={"num_predict": 2},
    ))
    assert res.done_reason == "error"
    assert "image" in (res.error or "")


def test_context_roundtrip_requires_images(twin):
    """Ollama `context` from an image turn carries expanded image-token
    runs: re-sending it WITHOUT the pixels must fail loudly (placeholder
    embeddings would silently answer about an unseen image); re-sending
    WITH the images must work (already-expanded runs pass through)."""
    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    eng = InferenceEngine(EngineConfig(
        model="tiny-llava", max_slots=2, page_size=16, num_pages=64,
        max_pages_per_slot=8, prefill_buckets=(32, 64),
    ))
    vc = eng.cfg.vision_cfg
    ctx = [1, 2] + [vc.image_token] * vc.num_patches + [3]

    res = eng.generate(GenerationRequest(
        id="ctx-no-img", prompt_ids=ctx, options={"num_predict": 2}))
    assert res.done_reason == "error"
    assert "re-send" in (res.error or "")

    import base64
    import io

    import numpy as np
    from PIL import Image

    img = Image.fromarray(
        np.random.default_rng(5).integers(0, 255, (20, 20, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    res = eng.generate(GenerationRequest(
        id="ctx-img", prompt_ids=ctx,
        images=[base64.b64encode(buf.getvalue()).decode()],
        options={"temperature": 0, "num_predict": 2, "seed": 0}))
    assert res.done_reason in ("stop", "length")


def test_plan_replay_reproduces_vision_admission(twin):
    """Multi-host followers replay admit records; a vision admit carries
    the raw base64 payload and the follower must re-run preprocessing +
    encode + splice to land in the SAME device state as the LIAISON.
    Compared against the liaison's actual pool (prompt rows are written
    once at prefill and never touched by later decode steps), and against
    a no-image replay to prove the image actually changed the K/V."""
    import base64
    import io

    import numpy as np
    from PIL import Image

    from gridllm_tpu.engine import EngineConfig, InferenceEngine
    from gridllm_tpu.engine.engine import GenerationRequest

    img = Image.fromarray(
        np.random.default_rng(6).integers(0, 255, (24, 24, 3), np.uint8))
    buf = io.BytesIO()
    img.save(buf, format="PNG")
    b64 = base64.b64encode(buf.getvalue()).decode()

    kw = dict(model="tiny-llava", max_slots=2, page_size=16, num_pages=64,
              max_pages_per_slot=8, prefill_buckets=(32, 64))
    liaison = InferenceEngine(EngineConfig(**kw))
    follower = InferenceEngine(EngineConfig(**kw))
    records = []
    liaison.plan_sink = records.append

    res = liaison.generate(GenerationRequest(
        id="vp", prompt="look", images=[b64],
        options={"temperature": 0, "num_predict": 3, "seed": 4}))
    assert res.done_reason in ("stop", "length")
    admits = [r for r in records if r["op"] == "admit"]
    assert admits and admits[0].get("images") == [b64]
    rec = admits[0]
    n_prompt = len(rec["ids"])
    ps = kw["page_size"]
    pages = [p for p in rec["row"] if p >= 0][: -(-n_prompt // ps)]

    def prompt_rows(eng):
        # positions [0, n_prompt) of the slot, gathered from its pages
        pool = np.asarray(eng.cache.k)  # [L, P, ps, KVH, D]
        rows = np.concatenate([pool[:, p] for p in pages], axis=1)
        return rows[:, :n_prompt]

    want = prompt_rows(liaison)  # decode wrote positions >= n_prompt only

    follower.apply_plan_op(rec)
    np.testing.assert_array_equal(prompt_rows(follower), want)
    assert int(np.asarray(follower.cache.lengths)[rec["slot"]]) == n_prompt
    # the record's format is what it was (ISSUE 25 moved the sampler row
    # into one program, not the plan): the follower's sampler row is the
    # record's, its slot is live, and once every record has been replayed
    # ("deact" included) sampler rows and active flags are the liaison's
    assert set(rec) == {"op", "slot", "ids", "row", "sp", "cached", "images"}
    assert np.asarray(follower.active)[rec["slot"]]
    for f, v in rec["sp"].items():
        got = np.asarray(getattr(follower.sampling, f))
        assert got[rec["slot"]] == np.asarray(v, got.dtype) + (f == "step"), f
    assert {"op": "deact", "slot": rec["slot"]} in records
    for later in records[records.index(rec) + 1:]:
        follower.apply_plan_op(later)
    for f in rec["sp"]:
        np.testing.assert_array_equal(
            np.asarray(getattr(follower.sampling, f)),
            np.asarray(getattr(liaison.sampling, f)), f)
    np.testing.assert_array_equal(np.asarray(follower.active),
                                  np.asarray(liaison.active))

    # and the image must MATTER: replaying with the pixels dropped gives
    # different K/V (guards against a replay path that skips the splice)
    textonly = InferenceEngine(EngineConfig(**kw))
    rec_no_img = dict(rec)
    rec_no_img.pop("images")
    textonly.apply_plan_op(rec_no_img)
    assert not np.array_equal(prompt_rows(textonly), want)
