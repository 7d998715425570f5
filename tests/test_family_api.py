"""The family API is what an engine launches (PR 49). The engine finds a
family module's entry points and hooks by NAME (duck typing:
`mod.mixed_step`, `getattr(mod, "STEP_STATS", False)`, ...), so a misspelt
hook is silence, or an AttributeError at a first request. One case a
module `engine._model_module` can return: the names are there, and no
others (and once: the lists are exactly what engine.py reads)."""
import inspect
import os
import re

import jax
import pytest

from gridllm_tpu.engine import engine as engine_mod
from gridllm_tpu.models.configs import get_config
from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh

# Every name engine.py reads off a family module, by what makes it read it.
EVERY_FAMILY = {"init_params", "hidden_states"}
DECODER = {"decode_step", "verify_step", "mixed_step"}
# an engine without a mixed step: ring attention (sp), parallel/pipeline (pp)
SP_OR_PP = {"prefill", "prefill_chunk"}
HOOKS = {
    "validate_mesh",                        # engine start-up, where defined
    "STEP_STATS",                           # a routed family's statistics
    "new_state", "commit_verify",           # cfg.cache_kinds has "state"
    "new_ring", "restore_snapshot",         # cfg.cache_kinds has "window"
    "SAVES",                                # either: the prefix cache's snapshots
    "splice_embeds", "encode_images",       # cfg.vision
}

# one preset a module, and the hooks that module defines
FAMILIES = {
    "tiny-llama": {"validate_mesh"},
    "tiny-mixtral": {"STEP_STATS"},
    "tiny-gemma2": {"validate_mesh"},
    "tiny-llava": {"splice_embeds", "encode_images"},
    "tiny-bert": set(),
    "tiny-deepseek-v2": {"validate_mesh", "STEP_STATS"},
    "tiny-olmo-hybrid": {"validate_mesh", "new_state", "commit_verify",
                         "SAVES"},
    "tiny-laguna": {"validate_mesh", "STEP_STATS", "new_ring",
                    "restore_snapshot", "SAVES"},
    "tiny-kimi-linear": {"validate_mesh", "STEP_STATS", "new_state",
                         "commit_verify", "SAVES"},
    "tiny-longcat-flash": {"validate_mesh", "STEP_STATS"},
    "tiny-granite-hybrid": {"validate_mesh", "new_state", "commit_verify",
                            "SAVES"},
}


def names_the_engine_reads() -> set[str]:
    src = open(os.path.join(os.path.dirname(engine_mod.__file__),
                            "engine.py")).read()
    return set(re.findall(r"\b(?:self\.)?d?mod\.([A-Za-z_]\w*)", src)) | set(
        re.findall(r'(?:getattr|hasattr)\(\s*(?:self\.)?d?mod,\s*"(\w+)"', src))


def admits(mod, cfg, **axes) -> bool:
    """Does the module's own start-up check let this mesh through?"""
    mesh = build_mesh(MeshConfig(**axes), devices=jax.devices()[:2])
    try:
        getattr(mod, "validate_mesh", lambda *_: None)(cfg, mesh)
    except ValueError:
        return False
    return True


def test_the_lists_are_the_engines_lookups():
    """A hook added to engine.py is added above, with the families that
    define it; and the presets reach eleven different modules."""
    assert names_the_engine_reads() == (
        EVERY_FAMILY | DECODER | SP_OR_PP | HOOKS)
    assert len({engine_mod._model_module(get_config(p))
                for p in FAMILIES}) == len(FAMILIES) == 11


@pytest.mark.parametrize("preset", sorted(FAMILIES))
def test_a_family_module_has_what_the_engine_looks_up(preset):
    cfg = get_config(preset)
    mod = engine_mod._model_module(cfg)
    have = {n for n in EVERY_FAMILY | DECODER | SP_OR_PP | HOOKS
            if hasattr(mod, n)}
    decoder = cfg.family != "bert_embed"
    want = EVERY_FAMILY | FAMILIES[preset] | (DECODER if decoder else set())
    if decoder and (admits(mod, cfg, sp=2) or admits(mod, cfg, pp=2)):
        want |= SP_OR_PP
    assert have == want

    # the hooks follow from the configuration, as the engine reads them
    kinds = cfg.cache_kinds
    assert ("new_state" in have) == ("state" in kinds)
    assert ("new_ring" in have) == ("window" in kinds)
    assert ("SAVES" in have) == ("state" in kinds or "window" in kinds)
    assert ("encode_images" in have) == bool(cfg.vision)
    if "STEP_STATS" in have:
        assert mod.STEP_STATS is True
        for step in (mod.decode_step, mod.verify_step):
            assert "with_stats" in inspect.signature(step).parameters
    if "SAVES" in have:     # the snapshots a chunk launch hands back
        assert "state_io" in inspect.signature(mod.mixed_step).parameters
