"""What the reference's weights rest on (``reference_check.init_params``),
and what the engine's own init will rest on once it stops building the
whole tree on device 0: ``init_params`` under ONE jit with the program's
``param_shardings`` as out-shardings, for every generative family, on a
four-device CPU mesh. Two properties, kept apart because they are not the
same: (1) the out-shardings change no bit (sharded jit == plain jit, leaf
by leaf); (2) against the EAGER tree the worker serves today a jitted init
may round a rare element the other way (XLA fuses the normal's arithmetic
with the scale and the cast: tiny-mixtral's ``we_down``, 1 element of
65,536, by one bf16 step), never more than one step and never more than
one element in ten thousand; the dense presets are equal bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_check
from gridllm_tpu.engine.engine import _model_module
from gridllm_tpu.models.configs import get_config

CASES = [("tiny-mistral", "tp:4"), ("tiny-mixtral", "tp:4"),
         ("tiny-mixtral", "ep:2,tp:2"), ("tiny-gemma2", "tp:4"),
         ("tiny-qwen3", "tp:4")]


def bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint16).astype(np.int32)


@pytest.mark.parametrize("preset,mesh", CASES)
def test_init_under_one_jit_with_out_shardings(preset, mesh):
    cfg = get_config(preset)

    def init():
        return _model_module(cfg).init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)

    eager, plain = init(), jax.jit(init)()
    built = reference_check.build_mesh({"mesh": mesh})
    assert built.devices.size == 4
    sharded = reference_check.init_params(cfg, jnp.bfloat16, built)
    assert jax.tree_util.tree_structure(sharded) == jax.tree_util.tree_structure(eager)
    differing = 0
    for (path, e), p, s in zip(jax.tree_util.tree_leaves_with_path(eager),
                               jax.tree_util.tree_leaves(plain),
                               jax.tree_util.tree_leaves(sharded)):
        assert e.dtype == s.dtype and e.shape == s.shape, path
        assert np.array_equal(bits(p), bits(s)), path       # (1): no bit moved
        step = np.abs(bits(e) - bits(s))                    # (2): a rare last bit
        assert step.max() <= 1, path
        differing += int(step.sum())
    total = sum(x.size for x in jax.tree_util.tree_leaves(eager))
    assert differing <= total / 10_000
    if cfg.family != "mixtral":
        assert differing == 0
    # and it is sharded: every leaf lives on the four devices, some are split
    leaves = jax.tree_util.tree_leaves(sharded)
    assert all(len(x.devices()) == 4 for x in leaves)
    assert any(x.addressable_shards[0].data.size < x.size for x in leaves)


def test_no_mesh_is_the_eager_call():
    cfg = get_config("tiny-mistral")
    assert reference_check.build_mesh({"mesh": ""}) is None
    a = reference_check.init_params(cfg, jnp.float32, None)
    b = _model_module(cfg).init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def test_the_mesh_is_the_workers():
    """``mesh`` parsed here and ``GRIDLLM_MESH_SHAPE`` parsed by the worker
    give one MeshConfig."""
    import types

    from gridllm_tpu.parallel.mesh import MeshConfig
    from gridllm_tpu.worker.main import _mesh_config

    import costs

    for shape in ("tp:4", "ep:2,tp:2", "ep:4", "dp:1,tp:4"):
        theirs = _mesh_config(types.SimpleNamespace(
            engine=types.SimpleNamespace(mesh_shape=shape)))
        assert MeshConfig(**costs.mesh_axes({"mesh": shape})).resolve(4) \
            == theirs.resolve(4), shape
