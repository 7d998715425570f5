"""The reference check, in a child of its own once the serving processes
have left the chip.

    python benchmark/reference_check.py --config <file> --records <json> [--rehearse]

Builds the weights the worker served: ``init_params(PRNGKey(0))`` of the
family module the program's engine picks for this ``ModelConfig`` (its own
resolver; no family is named here), in the configuration's type. Where the
configuration states a ``mesh`` the tree is made as the worker's is laid
out: the program's ``build_mesh`` from the same ``GRIDLLM_MESH_SHAPE``, its
``param_shardings`` of the tree's shapes, and one ``jax.jit(init,
out_shardings=...)``, so that no chip ever holds the whole tree (the
shardings move no bit; against the eager tree a jitted init rounds about
one element in a million the other way by one bf16 step, on the chip and on
the CPU: ``tests/test_sharded_init.py``, PERF.md).
Then runs the configuration's reference module (``reference.module``:
plain ``jax.numpy`` that imports nothing from the program, unedited on
sharded leaves) teacher-forced over each recorded request (prompt ids plus
the served output ids) and holds the served greedy tokens to the
configuration's two limits. The number compared is the shortfall: at a
generated position, the float32 reference maximum less the reference
logit of the served token, under the repeat penalty the request asked for
(``loadgen.REPEAT_PENALTY``). (1) At every position it is at most
``margin_abs + margin_rel * max|logit|``: one wrong token fails. (2) Its
mean over all checked positions is at most ``margin_mean``: a model that is
a little wrong everywhere fails, also where the output has settled on one
token whose lead a wrong model does not overturn (PERF.md, PR 26). Prints
``REFERENCE=<json>`` as its last line.

``--control`` adds the comparison that has to FAIL: the same records with
one layer left out of the reference. It is read on the chip when a limit is
set and kept as a test (``benchmark/tests``); the benchmark's own runs do
not run it and ``correct`` does not wait for it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(rel_path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_reference", os.path.join(HERE, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_mesh(spec: dict):
    """The mesh the worker builds from ``GRIDLLM_MESH_SHAPE`` (which the
    harness sets from ``mesh``), or None: ``worker.main._mesh_config``'s
    parse, so an axis left out is the program's default."""
    import costs
    from gridllm_tpu.parallel.mesh import MeshConfig, build_mesh

    axes = costs.mesh_axes(spec)
    return build_mesh(MeshConfig(**axes)) if axes else None


def init_params(cfg, dtype, mesh):
    """The tree the worker serves; under a mesh, made sharded."""
    import jax

    from gridllm_tpu.engine.engine import _model_module
    from gridllm_tpu.parallel.sharding import param_shardings

    def init():
        return _model_module(cfg).init_params(cfg, jax.random.PRNGKey(0), dtype)

    if mesh is None:
        return init()
    return jax.jit(init, out_shardings=param_shardings(
        jax.eval_shape(init), mesh))()


def reference_sizes(ref, cfg, spec: dict, rehearse: bool) -> dict:
    """What the reference module reads its shapes from: the file's
    published keys, or in a rehearsal (a tiny preset under the file's
    name) the module's own ``sizes(cfg)``."""
    if not rehearse:
        return spec
    if hasattr(ref, "sizes"):
        return ref.sizes(cfg)
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
        "tie_word_embeddings": cfg.tie_embeddings,
    }


def check(ref, params, sizes, vocab: int, limits: dict, records: list,
          skip_layer: int | None = None) -> dict:
    """The records teacher-forced through the reference (with `skip_layer`
    left out: the control) and their shortfalls held to `limits`
    (``margin_abs``, ``margin_rel``, ``margin_mean``).
    Every number compared is returned beside its limit."""
    import jax.numpy as jnp

    import loadgen

    m_abs, m_rel = limits["margin_abs"], limits["margin_rel"]
    m_mean = limits["margin_mean"]
    rows, total, positions = [], 0.0, 0
    for rec in records:
        toks = [int(t) % vocab for t in rec["context"]]
        lg = ref.logits(params, sizes, toks, skip_layer=skip_layer)
        short, top = ref.margins(lg, toks, rec["n_prompt"],
                                 loadgen.REPEAT_PENALTY, loadgen.REPEAT_LAST_N)
        over = short - (m_abs + m_rel * top)
        i = int(jnp.argmax(over))
        total, positions = total + float(short.sum()), positions + short.shape[0]
        rows.append({"index": rec["index"], "n_prompt": rec["n_prompt"],
                     "generated": len(toks) - rec["n_prompt"],
                     "worst_shortfall": float(short[i]),
                     "allowed_there": float(m_abs + m_rel * top[i]),
                     "max_abs_logit": float(top[i]), "at_generated": i,
                     "mean_shortfall": float(short.mean()),
                     "positions_over": int((over > 0).sum())})
    mean = total / positions if positions else None
    return {"records": rows, "mean_shortfall": mean, "mean_allowed": m_mean,
            "agrees": bool(rows)
            and all(r["positions_over"] == 0 for r in rows)
            and mean <= m_mean}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--records", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="also the comparison that must fail: one layer left out")
    args = ap.parse_args()
    t0 = time.monotonic()
    with open(args.config) as f:
        spec = json.load(f)
    with open(args.records) as f:
        records = json.load(f)

    import jax
    import jax.numpy as jnp

    from gridllm_tpu.utils.config import compile_cache_dir
    import launch_worker

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    name = launch_worker.config_name(args.config)
    launch_worker.check_deployment(spec, name)
    cfg = launch_worker.model_config(spec, name, args.rehearse)
    params = init_params(cfg, getattr(jnp, spec["dtype"]), build_mesh(spec))
    jax.block_until_ready(params)
    ref = load_reference(spec["reference"]["module"])
    sizes = reference_sizes(ref, cfg, spec, args.rehearse)
    t_weights = time.monotonic() - t0
    out = check(ref, params, sizes, cfg.vocab_size, spec["reference"], records)
    if args.control:
        skipped = check(ref, params, sizes, cfg.vocab_size, spec["reference"],
                        records, skip_layer=cfg.num_layers // 2)
        out["layer_skipped"] = skipped
        out["layer_skipped_fails"] = bool(records) and not skipped["agrees"]
    out["platform"] = jax.devices()[0].platform
    out["devices"] = len({d for leaf in jax.tree_util.tree_leaves(params)
                          for d in leaf.devices()})
    out["memory_peak_bytes"] = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in jax.devices()), default=0)
    out["weights_s"] = t_weights
    out["seconds"] = time.monotonic() - t0
    print("REFERENCE=" + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
