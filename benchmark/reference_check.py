"""The reference check, in a child of its own once the serving processes
have left the chip.

    python benchmark/reference_check.py --config <file> --records <json> [--rehearse]

Builds the weights the worker served (the program's own
``init_params(PRNGKey(0))``, the configuration's type), runs
``reference/llama_f32.py`` teacher-forced over each recorded request
(prompt ids plus the served output ids) and holds every served greedy token
to the configuration's margin: its float32 reference logit, under the repeat
penalty the request asked for (``loadgen.REPEAT_PENALTY``), within
``margin_abs + margin_rel * max|logit|`` of the reference maximum. Then the
check of the check: the first record again with one layer left out must
FAIL that margin, or the margin is too loose to see a wrong model. Prints
``REFERENCE=<json>`` as its last line. One device only: a meshed
configuration needs the program's ``shard_params`` here (later PR).
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--records", required=True)
    ap.add_argument("--rehearse", action="store_true")
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
    import loadgen

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = launch_worker.model_config(
        spec, launch_worker.config_name(args.config), args.rehearse)
    from gridllm_tpu.models import llama

    params = llama.init_params(cfg, jax.random.PRNGKey(0),
                               getattr(jnp, spec["dtype"]))
    sizes = {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_eps,
        "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
        "tie_word_embeddings": cfg.tie_embeddings,
    } if args.rehearse else spec
    ref = load_reference(spec["reference"]["module"])
    m_abs = spec["reference"]["margin_abs"]
    m_rel = spec["reference"]["margin_rel"]
    t_weights = time.monotonic() - t0

    def worst(rec: dict, skip_layer: int | None = None) -> dict:
        toks = [int(t) % cfg.vocab_size for t in rec["context"]]
        lg = ref.logits(params, sizes, toks, skip_layer=skip_layer)
        short, top = ref.margins(lg, toks, rec["n_prompt"],
                                 loadgen.REPEAT_PENALTY, loadgen.REPEAT_LAST_N)
        over = short - (m_abs + m_rel * top)
        i = int(jnp.argmax(over))
        return {"index": rec["index"], "n_prompt": rec["n_prompt"],
                "generated": len(toks) - rec["n_prompt"],
                "worst_shortfall": float(short[i]),
                "allowed_there": float(m_abs + m_rel * top[i]),
                "max_abs_logit": float(top[i]), "at_generated": i,
                "mean_shortfall": float(short.mean()),
                "positions_over": int((over > 0).sum())}

    out = {"records": [worst(r) for r in records]}
    out["agrees"] = bool(records) and all(
        r["positions_over"] == 0 for r in out["records"])
    if records:
        n_layers = cfg.num_layers
        skipped = worst(records[0], skip_layer=n_layers // 2)
        out["layer_skipped"] = skipped
        out["layer_skipped_fails"] = skipped["positions_over"] > 0
    out["platform"] = jax.devices()[0].platform
    out["weights_s"] = t_weights
    out["seconds"] = time.monotonic() - t0
    print("REFERENCE=" + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
