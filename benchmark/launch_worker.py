"""Start the program's own worker on one of the benchmark's configurations.

    python benchmark/launch_worker.py --config benchmark/configs/<name>.json [--rehearse]

Registers ``dataclasses.replace(get_config(base), name=<name>, <the file's
sizes>)`` with the program's model registry, then calls
``gridllm_tpu.worker.main.main()``: the normal worker entry point,
scheduler, bus, cache and kernels. No program file is edited for a depth
cut. The file's ``env`` (the deployment's ``GRIDLLM_*`` settings) is set by
the harness before this process starts. ``--rehearse`` registers the
file's ``rehearse_base`` (a tiny preset) unchanged under the same name.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

# the published config.json key -> the program's ModelConfig field
HF_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq_len",
}


def model_config(spec: dict, name: str, rehearse: bool):
    """The ModelConfig a configuration file stands for."""
    from gridllm_tpu.models.configs import get_config

    if rehearse:
        return dataclasses.replace(get_config(spec["rehearse_base"]), name=name)
    sizes = {field: spec[key] for key, field in HF_KEYS.items() if key in spec}
    sizes["sliding_window"] = spec.get("sliding_window") or 0
    cfg = dataclasses.replace(get_config(spec["base"]), name=name, **sizes)
    base = get_config(spec["base"])
    for key, field in HF_KEYS.items():
        changed = getattr(cfg, field) != getattr(base, field)
        if field == "head_dim":
            changed = cfg.head_dim_ != base.head_dim_
        if changed and key not in spec.get("reduced", {}):
            raise SystemExit(
                f"{name}: {key}={getattr(cfg, field)} differs from the "
                f"registry's {spec['base']} ({getattr(base, field)}) and is "
                "not listed under reduced")
    return cfg


def config_name(path: str) -> str:
    return os.path.basename(path)[:-len(".json")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        spec = json.load(f)
    from gridllm_tpu.models.configs import register

    register(model_config(spec, config_name(args.config), args.rehearse))
    sys.argv = sys.argv[:1]
    from gridllm_tpu.worker.main import main as worker_main

    worker_main()


if __name__ == "__main__":
    main()
