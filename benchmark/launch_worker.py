"""Start the program's own worker on one of the benchmark's configurations.

    python benchmark/launch_worker.py --config benchmark/configs/<name>.json [--rehearse]

Reads the file's published ``config.json`` keys with the program's own
reader (``models.configs._config_from_hf_dict``: every family and key the
program serves, none mapped here), registers the result with the program's
model registry under the file's name, then calls
``gridllm_tpu.worker.main.main()``: the normal worker entry point,
scheduler, bus, cache and kernels. No program file is edited for a depth
cut. The file's ``env`` (the deployment's ``GRIDLLM_*`` settings) and
``GRIDLLM_MESH_SHAPE`` (from its ``mesh``) are set by the harness before
this process starts. ``--rehearse`` registers the file's ``rehearse_base``
(a tiny preset) unchanged under the same name.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import costs


def model_config(spec: dict, name: str, rehearse: bool):
    """The ModelConfig a configuration file stands for. Held to the
    registry's ``base``: the file with every ``reduced`` key put back to
    its ``from`` must read as ``base`` in every field of the dataclass,
    or the field that differs is named and the file refused. A field the
    published keys cannot express is listed under ``reduced`` by its
    ``ModelConfig`` name."""
    from gridllm_tpu.models.configs import _config_from_hf_dict, get_config

    if rehearse:
        return dataclasses.replace(get_config(spec["rehearse_base"]), name=name)
    cfg = _config_from_hf_dict(name, spec, name + ".json")
    reduced = spec.get("reduced", {})
    whole = _config_from_hf_dict(
        name, {**spec, **{k: v["from"] for k, v in reduced.items() if k in spec}},
        name + ".json")
    # head_dim: None in a registry entry means hidden / heads
    whole, base = (dataclasses.replace(c, head_dim=c.head_dim_)
                   for c in (whole, get_config(spec["base"])))
    for f in dataclasses.fields(cfg):
        got, want = getattr(whole, f.name), getattr(base, f.name)
        if got != want and f.name not in ("name", *reduced):
            raise SystemExit(
                f"{name}: {f.name}={got!r} differs from the registry's "
                f"{spec['base']} ({want!r}) and is not listed under reduced")
    return cfg


def check_deployment(spec: dict, name: str) -> None:
    """``mesh`` is the one source of the mesh: ``env`` may not say
    otherwise, ``chips`` is the mesh's size, and a mesh the costs cannot
    split over whole heads is refused here, before anything starts."""
    mesh = spec.get("mesh") or ""
    for env in (spec.get("env", {}), spec.get("rehearse_env", {})):
        said = env.get("GRIDLLM_MESH_SHAPE", mesh)
        if said != mesh:
            raise SystemExit(
                f"{name}: env sets GRIDLLM_MESH_SHAPE={said!r} but mesh is "
                f"{mesh!r}; the harness sets the variable from mesh")
    try:
        size = costs.mesh_size(spec)
        costs.of(spec).chip_share(spec)
    except ValueError as e:
        raise SystemExit(f"{name}: {e}")
    if size != spec.get("chips", 1):
        raise SystemExit(f"{name}: mesh {mesh!r} spans {size} chips but "
                         f"chips is {spec.get('chips', 1)}")


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
