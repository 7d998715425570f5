"""Operations and bytes computed from a configuration's shapes (the
published ``config.json`` keys of a ``configs/<name>.json``), and the table
of peaks. Kept with the benchmark so that no PR that claims a gain can
change what a roofline share is measured against.

This file is the dense SwiGLU decoder. A configuration of another
architecture (routed experts, latent attention) names a file of its own,
``"costs": "family_costs/<name>.py"``, with the same functions:
``total_params``, ``weight_bytes``, ``step_weight_bytes``,
``kv_bytes_per_token``, ``flash_prefill_flops`` and ``chip_share``, each
taking the configuration. ``of(spec)`` is the one lookup the readers use.
A family whose launch does not read every position of a context in every
layer (a window, a ring, a selection) also defines ``kv_launch_bytes(spec,
per_launch)``: the cache bytes ONE verify / decode launch reads, counted
from the program's own counters (``per_launch(name)`` is a counter's change
a launch of the capture, None where it did not move), never from a
constant; ``phases.kv_bytes_per_launch`` then charges that and not
context x ``kv_bytes_per_token``.
The first five count the whole model; ``chip_share`` says by how much
each is divided to give what ONE chip holds under the configuration's
``mesh``, because a trace's times are one chip's."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
MESH_AXES = ("pp", "dp", "ep", "tp", "sp")      # the program's vocabulary
# the program's count of what a verify / decode launch reads of its contexts
# where layers have a window: Σ over live slots of the mean over layers of
# min(context, that layer's window); the context counter where none has
WINDOW_TOKENS = "gridllm_engine_verify_window_tokens_total"


def of(spec: dict):
    """The module that counts for this configuration: the file its
    ``costs`` key names under ``benchmark/``, else this one."""
    rel = spec.get("costs")
    if not rel:
        return sys.modules[__name__]
    path = os.path.normpath(os.path.join(HERE, rel))
    if not path.startswith(HERE + os.sep):
        raise ValueError(f"costs file {rel!r} is not under benchmark/")
    name = "bench_costs_" + "".join(c if c.isalnum() else "_" for c in rel)
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def mesh_axes(spec: dict) -> dict[str, int]:
    """``"tp:4"`` -> ``{"tp": 4}``: the configuration's ``mesh``, in the
    form of ``GRIDLLM_MESH_SHAPE`` and parsed as the worker parses that."""
    axes = {}
    for part in filter(None, (spec.get("mesh") or "").split(",")):
        axis, _, size = part.partition(":")
        if axis not in MESH_AXES or not size.isdigit() or int(size) < 1:
            raise ValueError(f"mesh {spec.get('mesh')!r}: {part!r} is not "
                             f"<axis>:<size> with an axis of {MESH_AXES}")
        axes[axis] = int(size)
    return axes


def mesh_size(spec: dict) -> int:
    return math.prod(mesh_axes(spec).values())


def chip_share(spec: dict) -> dict | None:
    """What each whole-model count is divided by to give one chip's:
    ``weights`` (``step_weight_bytes``, ``weight_bytes``), ``kv``
    (``kv_bytes_per_token``) and ``heads`` (``flash_prefill_flops``, and
    the query heads in a kernel's result shape). No mesh: 1 each. ``tp:N``
    splits every projection, the FFN, the vocabulary and the KV heads N
    ways (the norms, 0.003 % of a layer, are held whole and counted as
    split). An axis with no rule here gives None, and a roofline reader
    then reports nothing rather than a guess."""
    axes = mesh_axes(spec)
    n = axes.pop("tp", 1)
    if any(size > 1 for size in axes.values()):
        return None
    if spec["num_key_value_heads"] % n or spec["num_attention_heads"] % n:
        raise ValueError(
            f"tp:{n} does not divide {spec['num_key_value_heads']} KV heads "
            f"and {spec['num_attention_heads']} query heads: one chip's "
            "share of the cache is not a whole head")
    return {"weights": n, "kv": n, "heads": n}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json; add it with its source")
    return table[device_kind]


def head_dim(spec: dict) -> int:
    return spec.get("head_dim") or spec["hidden_size"] // spec["num_attention_heads"]


def layer_params(spec: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o projections, the three
    SwiGLU matrices and the two norms."""
    e, f, d = spec["hidden_size"], spec["intermediate_size"], head_dim(spec)
    h, kvh = spec["num_attention_heads"], spec["num_key_value_heads"]
    return e * h * d + 2 * e * kvh * d + h * d * e + 3 * e * f + 2 * e


def embedding_params(spec: dict) -> int:
    """Token embedding, the output head unless tied, the final norm."""
    ve = spec["vocab_size"] * spec["hidden_size"]
    return (ve if spec.get("tie_word_embeddings") else 2 * ve) + spec["hidden_size"]


def total_params(spec: dict) -> int:
    return spec["num_hidden_layers"] * layer_params(spec) + embedding_params(spec)


def weight_bytes(spec: dict) -> int:
    return total_params(spec) * DTYPE_BYTES[spec.get("dtype", "bfloat16")]


def step_weight_bytes(spec: dict) -> int:
    """Weight bytes one decode or verify step must read: every layer and
    the output head; of the embedding only the rows looked up."""
    b = DTYPE_BYTES[spec.get("dtype", "bfloat16")]
    head = spec["vocab_size"] * spec["hidden_size"]
    return (spec["num_hidden_layers"] * layer_params(spec) + head) * b


def kv_bytes_per_token(spec: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one position over every layer."""
    return (spec["num_hidden_layers"] * 2 * spec["num_key_value_heads"]
            * head_dim(spec) * kv_dtype_bytes)


def flash_prefill_flops(spec: dict, t: int) -> float:
    """Floating-point operations one causal flash-attention call over a
    bucket of t positions needs, one layer: QK^T and PV are 2*t*t*D each
    per query head, and causality needs half of the square."""
    return 0.5 * 2 * 2.0 * spec["num_attention_heads"] * t * t * head_dim(spec)
