"""Operations and bytes computed from a configuration's shapes (the
published ``config.json`` keys of a ``configs/<name>.json``), and the table
of peaks. Kept with the benchmark so that no PR that claims a gain can
change what a roofline share is measured against."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


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
