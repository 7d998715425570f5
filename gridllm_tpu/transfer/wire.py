"""Versioned chunked wire format for paged-KV state migration.

Disaggregated serving (ISSUE 7) ships a request's prefilled KV pages from
a prefill-pool worker to a decode-pool worker. The unit of transfer is
the longest CACHED FULL-PAGE PREFIX of the request's prompt — exactly
what ops/kvcache.py's content-addressed prefix cache registers when the
prefill finishes, and exactly what the decode side's ``match_prefix``
will re-derive from the token ids. The wire therefore carries:

- a JSON header: format version, request/model identity, pool geometry
  (page size, layer/head/dim counts), dtype, kvLayout (``ragged`` pools
  are UNPADDED while ``ragged-padded`` pools — and the ``legacy`` pools
  of older peers — are lane-padded; the wire always carries the UNPADDED
  model head dim and each side pads/slices to its own pool), the weight-quant mode (info
  only; KV bytes are the engine dtype either way), the token ids the
  pages cover, and a blake2b digest of the full payload;
- a raw payload: K bytes then V bytes, each [L, n_pages, ps, KVH, D]
  C-contiguous in the header's dtype;
- chunk frames: the payload split into ``chunkBytes`` pieces, each with
  its sequence number and a crc32 — one bus message per chunk
  (``kvx:{request_id}``), or the whole payload in one HTTP POST for
  large transfers (transfer/migrate.py picks the path).

The header travels OUT OF BAND (inside the receiver-prepare control
message), so the chunk stream itself is header-free and idempotent:
duplicate chunks are ignored, a crc/digest mismatch fails the import
loudly and the sender falls back to serving the request locally.
"""

from __future__ import annotations

import base64
import hashlib
import json
import zlib
from typing import Any

import numpy as np

WIRE_VERSION = 1


def _np_dtype(name: str) -> np.dtype:
    """Resolve a wire dtype name to numpy, including ml_dtypes extras
    (bfloat16 — the default KV dtype — is a registered numpy dtype via
    jax's ml_dtypes dependency, but only reachable through it)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def payload_bytes(k: np.ndarray, v: np.ndarray) -> bytes:
    """K then V, C-contiguous raw bytes."""
    return np.ascontiguousarray(k).tobytes() + np.ascontiguousarray(v).tobytes()


def build_header(
    request_id: str,
    model: str,
    tokens: list[int],
    k: np.ndarray,
    v: np.ndarray,
    *,
    kv_layout: str = "legacy",
    quant: str | None = None,
    chunk_bytes: int = 256 * 1024,
) -> tuple[dict[str, Any], bytes]:
    """(header, payload) for one export. ``k``/``v``: [L, n, ps, KVH, D]
    host arrays already sliced to the UNPADDED model head dim."""
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if k.ndim != 5:
        raise ValueError(f"expected [L, n, ps, KVH, D] pages, got {k.shape}")
    n_layers, n_pages, page_size, kv_heads, head_dim = k.shape
    if n_pages * page_size != len(tokens):
        raise ValueError(
            f"{n_pages} pages of {page_size} cover "
            f"{n_pages * page_size} tokens, not {len(tokens)}")
    payload = payload_bytes(k, v)
    chunk_bytes = max(int(chunk_bytes), 1)
    header = {
        "v": WIRE_VERSION,
        "requestId": request_id,
        "model": model,
        "dtype": str(k.dtype),
        "pageSize": page_size,
        "numLayers": n_layers,
        "kvHeads": kv_heads,
        "headDim": head_dim,
        "numPages": n_pages,
        "kvLayout": kv_layout,
        "quant": quant,
        "tokens": [int(t) for t in tokens],
        "totalBytes": len(payload),
        "chunkBytes": chunk_bytes,
        "numChunks": -(-len(payload) // chunk_bytes),
        "digest": hashlib.blake2b(payload, digest_size=16).hexdigest(),
    }
    return header, payload


def iter_chunks(header: dict[str, Any], payload: bytes):
    """Yield (seq, frame_json) chunk frames for the bus path."""
    cb = int(header["chunkBytes"])
    for seq in range(int(header["numChunks"])):
        piece = payload[seq * cb:(seq + 1) * cb]
        yield seq, json.dumps({
            "seq": seq,
            "crc": zlib.crc32(piece) & 0xFFFFFFFF,
            "data": base64.b64encode(piece).decode("ascii"),
        })


def build_spill_header(
    key_hex: str,
    model: str,
    k: np.ndarray,
    v: np.ndarray,
    *,
    k_scale: np.ndarray | None = None,
    v_scale: np.ndarray | None = None,
    quant: str | None = None,
    chunk_bytes: int = 256 * 1024,
) -> tuple[dict[str, Any], bytes]:
    """(header, payload) for ONE host-tier page spill (ISSUE 11). The
    spill codec IS the migration wire format — same version, chunk/crc
    framing, and whole-payload digest — addressed by the prefix cache's
    CHAIN KEY instead of token ids (at eviction time the allocator knows
    the key, not the tokens; a later ``match_prefix`` re-derives the same
    key from the prompt and restores). ``k``/``v``: [L, 1, ps, KVH, D]
    host arrays sliced to the UNPADDED model head dim. ``quant`` names
    the scale layout riding in ``k_scale``/``v_scale`` (float32):
    ``int8-page`` = one scale per (layer, page) — the host-side spill
    quantization of an fp pool; ``int8-rows`` = per-row scales copied
    verbatim from a resident int8 pool (GRIDLLM_KV_INT8)."""
    if k.shape != v.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if k.ndim != 5 or k.shape[1] != 1:
        raise ValueError(f"expected [L, 1, ps, KVH, D] page, got {k.shape}")
    if (k_scale is None) != (v_scale is None) or (
        (quant is None) != (k_scale is None)
    ):
        raise ValueError("quant and k_scale/v_scale travel together")
    payload = payload_bytes(k, v)
    scale_shape: list[int] = []
    if k_scale is not None:
        k_scale = np.ascontiguousarray(k_scale, np.float32)
        v_scale = np.ascontiguousarray(v_scale, np.float32)
        if k_scale.shape != v_scale.shape:
            raise ValueError(
                f"scale shape mismatch: {k_scale.shape} vs {v_scale.shape}")
        scale_shape = list(k_scale.shape)
        payload += k_scale.tobytes() + v_scale.tobytes()
    n_layers, _, page_size, kv_heads, head_dim = k.shape
    chunk_bytes = max(int(chunk_bytes), 1)
    header = {
        "v": WIRE_VERSION,
        "kind": "kv-spill",
        "chainKey": key_hex,
        "model": model,
        "dtype": str(k.dtype),
        "pageSize": page_size,
        "numLayers": n_layers,
        "kvHeads": kv_heads,
        "headDim": head_dim,
        "numPages": 1,
        "quant": quant,
        "scaleShape": scale_shape,
        "totalBytes": len(payload),
        "chunkBytes": chunk_bytes,
        "numChunks": -(-len(payload) // chunk_bytes),
        "digest": hashlib.blake2b(payload, digest_size=16).hexdigest(),
    }
    return header, payload


def spill_arrays(
    header: dict[str, Any], payload: bytes
) -> tuple[np.ndarray, np.ndarray, np.ndarray | None, np.ndarray | None]:
    """(k, v, k_scale, v_scale) from a verified spill payload (feed it
    through :class:`Assembler` first — that is what checks the digest)."""
    h = header
    dtype = _np_dtype(h["dtype"])
    shape = (int(h["numLayers"]), int(h["numPages"]), int(h["pageSize"]),
             int(h["kvHeads"]), int(h["headDim"]))
    n = int(np.prod(shape)) * dtype.itemsize
    scale_shape = tuple(int(s) for s in (h.get("scaleShape") or []))
    sn = int(np.prod(scale_shape)) * 4 if scale_shape else 0
    if len(payload) != 2 * n + 2 * sn:
        raise WireError(
            f"spill payload {len(payload)} bytes does not match "
            f"2×{n} + 2×{sn} for shape {shape} {dtype}")
    k = np.frombuffer(payload[:n], dtype=dtype).reshape(shape)
    v = np.frombuffer(payload[n:2 * n], dtype=dtype).reshape(shape)
    k_scale = v_scale = None
    if sn:
        k_scale = np.frombuffer(
            payload[2 * n:2 * n + sn], dtype=np.float32).reshape(scale_shape)
        v_scale = np.frombuffer(
            payload[2 * n + sn:], dtype=np.float32).reshape(scale_shape)
    return k, v, k_scale, v_scale


class WireError(RuntimeError):
    """Integrity/shape failure during reassembly — the import is aborted
    and the sender falls back to local serving."""


class Assembler:
    """Reassemble one transfer from chunk frames (bus) or the whole
    payload (HTTP). Duplicate chunks are ignored; crc32 guards each
    chunk, the header digest guards the whole payload."""

    def __init__(self, header: dict[str, Any]):
        if int(header.get("v", -1)) != WIRE_VERSION:
            raise WireError(f"unsupported wire version {header.get('v')!r}")
        self.header = header
        self.total = int(header["numChunks"])
        self._chunks: dict[int, bytes] = {}
        self._payload: bytes | None = None

    @property
    def received(self) -> int:
        return len(self._chunks)

    @property
    def contiguous(self) -> int:
        """Highest seq N such that chunks 0..N-1 all arrived — the
        receiver advertises this for sender-side backpressure."""
        n = 0
        while n in self._chunks:
            n += 1
        return n

    @property
    def complete(self) -> bool:
        return self._payload is not None or len(self._chunks) >= self.total

    def feed(self, frame: str) -> bool:
        """One bus chunk frame; returns True when the transfer completed."""
        rec = json.loads(frame)
        seq = int(rec["seq"])
        if seq < 0 or seq >= self.total or seq in self._chunks:
            return self.complete
        piece = base64.b64decode(rec["data"])
        if (zlib.crc32(piece) & 0xFFFFFFFF) != int(rec["crc"]):
            raise WireError(f"crc mismatch on chunk {seq}")
        self._chunks[seq] = piece
        return self.complete

    def feed_raw(self, payload: bytes) -> bool:
        """The HTTP fast path: the whole payload in one body."""
        self._payload = payload
        return True

    def payload(self) -> bytes:
        if self._payload is None:
            if not self.complete:
                raise WireError(
                    f"incomplete transfer: {self.received}/{self.total}")
            self._payload = b"".join(
                self._chunks[i] for i in range(self.total))
        if len(self._payload) != int(self.header["totalBytes"]):
            raise WireError(
                f"payload size {len(self._payload)} != "
                f"{self.header['totalBytes']}")
        digest = hashlib.blake2b(self._payload, digest_size=16).hexdigest()
        if digest != self.header["digest"]:
            raise WireError("payload digest mismatch")
        return self._payload

    def arrays(self) -> tuple[list[int], np.ndarray, np.ndarray]:
        """(tokens, k, v) with k/v reshaped to [L, n, ps, KVH, D]."""
        h = self.header
        payload = self.payload()
        dtype = _np_dtype(h["dtype"])
        shape = (int(h["numLayers"]), int(h["numPages"]), int(h["pageSize"]),
                 int(h["kvHeads"]), int(h["headDim"]))
        n = int(np.prod(shape)) * dtype.itemsize
        if len(payload) != 2 * n:
            raise WireError(
                f"payload {len(payload)} bytes does not match 2×{n} for "
                f"shape {shape} {dtype}")
        k = np.frombuffer(payload[:n], dtype=dtype).reshape(shape)
        v = np.frombuffer(payload[n:], dtype=dtype).reshape(shape)
        return [int(t) for t in h["tokens"]], k, v
