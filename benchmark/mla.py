"""What the ``mla.*`` and ``experts.time_pct`` readers share: how a
latent-attention family's operations are found in a run. Works for any
configuration whose file carries the published keys ``kv_lora_rank``,
``qk_rope_head_dim``, ``qk_nope_head_dim``, ``v_head_dim``,
``num_attention_heads`` (and for the experts ``n_routed_experts``,
``hidden_size``, ``moe_intermediate_size``); anything else (a
configuration of another family, a program without the operations, such
as the parent of the PR that added them) reads as nothing, never as an
error.

The program puts ``jax.named_scope("mla_absorb")``, ``"mla_expand"`` and
``"moe_shared"`` around the operations (``models/deepseek.py``,
``models/mixtral.py``), but the profiler's events carry the HLO line
without its metadata (see ``moe.py``), so the patterns go by what that
line shows, read off the chip's trace (PERF.md, PR 36): the latent read
is the ``ragged_attention`` custom call (``readers.RAGGED_OPS``); an absorb
product has the folded query ``[rows.., H, rank + rope]`` (or its 640-lane
padded form) or ``[H, rank, rows]``, the latent output ``[rows.., H,
rank]`` or the up-projection ``[rank, H, nope + v]`` (or one half of it)
among its shapes: in the call-1 trace of PR 36 the folding product is
``f32[16,512,528] fusion(bf16[528,16,128], bf16[512,16,256])`` and the
output's ``bf16[16,5,2,8,128] fusion(bf16[16,5,16,512], bf16[512,16,256])``.
"""

from __future__ import annotations

import re

import costs
import phases
import readers
import stack

STEP_PROGRAMS = readers.VERIFY_PROGRAMS + "|" + readers.PREFILL_PROGRAMS
CHUNK_PROGRAMS = r"mixed_chunk|prefill_chunk"


def shapes(spec: dict) -> dict | None:
    try:
        return {k: int(spec[k]) for k in (
            "kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
            "v_head_dim", "num_attention_heads")}
    except (KeyError, TypeError, ValueError):
        return None


def absorb_pattern(spec: dict) -> str | None:
    s = shapes(spec)
    if s is None:
        return None
    h, r = s["num_attention_heads"], s["kv_lora_rank"]
    row = r + s["qk_rope_head_dim"]
    padded = -(-row // 128) * 128
    dn, dv = s["qk_nope_head_dim"], s["v_head_dim"]
    return (rf"\[(\d+,)+{h},({row}|{padded}|{r})\]"
            rf"|\[(\d+,)*{r},{h},({dn}|{dv}|{dn + dv})\]"
            rf"|\[{h},{r},\d+\]")


def latent_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    """The latent reads (the ragged kernel's launches) and the absorb
    products around them, inside the step programs."""
    pat = absorb_pattern(run["config"])
    if pat is None:
        return []
    found = {o["key"]: o for o in readers.ops(run, readers.RAGGED_OPS)}
    found.update((o["key"], o) for o in readers.ops(run, pat))
    return [o for o in found.values() if re.search(programs, o["program"])]


def expert_pattern(spec: dict) -> str | None:
    """Router, routed and shared products of a deepseek_v2 expert layer,
    a kernel named ``grouped_experts`` (``readers.GROUPED_OPS``) among them."""
    try:
        x, e, f = (int(spec["n_routed_experts"]), int(spec["hidden_size"]),
                   int(spec["moe_intermediate_size"]))
        fs = f * int(spec.get("n_shared_experts") or 0)
    except (KeyError, TypeError, ValueError):
        return None
    shared = rf"|[\[,]{e},{fs}\]|[\[,]{fs},{e}\]|\[(\d+,)+{fs}\]" if fs else ""
    return (rf"ragged-dot|[\[,]{x},{e},{f}\]|[\[,]{x},{f},{e}\]"
            rf"|\[(\d+,)+{x},{f}\]|f32\[(\d+,)+{x}\]|[\[,]{e},{x}\]" + shared
            + "|" + readers.GROUPED_OPS)


def expert_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    pat = expert_pattern(run["config"])
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat) if re.search(programs, o["program"])]


def verify_rows_per_slot(run: dict) -> float:
    """Query rows a slot of the verify / decode launches in the trace:
    K + 1 in a verify program (the engine's default K = 4), 1 in a decode
    block, weighted by launches."""
    _, n_verify = readers.programs(run, r"verify_block")
    _, n_decode = readers.programs(run, r"decode_block")
    k1 = int(run["config"].get("env", {}).get("GRIDLLM_SPEC_K", 4)) + 1
    return (k1 * n_verify + n_decode) / max(n_verify + n_decode, 1)


def chunk_launches(run: dict) -> tuple[float, float, float]:
    """(launches, real tokens, padded tokens) of the chunk programs over
    the window, from the engine's counters."""
    return (
        readers.counter_delta(run, "worker", "gridllm_engine_chunk_launches_total"),
        readers.counter_delta(run, "worker", "gridllm_engine_chunk_tokens_total",
                              kind="real"),
        readers.counter_delta(run, "worker", "gridllm_engine_chunk_tokens_total",
                              kind="padded"))


def chunk_context(run: dict) -> float | None:
    """Mean positions a chunk launch's queries attend, over the window:
    its prefix plus, causally, half of its own real rows. A request of n
    fresh tokens behind k cached ones runs m = ceil(n / C) launches whose
    prefixes are k, k + C, ..: it attends m k + C m (m - 1) / 2 + n / 2
    positions in all. Summed from the engine's counters (launches L, real
    tokens T, the prefix cache's hit pages x the page size = K cached
    tokens) and the schedule's groups: a group's first request is cold,
    the others ride its cached pages and take one launch each, so the cold
    ones took (L - others) / firsts launches apiece."""
    launches, real, padded = chunk_launches(run)
    groups = [r.group for r in run.get("requests") or []]
    if launches <= 0 or real <= 0 or not groups:
        return None
    hits = readers.counter_delta(run, "worker", "gridllm_prefix_cache_hits_total")
    cached = hits * float((run.get("pool") or {}).get("pageSize") or 0)
    # with nothing found by the cache every request is a cold one
    cold_n = len(set(groups)) if cached > 0 else len(groups)
    m = max((launches - (len(groups) - cold_n)) / cold_n, 1.0)
    cold = cold_n * (padded / launches) * m * (m - 1) / 2
    return (cached + cold + real / 2) / launches


def least_seconds(run: dict, kv_bytes, rows_per_slot: float,
                  ctx: float | None = None) -> float | None:
    """The least time one launch's latent attention could take on the
    chip, every layer: the longer of bytes over bandwidth and operations
    over the bf16 peak. `kv_bytes`: the launch's cache rows over every
    layer (or None with `ctx`, the positions read)."""
    spec = run["config"]
    count = costs.of(spec)
    peak = phases.hbm_bytes_per_s(run)
    if peak is None or not hasattr(count, "latent_attn_flops"):
        return None
    layers = spec["num_hidden_layers"]
    if ctx is None:
        ctx = kv_bytes / count.kv_bytes_per_token(spec)
    else:
        kv_bytes = layers * count.latent_attn_bytes(spec, ctx)
    flops = layers * (count.latent_attn_flops(spec, rows_per_slot, ctx)
                      + count.absorb_flops(spec, rows_per_slot))
    return max(kv_bytes / peak,
               flops / costs.peaks(run["device"]["kind"])["bf16_flops_per_s"])
