"""What the ``lcf.*`` readers share: how a LongCat-Flash block's parts are
found in a run. Works for any configuration whose file carries the
published keys ``num_layers``, ``q_lora_rank``, ``kv_lora_rank``,
``hidden_size``, ``num_attention_heads``, ``n_routed_experts``,
``expert_ffn_hidden_size`` (and the latent widths ``mla.py`` reads) and
whose costs file has ``pool_layers``; anything else (a configuration of
another family, a program without the operations or the counters, such as
the parent of the PR that added them) reads as nothing, never as an error.

The latent reads and the absorb products are found by ``mla.latent_ops``
(the accepted helper, unedited). Its ``least_seconds`` multiplies one
layer's count by ``num_hidden_layers``; a LongCat block owns TWO layers of
the latent pool and its file says ``num_layers``, so ``view`` hands the
helper the run with that one key set to the pool's layers
(``longcat_flash_costs.pool_layers``): 8 at four blocks.

The program puts ``jax.named_scope("mla_qlora")``, ``"moe_zero"`` and
``"scmoe_join"`` around its new parts (``models/deepseek.py``,
``models/mixtral.py``, ``models/longcat_flash.py``), but the profiler's
events carry the HLO line without its metadata (see ``moe.py``), so the
patterns go by what that line shows: the low-rank query's products have
``W_qa [E, rq]`` or ``W_qb [rq, H x (nope + rope)]`` or the normed
``[rows.., rq]`` among their shapes; the held experts' products are the
``grouped_experts`` kernel (``readers.GROUPED_OPS``), XLA's ``ragged-dot``,
or carry the stacked held experts ``[held, E, F]`` / ``[held, F, E]`` or
the all-experts intermediate ``[rows.., held, F]``.
"""

from __future__ import annotations

import re

import costs
import mla
import readers

PICKS = "gridllm_moe_picks_total"
STEP_PROGRAMS = mla.STEP_PROGRAMS


def shapes(spec: dict) -> dict | None:
    try:
        return {k: int(spec[k]) for k in (
            "num_layers", "q_lora_rank", "kv_lora_rank", "hidden_size",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "n_routed_experts", "expert_ffn_hidden_size")}
    except (KeyError, TypeError, ValueError):
        return None


def view(run: dict) -> dict | None:
    """The run as ``mla.least_seconds`` reads it: ``num_hidden_layers`` =
    the latent pool's layers. None for another family."""
    spec = run["config"]
    count = costs.of(spec)
    if shapes(spec) is None or not hasattr(count, "pool_layers"):
        return None
    return {**run, "config": {**spec, "num_hidden_layers": count.pool_layers(spec)}}


def _ops(run: dict, pat: str | None, programs: str) -> list[dict]:
    if pat is None:
        return []
    return [o for o in readers.ops(run, pat) if re.search(programs, o["program"])]


def qlora_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    s = shapes(run["config"])
    if s is None:
        return []
    e, rq = s["hidden_size"], s["q_lora_rank"]
    hq = s["num_attention_heads"] * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
    return _ops(run, rf"[\[,]{e},{rq}\]|[\[,]{rq},{hq}\]|\[(\d+,)+{rq}\]",
                programs)


def held_ops(run: dict, programs: str = STEP_PROGRAMS) -> list[dict]:
    s = shapes(run["config"])
    if s is None:
        return []
    x, e, f = s["n_routed_experts"], s["hidden_size"], s["expert_ffn_hidden_size"]
    return _ops(run, rf"ragged-dot|[\[,]{x},{e},{f}\]|[\[,]{x},{f},{e}\]"
                rf"|\[(\d+,)+{x},{f}\]|" + readers.GROUPED_OPS, programs)


def picks(run: dict) -> dict[str, float] | None:
    """The window's router picks of live rows by where the expert lives."""
    got = {w: readers.counter_delta(run, "worker", PICKS, where=w)
           for w in ("held", "absent", "zero")}
    return got if sum(got.values()) > 0 and shapes(run["config"]) else None
