"""Pipeline parallelism over the "pp" mesh axis (SURVEY.md §2.5; the last
parallelism form the framework lacked — VERDICT r03 missing #7).

TPU-first design: stages are LAYER blocks. Every stacked [L, ...] params
leaf and the [L, ...] KV page pool are sharded on axis 0 over "pp"
(parallel/sharding.py), so stage s holds layers [s*L/pp, (s+1)*L/pp) and
their KV — the memory win that makes models beyond one slice's HBM
servable. Compute is a token-passing schedule inside ONE jitted program:

    jax.shard_map, manual over {"pp"} only (jax partial-manual mode) —
    "tp"/"ep"/"sp"/"dp" stay AUTO, so the existing GSPMD tensor layout
    (Megatron specs, psum on wo/w_down) keeps working untouched inside
    each stage.

    the live activation starts on stage 0 (every device embeds — cheap,
    replicated); each stage applies its layer block when the live value
    reaches it (lax.cond on axis_index, per-device branches are exactly
    what manual mode permits), then the value hops one stage via
    ppermute. After the last stage, a masked psum broadcasts the final
    hidden state so the (pp-replicated) unembed + sampler see it
    everywhere. Per step the wire carries (pp-1+1) tensors of [S, E] —
    tens of KB, cheap enough to ride DCN, which is why "pp" is the
    outermost mesh axis.

Two schedules share this structure. Prefill (one slot at a time by
construction) and the fallback decode use the SEQUENTIAL schedule — one
live activation, 1/pp utilization. The decode hot path is MICROBATCHED
(GPipe-style): slots split into pp groups; at tick t stage p runs
microbatch t-p. Each stage does pp ticks of work in a 2pp-1-tick step,
so utilization is pp/(2pp-1) ≈ 50% (the classic GPipe bubble; more
microbatches than stages would push it higher). Either way PP's main
buy here is MEMORY — BASELINE's serving configs are all within-slice,
where tp is the right axis; pp is for the models that do not fit one
slice.

The reference has no analogue (single-GPU Ollama nodes); the design
follows the public GPipe/shard_map pattern (PAPERS.md — pattern
reference only).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gridllm_tpu.models import llama
from gridllm_tpu.models.configs import ModelConfig
from gridllm_tpu.ops.kvcache import (
    PagedKVCache,
    write_decode_all,
    write_prefill_all,
)
from gridllm_tpu.ops.layers import rms_norm

Params = dict


def _pp_shard_map(mesh, in_specs, out_specs):
    """Decorator for the pp token-passing programs: manual over {"pp"}
    only, tp/ep/sp/dp stay AUTO (GSPMD)."""
    return partial(jax.shard_map, mesh=mesh, axis_names={"pp"},
                   in_specs=in_specs, out_specs=out_specs, check_vma=False)


def pp_size(mesh) -> int:
    return int(mesh.shape.get("pp", 1)) if mesh is not None else 1


def validate(cfg: ModelConfig, mesh) -> None:
    pp = pp_size(mesh)
    if pp <= 1:
        return
    if cfg.num_layers % pp:
        raise ValueError(
            f"{cfg.name}: num_layers {cfg.num_layers} not divisible by "
            f"pp={pp}"
        )
    if mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            "pp and sp (ring-attention prefill) cannot combine yet — "
            "nested manual collectives; shape the mesh with one of them"
        )
    if cfg.family not in ("llama", "qwen2", "qwen3", "llava"):
        raise ValueError(
            f"pp supports the llama-skeleton families, not {cfg.family}"
        )


def _ring(pp: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % pp) for i in range(pp)]


def _stage_specs(params: Params) -> Params:
    """shard_map in_specs for the params pytree: layer-stacked leaves are
    manual on "pp" axis 0, everything else pp-replicated. Only the MANUAL
    axis appears — tp/ep placement stays automatic (GSPMD)."""

    def leaf_spec(path, leaf):
        in_layers = any(
            isinstance(e, jax.tree_util.DictKey) and e.key == "layers"
            for e in path
        )
        return P("pp") if in_layers else P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def _bcast_from_last(x: jnp.ndarray, p: jnp.ndarray, pp: int) -> jnp.ndarray:
    """Masked psum: the fully-processed activation lives on stage pp-1;
    every stage needs it for the (replicated) final norm + unembed.
    The sum runs in fp32: exact (one nonzero term), and bf16 psum under
    partial-manual shard_map hard-crashes XLA's CPU backend ("Invalid
    binary instruction opcode copy", hlo_instruction.cc:1585 — jax 0.9)."""
    mask = (p == pp - 1).astype(jnp.float32)
    x32 = x.astype(jnp.float32) * mask
    return jax.lax.psum(x32, "pp").astype(x.dtype)


def _token_passing(pp: int, stage, x, k_pool, v_pool):
    """The shared schedule of all three entry points: the live activation
    visits each stage in turn (lax.cond on this device's stage id — only
    the owner computes), hopping stages via ppermute; the final stage's
    result is broadcast to all for the replicated norm/unembed tail.
    Returns (x broadcast everywhere, k_pool, v_pool)."""
    p = jax.lax.axis_index("pp")
    for k in range(pp):
        x, k_pool, v_pool = jax.lax.cond(
            p == k, stage, lambda args: args, (x, k_pool, v_pool)
        )
        if k < pp - 1:
            x = jax.lax.ppermute(x, "pp", _ring(pp))
    return _bcast_from_last(x, p, pp), k_pool, v_pool


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: PagedKVCache,
    active: jnp.ndarray,
    mlp=llama._mlp,
    mesh=None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """PP decode step — same contract as llama.decode_step.

    Slots are split into pp MICROBATCHES and pipelined GPipe-style: at
    tick t, stage p runs its layer block on microbatch t-p — pp ticks of
    work per stage in a 2pp-1-tick step (≈50% utilization vs the
    sequential schedule's 1/pp; the fill/drain bubble is the classic
    GPipe cost of matching microbatch count to stage count). Falls back
    to the sequential schedule when S % pp != 0.
    """
    pp = pp_size(mesh)
    s = tokens.shape[0]
    positions = cache.lengths
    new_lengths = jnp.minimum(
        cache.lengths + active.astype(jnp.int32), cache.max_context
    )
    microbatched = s % pp == 0 and s >= pp

    @_pp_shard_map(
        mesh,
        in_specs=(_stage_specs(params), P(), P("pp"), P("pp"), P(), P(), P()),
        out_specs=(P(), P("pp"), P("pp")),
    )
    def run(params, tokens, k_pool, v_pool, page_table, positions, active):
        x = params["embed"][tokens]  # [S, E] — every stage embeds

        def stage(args):
            x, kp, vp = args
            x, k_new, v_new, _ = llama.stack(
                params["layers"], cfg, x, positions,
                llama.group_attend(cfg, kp, vp, cache.page_size, page_table,
                                   positions), mlp,
            )
            # Pallas stays off here regardless of cfg.use_pallas: the auto
            # axes inside this partial-manual region (tp/ep) still go
            # through GSPMD, and pallas_call has no partitioning rule —
            # same constraint that makes the engine disable kernels under
            # any mesh (engine.py _init).
            kp, vp = write_decode_all(
                kp, vp, k_new, v_new, page_table, positions, active,
                cache.page_size, use_pallas=False,
            )
            return x, kp, vp

        x, k_pool, v_pool = _token_passing(pp, stage, x, k_pool, v_pool)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = llama._unembed(cfg, params, x)
        return logits, k_pool, v_pool

    @_pp_shard_map(
        mesh,
        in_specs=(_stage_specs(params), P(), P("pp"), P("pp"), P(), P(), P()),
        out_specs=(P(), P("pp"), P("pp")),
    )
    def run_mb(params, tokens, k_pool, v_pool, page_table, positions,
               active):
        p = jax.lax.axis_index("pp")
        m_sz = s // pp
        e = params["embed"].shape[1]
        n_local = jax.tree.leaves(params["layers"])[0].shape[0]
        kvh, d = k_pool.shape[-2], k_pool.shape[-1]
        x_all = params["embed"][tokens]          # [S, E] — cheap, replicated
        buf = jnp.zeros((m_sz, e), x_all.dtype)  # activation arriving from p-1
        outs = jnp.zeros((pp, m_sz, e), x_all.dtype)  # last-stage results
        k_acc = jnp.zeros((pp, n_local, m_sz, kvh, d), k_pool.dtype)
        v_acc = jnp.zeros_like(k_acc)

        def stage_mb(x_in, m):
            """This stage's layer block on microbatch m's slots."""
            off = m * m_sz
            pt = jax.lax.dynamic_slice_in_dim(page_table, off, m_sz)
            pos = jax.lax.dynamic_slice_in_dim(positions, off, m_sz)
            return llama.stack(
                params["layers"], cfg, x_in, pos,
                llama.group_attend(cfg, k_pool, v_pool, cache.page_size, pt,
                                   pos), mlp,
            )[:3]

        for t in range(2 * pp - 1):  # static unroll: pipeline schedule
            m = t - p                # this tick's microbatch for this stage
            mc = jnp.clip(m, 0, pp - 1)
            busy = (m >= 0) & (m < pp)
            # stage 0 picks up fresh embeddings; later stages continue the
            # activation handed over by the previous stage last tick
            fresh = jax.lax.dynamic_slice_in_dim(x_all, mc * m_sz, m_sz)
            x_in = jnp.where(p == 0, fresh, buf)

            def work(args):
                x_in, k_acc, v_acc = args
                x_out, k_new, v_new = stage_mb(x_in, mc)
                k_acc = jax.lax.dynamic_update_slice_in_dim(
                    k_acc, k_new[None], mc, axis=0)
                v_acc = jax.lax.dynamic_update_slice_in_dim(
                    v_acc, v_new[None], mc, axis=0)
                return x_out, k_acc, v_acc

            x_out, k_acc, v_acc = jax.lax.cond(
                busy, work, lambda args: args, (x_in, k_acc, v_acc)
            )
            outs = jnp.where(
                busy & (p == pp - 1),
                jax.lax.dynamic_update_slice_in_dim(outs, x_out[None], mc,
                                                    axis=0),
                outs,
            )
            if t < 2 * pp - 2:
                buf = jax.lax.ppermute(x_out, "pp", _ring(pp))

        # every device wrote its own layer block's K/V for ALL microbatches
        # (accumulated per tick) — one deferred pool write, as elsewhere
        k_new_all = k_acc.transpose(1, 0, 2, 3, 4).reshape(
            n_local, s, kvh, d)
        v_new_all = v_acc.transpose(1, 0, 2, 3, 4).reshape(
            n_local, s, kvh, d)
        k_pool, v_pool = write_decode_all(
            k_pool, v_pool, k_new_all, v_new_all, page_table, positions,
            active, cache.page_size, use_pallas=False,
        )
        # final-stage activations → everyone, for the replicated tail
        x = _bcast_from_last(outs.reshape(s, e), p, pp)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = llama._unembed(cfg, params, x)
        return logits, k_pool, v_pool

    fn = run_mb if microbatched else run
    logits, k_pool, v_pool = jax.jit(fn)(
        params, tokens, cache.k, cache.v, cache.page_table, positions, active
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool, page_table=cache.page_table,
        lengths=new_lengths, page_size=cache.page_size,
    )


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mlp=llama._mlp,
    attn=None,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """PP prefill of ONE slot — same contract as llama.prefill."""
    if attn is not None:
        raise ValueError("pp prefill has no sp/ring-attention variant")
    pp = pp_size(mesh)

    @_pp_shard_map(
        mesh,
        in_specs=(
            _stage_specs(params), P(), P(),
            P("pp"), P("pp"), P(), P(),
        ),
        out_specs=(P(), P("pp"), P("pp")),
    )
    def run(params, tokens, embeds_or_tokens, k_pool, v_pool, length,
            table_row):
        x = (
            params["embed"][tokens] if embeds is None else embeds_or_tokens
        )
        x = x.astype(params["embed"].dtype)[None]  # [1, T, E]

        def stage(args):
            x, kp, vp = args
            x, k_new, v_new, _ = llama.stack(
                params["layers"], cfg, x,
                jnp.arange(x.shape[1], dtype=jnp.int32)[None],
                llama.whole_attend(cfg, length[None]), mlp,
            )
            kp, vp = write_prefill_all(
                kp, vp, k_new[:, 0], v_new[:, 0], table_row, jnp.int32(0),
                length,
                cache.page_size, use_pallas=False,  # see decode_step note
            )
            return x, kp, vp

        x, k_pool, v_pool = _token_passing(pp, stage, x, k_pool, v_pool)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        last = x[0, jnp.maximum(length - 1, 0)]
        logits = llama._unembed(cfg, params, last)
        return logits, k_pool, v_pool

    logits, k_pool, v_pool = jax.jit(run)(
        params, tokens, tokens if embeds is None else embeds,
        cache.k, cache.v, length, table_row,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=cache.lengths.at[slot].set(length),
        page_size=cache.page_size,
    )


def prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    start: jnp.ndarray,
    length: jnp.ndarray,
    cache: PagedKVCache,
    slot: jnp.ndarray,
    table_row: jnp.ndarray,
    mlp=llama._mlp,
    mesh=None,
    embeds: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """PP chunked prefill — same contract as llama.prefill_chunk."""
    pp = pp_size(mesh)

    @_pp_shard_map(
        mesh,
        in_specs=(
            _stage_specs(params), P(), P(), P("pp"), P("pp"), P(), P(), P(),
        ),
        out_specs=(P(), P("pp"), P("pp")),
    )
    def run(params, tokens, embeds_or_tokens, k_pool, v_pool, start,
            length, table_row):
        x = (
            params["embed"][tokens] if embeds is None else embeds_or_tokens
        )
        x = x.astype(params["embed"].dtype)[None]  # [1, C, E]

        def stage(args):
            x, kp, vp = args
            x, k_new, v_new, _ = llama.stack(
                params["layers"], cfg, x,
                (start + jnp.arange(x.shape[1], dtype=jnp.int32))[None],
                llama.chunk_attend(cfg, kp, vp, cache.page_size, table_row,
                                   start, start + length), mlp,
            )
            kp, vp = write_prefill_all(
                kp, vp, k_new[:, 0], v_new[:, 0], table_row, start, length,
                cache.page_size, use_pallas=False,  # see decode_step note
            )
            return x, kp, vp

        x, k_pool, v_pool = _token_passing(pp, stage, x, k_pool, v_pool)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        last = x[0, jnp.maximum(length - 1, 0)]
        logits = llama._unembed(cfg, params, last)
        return logits, k_pool, v_pool

    logits, k_pool, v_pool = jax.jit(run)(
        params, tokens, tokens if embeds is None else embeds,
        cache.k, cache.v, start, length, table_row,
    )
    return logits, PagedKVCache(
        k=k_pool, v=v_pool,
        page_table=cache.page_table.at[slot].set(table_row),
        lengths=cache.lengths.at[slot].set(start + length),
        page_size=cache.page_size,
    )
