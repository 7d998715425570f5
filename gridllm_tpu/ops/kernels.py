"""KERNELS: the typed Pallas-kernel registry (gridcheck v3, ISSUE 14).

Every Pallas kernel in ``ops/pallas_kernels.py`` is declared here ONCE —
with the jnp reference that is its numerical oracle, the
``gridllm_kernel_dispatch_total`` label its dispatcher records under, the
tolerance its differential test (and the runtime numerics sanitizer,
``analysis/numcheck.py``) holds it to, and the named test that owns the
kernel-vs-reference differential. The ``kernel-parity`` analyzer rule
cross-checks all of it both ways: an unregistered ``pl.pallas_call``
site, a registered kernel whose reference or test went missing, a
dispatch label the registry doesn't know (or vice versa), and drift in
the README "Kernels" table are each a ``--strict`` failure.

This mirrors the ``ENV_VARS`` (utils/config.py) and ``CHANNELS``
(bus/base.py) pattern: pure data, importable without jax, parsed from
the AST by the rule so ``--root`` on another checkout validates THAT
checkout's registry.

Tolerances are the BF16-input bound (the loosest dtype the serving path
feeds the kernels); f32 differential tests pass far inside it. The two
KV-write kernels are data movement, not math — their oracle is the
scatter form and the bound is exact (0); the numerics sanitizer covers
them with the NaN/Inf tripwire instead of value shadowing.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One Pallas kernel's parity contract."""

    name: str         # public entry fn in ops/pallas_kernels.py
    reference: str    # "module:function" jnp oracle under gridllm_tpu/ops/
    dispatch: str     # gridllm_kernel_dispatch_total op label
    rtol: float       # differential-test / numcheck relative tolerance
    atol: float       # ... absolute tolerance
    test: str         # "tests/file.py::test_name" owning differential test
    description: str


KERNELS: tuple[KernelSpec, ...] = (
    KernelSpec(
        name="flash_prefill",
        reference="attention:attention_prefill_ref",
        dispatch="attention_prefill",
        rtol=3e-2, atol=3e-2,
        test="tests/test_pallas.py::test_flash_prefill_matches_ref",
        description="causal GQA flash attention over one prompt chunk, "
                    "K/V VMEM-resident per kv head",
    ),
    KernelSpec(
        name="flash_prefill_streamed",
        reference="attention:attention_prefill_ref",
        dispatch="attention_prefill",
        rtol=3e-2, atol=3e-2,
        test="tests/test_pallas.py::test_flash_prefill_streamed_matches_ref",
        description="flash prefill past the VMEM budget: K/V blocks "
                    "stream from HBM as a grid dimension",
    ),
    KernelSpec(
        name="ragged_attention",
        reference="attention:ragged_paged_attention_ref",
        dispatch="attention_ragged",
        rtol=3e-2, atol=3e-2,
        test="tests/test_ragged_attention.py::"
             "test_ragged_kernel_mixed_batch_matches_ref",
        description="ragged paged attention: one launch serving "
                    "chunked prefill, decode, and spec-verify tiles "
                    "against the HBM page pool, double-buffered page DMA "
                    "(int8 pools via the dequant epilogue)",
    ),
    KernelSpec(
        name="paged_write_decode",
        reference="kvcache:write_decode",
        dispatch="write_decode",
        rtol=0.0, atol=0.0,
        test="tests/test_pallas.py::test_paged_write_decode_matches_scatter",
        description="in-place per-row KV pool write (decode / flattened "
                    "spec-verify rows), DMA instead of XLA scatter",
    ),
    KernelSpec(
        name="paged_write_chunk",
        reference="kvcache:write_prefill",
        dispatch="write_prefill",
        rtol=0.0, atol=0.0,
        test="tests/test_pallas.py::"
             "test_paged_write_chunk_matches_scatter_valid_region",
        description="in-place whole-page KV pool write for one slot's "
                    "prefill chunk, all layers",
    ),
    KernelSpec(
        name="gdn_chunk",
        reference="linear_attn:gdn_recurrent",
        dispatch="gdn_chunk",
        rtol=2e-3, atol=2e-3,
        test="tests/test_olmo_hybrid.py::test_gdn_chunk_matches_recurrent",
        description="the gated delta rule over one slot's prompt rows in "
                    "blocks of 64 from a carried state (VMEM-resident a "
                    "head pack), the state at chosen blocks' ends handed "
                    "back for the prefix cache's snapshots",
    ),
    KernelSpec(
        name="gdn_step",
        reference="linear_attn:gdn_recurrent",
        dispatch="gdn_step",
        rtol=2e-3, atol=2e-3,
        test="tests/test_olmo_hybrid.py::test_gdn_step_matches_recurrent",
        description="the gated delta rule over a launch's 1 to K+1 rows of "
                    "every slot: the last launch's accepted rows committed "
                    "into the state in place, the new rows run on top",
    ),
    KernelSpec(
        name="ssd_chunk",
        reference="linear_attn:ssd_recurrent",
        dispatch="ssd_chunk",
        rtol=2e-3, atol=2e-3,
        test="tests/test_granite_hybrid.py::test_ssd_chunk_matches_recurrent",
        description="the state-space scan (the rule without the delta, B "
                    "and C shared by every head) over one slot's prompt "
                    "rows in blocks of 64 from a carried state: one "
                    "product at the packed width a lane tile of 1,024, "
                    "the state at chosen blocks' ends handed back",
    ),
    KernelSpec(
        name="ssd_step",
        reference="linear_attn:ssd_recurrent",
        dispatch="ssd_step",
        rtol=2e-3, atol=2e-3,
        test="tests/test_granite_hybrid.py::test_ssd_step_matches_recurrent",
        description="the state-space scan over a launch's 1 to K+1 rows of "
                    "every live slot: the last launch's accepted rows "
                    "committed into the state in place, the new rows read "
                    "on top; one read and one write of a live slot's state",
    ),
    KernelSpec(
        name="grouped_experts",
        reference="experts:grouped_experts_ref",
        dispatch="grouped_experts",
        rtol=3e-2, atol=3e-2,
        test="tests/test_grouped_experts.py::"
             "test_grouped_experts_matches_its_reference_and_the_all_experts_form",
        description="the routed experts' products over the experts a live "
                    "row touched and no others: an expert's gate, up and "
                    "down slabs by double-buffered DMA from the stacked "
                    "leaves, all rows against each, float32 sums",
    ),
    KernelSpec(
        name="grouped_experts_sorted",
        reference="experts:sorted_experts_ref",
        dispatch="grouped_experts_sorted",
        rtol=3e-2, atol=3e-2,
        test="tests/test_grouped_experts.py::"
             "test_the_sorted_regime_matches_its_reference_and_the_all_experts_form",
        description="the same products at rows past the chip's ridge: the "
                    "rows sorted by expert into whole row tiles, a tile's "
                    "expert from scalar prefetch, each expert's slabs "
                    "fetched once against its own rows; the custom call "
                    "is named grouped_experts",
    ),
)

# Dispatch labels with NO kernel of their own: dispatchers whose kernel
# leg routes through another registered kernel (write_multi flattens onto
# paged_write_decode).
# The kernel-parity rule requires the union of KERNELS dispatch labels
# and this table to equal the set of record_kernel_path(...) literals in
# ops/ exactly, both ways.
EXTRA_DISPATCH_LABELS: dict[str, str] = {
    "write_multi": "multi-token append flattened onto paged_write_decode",
    "kda_chunk": "the delta rule with a decay a key channel (Kimi Delta "
                 "Attention) through gdn_chunk's kernel, general over the "
                 "decay's shape; the custom call is named kda_chunk",
    "kda_step": "the same through gdn_step's kernel, named kda_step",
}


def kernel_names() -> tuple[str, ...]:
    return tuple(k.name for k in KERNELS)


def dispatch_labels() -> frozenset[str]:
    """Every legal gridllm_kernel_dispatch_total op label."""
    return frozenset(k.dispatch for k in KERNELS) | frozenset(
        EXTRA_DISPATCH_LABELS)


def by_dispatch(label: str) -> tuple[KernelSpec, ...]:
    return tuple(k for k in KERNELS if k.dispatch == label)


def tolerance(label: str) -> tuple[float, float]:
    """(rtol, atol) the numerics sanitizer applies to a dispatch label —
    the loosest bound among the kernels sharing it (they share a
    reference when they share a label)."""
    specs = by_dispatch(label)
    if not specs:
        raise KeyError(f"unknown kernel dispatch label {label!r}")
    return (max(k.rtol for k in specs), max(k.atol for k in specs))
