"""PR 52: a routed family's memory shares charge a verify launch for the
experts its live rows touched, not for every expert held, so that a
grouped product that reads the touched experts alone (ROADMAP S9) cannot
read over 100; and the four families' expert patterns know such a kernel
by its name. Each of the four routed configurations against a synthetic
launch whose expert operations take exactly the touched experts' bytes
over the chip's bandwidth."""
import pytest

import costs
import phases
import run as harness

BANDWIDTH = 819e9        # TPU v5e, benchmark/peaks.json
LAUNCHES = 40            # verify launches in the synthetic capture
CTX_TOKENS = 30_000      # live context tokens a launch
WINDOW_TOKENS = 21_000   # the same with each layer's window applied
VERIFY = "jit_verify_block_fn"
# a grouped kernel as the trace would print it: named after its wrapper,
# scalar operands first, no weight shape within the 240 characters kept
GROUPED = ("%grouped_experts.3 = bf16[80,2560]{1,0:T(8,128)(2,1)} custom-call("
           "s32[65]{0} %group_offsets, s32[80]{0} %row_ids, s32[1]{0} %n")

# cell: (the experts' share, the experts' time share, one expert's bytes)
FAMILIES = {
    "smallthinker21b.chat": (
        "moe.expert_mem_roofline_pct", "moe.time_pct", "one_expert_bytes"),
    "dsv2lite.shared_doc": (
        "experts.mem_roofline_pct", "experts.time_pct", "one_expert_bytes"),
    "laguna-xs2.agent_turns": (
        "routed.verify_mem_roofline_pct", "routed.time_pct", "expert_bytes"),
    "kimilinear.agent_turns": (
        "held.mem_roofline_pct", "held.time_pct", "expert_bytes"),
}
# what the costs files' step_weight_bytes returned before it took `touched`
# (read on the parent commit's files)
WHOLE_STEP_BYTES = {
    "smallthinker21b.chat": 10_344_980_480,
    "dsv2lite.shared_doc": 11_108_706_304,
    "laguna-xs2.agent_turns": 7_328_669_696,
    "kimilinear.agent_turns": 7_922_220_416,
}


def metrics(launches: float, touched: float | None, ctx_tokens: float,
            window_tokens: float) -> str:
    lines = [
        f'gridllm_engine_phase_seconds_sum{{model="m",phase="dispatch_verify"}} 1.0',
        f'gridllm_engine_phase_seconds_count{{model="m",phase="dispatch_verify"}} {launches}',
        f'gridllm_engine_verify_ctx_tokens_total{{model="m"}} {ctx_tokens}',
        f'gridllm_engine_verify_window_tokens_total{{model="m"}} {window_tokens}']
    if touched is not None:
        lines.append(f'gridllm_moe_experts_touched_total{{model="m"}} {touched}')
    return "\n".join(lines) + "\n"


def kv_bytes(cell: str) -> float:
    """What a launch of the synthetic capture reads of the cache, by hand:
    the two families with a window by the window counter over every layer
    (PR 55: ``kv_launch_bytes``), the others the context over the layers
    that keep pages."""
    spec = harness.Cell(cell).config
    if cell == "smallthinker21b.chat":
        return WINDOW_TOKENS * 12 * 2 * 4 * 128 * 2      # 24,576 B a position
    if cell == "laguna-xs2.agent_turns":
        return WINDOW_TOKENS * 5 * 2 * 8 * 128 * 2       # 20,480 B a position
    return CTX_TOKENS * costs.of(spec).kv_bytes_per_token(spec)


def counts(cell: str):
    """(the configuration, its costs file, one routed expert's bytes)."""
    spec = harness.Cell(cell).config
    count = costs.of(spec)
    return spec, count, getattr(count, FAMILIES[cell][2])(spec)


def a_launch(cell: str, touched: float | None, expert_seconds: float,
             text: str = GROUPED) -> dict:
    """A capture of LAUNCHES verify launches, each with `touched` experts
    touched (None: a program without the counter) and one expert operation
    of `expert_seconds`; everything else of the launch runs at four fifths
    of the bandwidth."""
    spec, count, one = counts(cell)
    rest = (count.step_weight_bytes(spec) - count.held_experts(spec) * one
            + kv_bytes(cell))
    step_seconds = expert_seconds + rest / (0.8 * BANDWIDTH)
    return {
        "config": spec,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "trace_counters": (
            metrics(10, None if touched is None else 1000, 1e6, 8e5),
            metrics(10 + LAUNCHES,
                    None if touched is None else 1000 + touched * LAUNCHES,
                    1e6 + CTX_TOKENS * LAUNCHES, 8e5 + WINDOW_TOKENS * LAUNCHES)),
        "trace": {
            "devices": {"/device:TPU:0": {"busy_s": 2 * step_seconds * LAUNCHES}},
            "programs": {VERIFY: {"seconds": step_seconds * LAUNCHES,
                                  "count": LAUNCHES}},
            "ops": {f"{VERIFY}#1/experts": {
                "program": VERIFY, "text": text, "count": LAUNCHES,
                "seconds": expert_seconds * LAUNCHES}}},
    }


def read(cell: str, name: str, run: dict):
    return harness.Cell(cell).reader(name).compute(run)


@pytest.mark.parametrize("cell", FAMILIES)
def test_the_costs_charge_the_touched_experts_and_nothing_else_moves(cell):
    spec, count, one = counts(cell)
    held = count.held_experts(spec)
    whole = count.step_weight_bytes(spec)
    # with no count, and with every expert touched: what it returned before
    assert whole == WHOLE_STEP_BYTES[cell]
    assert count.step_weight_bytes(spec, held) == whole
    assert count.step_weight_bytes(spec, touched=None) == whole
    # attention, norms, routers, shared experts, dense layers, the head: whole
    rest = count.step_weight_bytes(spec, 0)
    assert rest == whole - held * one and 0 < rest < 0.2 * whole
    for touched in (held / 5, held / 2, 0.355 * held):
        assert count.step_weight_bytes(spec, touched) == pytest.approx(
            rest + touched * one, rel=1e-12)
    # the every-expert counts the readers had stand beside the new one
    if FAMILIES[cell][2] == "one_expert_bytes":
        assert count.expert_bytes(spec) == held * one
        assert one == count.expert_params(spec) * 2


@pytest.mark.parametrize("share", [0.2, 0.5, 1.0])
@pytest.mark.parametrize("cell", FAMILIES)
def test_a_launch_that_reads_the_touched_experts_alone_reads_100(cell, share):
    """The property the grouped product (S9) is measured against: expert
    operations that take exactly touched x one expert's bytes / bandwidth
    read 100 in the experts' share, the whole step under 100."""
    spec, count, one = counts(cell)
    touched = share * count.held_experts(spec)
    run = a_launch(cell, touched, touched * one / BANDWIDTH)
    assert phases.touched_per_launch(run) == pytest.approx(touched)
    assert read(cell, FAMILIES[cell][0], run) == pytest.approx(100.0)
    step = read(cell, "step.verify_mem_mfu_pct", run)
    kv = kv_bytes(cell)
    assert phases.kv_bytes_per_launch(run) == pytest.approx(kv, rel=1e-12)
    secs = run["trace"]["programs"][VERIFY]["seconds"] / LAUNCHES
    assert step == pytest.approx(
        100.0 * (count.step_weight_bytes(spec, touched) + kv) / BANDWIDTH / secs)
    assert 80.0 < step < 100.0
    # the all-experts form beside it: every expert read at the bandwidth
    # for the same rows reads the touched share, where it read 100 before
    every = a_launch(cell, touched, count.held_experts(spec) * one / BANDWIDTH)
    assert read(cell, FAMILIES[cell][0], every) == pytest.approx(100.0 * share)
    assert read(cell, "step.verify_mem_mfu_pct", every) < step + 1e-9


@pytest.mark.parametrize("cell", ["smallthinker21b.chat", "dsv2lite.shared_doc"])
def test_with_every_expert_touched_the_recounted_shares_are_the_old_ones(cell):
    """SmallThinker's share to the last bit; DeepSeek-V2's less the router
    and the shared experts it no longer charges (3 % of a layer's bytes)."""
    spec = harness.Cell(cell).config
    count = costs.of(spec)
    held = count.held_experts(spec)
    counted = a_launch(cell, held, 0.012)
    bare = a_launch(cell, None, 0.012)
    old, new = (read(cell, FAMILIES[cell][0], r) for r in (bare, counted))
    if cell == "smallthinker21b.chat":
        assert new == old == 100.0 * (count.expert_bytes(spec) / BANDWIDTH) / 0.012
    else:
        assert old == 100.0 * (count.expert_layer_bytes(spec) / BANDWIDTH) / 0.012
        assert new == pytest.approx(
            old * count.expert_bytes(spec) / count.expert_layer_bytes(spec))
        assert 0.96 * old < new < old
    assert read(cell, "step.verify_mem_mfu_pct", counted) == pytest.approx(
        read(cell, "step.verify_mem_mfu_pct", bare), rel=1e-12)


@pytest.mark.parametrize("cell", FAMILIES)
def test_a_capture_without_the_counter_reads_what_the_parent_read(cell):
    """No touched counter between the capture's ends: the whole-step share
    charges every weight, as ``step.verify_mem_roofline_pct`` did, to the
    last bit; without ``trace_counters`` it reads nothing, as it did."""
    spec = harness.Cell(cell).config
    run = a_launch(cell, None, 0.010)
    secs = run["trace"]["programs"][VERIFY]["seconds"] / LAUNCHES
    kv = phases.kv_bytes_per_launch(run)
    assert phases.touched_per_launch(run) is None
    assert read(cell, "step.verify_mem_mfu_pct", run) == (
        100.0 * ((WHOLE_STEP_BYTES[cell] + kv) / BANDWIDTH) / secs)
    run["trace_counters"] = None
    assert read(cell, "step.verify_mem_mfu_pct", run) is None
    if FAMILIES[cell][2] == "expert_bytes":        # Laguna's and Kimi's: as before
        assert read(cell, FAMILIES[cell][0], run) is None


def test_a_dense_configuration_reads_what_the_parent_read():
    """A dense costs file takes no `touched`; a stray counter changes
    nothing (``costs.py`` and ``olmo_hybrid_costs.py`` are not touched)."""
    for cell in ("mistral7b.chat", "olmohybrid7b.agent_turns"):
        spec = harness.Cell(cell).config
        count = costs.of(spec)
        run = a_launch("smallthinker21b.chat", 300.0, 0.010)
        run["config"] = spec
        secs = run["trace"]["programs"][VERIFY]["seconds"] / LAUNCHES
        kv = CTX_TOKENS * count.kv_bytes_per_token(spec)
        assert read(cell, "step.verify_mem_mfu_pct", run) == (
            100.0 * ((count.step_weight_bytes(spec) + kv) / BANDWIDTH) / secs)


@pytest.mark.parametrize("cell", FAMILIES)
def test_a_grouped_kernel_is_known_by_its_name(cell):
    """``%grouped_experts.N = ... custom-call(`` with no weight shape on
    its line is an expert product to the time share and to the roofline
    share of every family; the same line under another name is not."""
    spec, count, one = counts(cell)
    touched = count.held_experts(spec) / 4
    run = a_launch(cell, touched, touched * one / BANDWIDTH)
    assert read(cell, FAMILIES[cell][1], run) == pytest.approx(
        100.0 * (touched * one / BANDWIDTH * LAUNCHES)
        / run["trace"]["devices"]["/device:TPU:0"]["busy_s"])
    assert read(cell, FAMILIES[cell][0], run) == pytest.approx(100.0)
    other = a_launch(cell, touched, touched * one / BANDWIDTH,
                     GROUPED.replace("%grouped_experts.3", "%some_kernel.3"))
    assert read(cell, FAMILIES[cell][1], other) is None
    assert read(cell, FAMILIES[cell][0], other) is None
    # a fusion that merely consumes the kernel's result is not the kernel
    consumer = a_launch(cell, touched, 0.001,
                        "%fusion.9 = bf16[80,7]{1,0} fusion(bf16[80,7]{1,0} "
                        "%grouped_experts.3), kind=kLoop")
    assert read(cell, FAMILIES[cell][1], consumer) is None


def test_the_manifest_has_the_new_name_and_neither_old_one():
    manifest = harness.Cell("mistral7b.chat").manifest
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert "step.verify_mem_roofline_pct" not in entries
    assert "kernel.flash_prefill_roofline_pct" not in entries
    assert entries["step.verify_mem_mfu_pct"] == {
        "name": "step.verify_mem_mfu_pct", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "programs", "moves": "itl_p95_ms"}
    mod = harness.Cell("mistral7b.chat").reader("step.verify_mem_mfu_pct")
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
        "step.verify_mem_mfu_pct", "%", "programs", "itl_p95_ms")
    assert not hasattr(mod, "CELLS")          # every cell is asked for it
