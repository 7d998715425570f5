"""The readers PR 24 added, each against a hand-made ``run``; a program
without the phase series (the parent) gives nothing and raises nothing;
and a trace recorded here, through the program's own capture and phase
clock, reduces to idle gaps named after the runner's phases."""
import threading
import time

import pytest

import costs
import phases
import run as harness
import trace_reduce
from loadgen import Outcome

PHASE_MS = {"idle_wait": (30000, 40), "ctl": (100, 1000), "admit": (400, 200),
            "dispatch_prefill": (1600, 200), "draft": (900, 1000),
            "dispatch_verify": (2000, 1000), "fetch": (15000, 1100),
            "ingest": (5000, 1100)}


def metrics(scale: float, ctx_tokens: float = 0.0, with_phases: bool = True) -> str:
    """A worker's /metrics text: every phase's sum (ms above) and count
    times `scale`, so that after - before is `scale` times the table."""
    lines = []
    if with_phases:
        for phase, (ms, n) in PHASE_MS.items():
            lab = f'{{model="m",phase="{phase}"}}'
            lines += [f"gridllm_engine_phase_seconds_sum{lab} {scale * ms / 1e3}",
                      f"gridllm_engine_phase_seconds_count{lab} {scale * n}"]
        lines += [f'gridllm_engine_verify_ctx_tokens_total{{model="m"}} {ctx_tokens}',
                  f'gridllm_engine_admit_wait_seconds_bucket{{model="m",le="+Inf"}} {scale * 200}',
                  f'gridllm_engine_admit_wait_seconds_sum{{model="m"}} {scale * 0.5}',
                  f'gridllm_engine_admit_wait_seconds_count{{model="m"}} {scale * 200}']
    return "\n".join(lines) + "\n"


def gateway(n: float, seconds: float) -> str:
    return "\n".join(
        f'gridllm_critical_path_seconds_{k}{{segment="{seg}"}} {v}'
        for seg, s in (("dispatch", seconds), ("prefill", 99.0))
        for k, v in (("sum", s), ("count", n))) + "\n"


def finished(n: int) -> list:
    outs = []
    for i in range(n):
        outs.append(Outcome(i, 0.0, sent=0.0, frames=[(0.1, 4)], done=True))
    return outs


SPEC = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 20,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "vocab_size": 32768, "tie_word_embeddings": False, "dtype": "bfloat16"}


def a_run(with_phases: bool = True) -> dict:
    ragged = "%ragged_attention.8 = bf16[16,8,20,128]{3,2,1,0} custom-call(s32[4]{0} %x)"
    return {
        "worker_before": metrics(1.0, 1e6, with_phases),
        "worker_after": metrics(2.0, 5e6, with_phases),
        "gateway_before": gateway(10, 1.0), "gateway_after": gateway(210, 1.8),
        "outcomes": finished(204), "config": SPEC,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        # the capture saw 200 launches reading 4000 context tokens each
        "trace_counters": (metrics(1.0, 1.0e6, with_phases),
                           metrics(1.2, 1.8e6, with_phases)),
        "trace": {"programs": {"jit_verify_block_fn": {"seconds": 3.4, "count": 200},
                               "jit_mixed_chunk_fn": {"seconds": 1.0, "count": 10}},
                  "ops": {"jit_verify_block_fn#1659/ragged_attention.8": {
                              "seconds": 0.3, "count": 4000,
                              "program": "jit_verify_block_fn", "text": ragged},
                          "jit_mixed_chunk_fn#4722/ragged_attention.6": {
                              "seconds": 0.5, "count": 200,
                              "program": "jit_mixed_chunk_fn", "text": ragged}}},
    }


def read(name: str, run: dict):
    return harness.Cell("mistral7b.chat").reader(name).compute(run)


def test_runner_readers_on_a_hand_made_run():
    run = a_run()
    # 1000 launches; busy 25.0 s of which fetch 15.0
    assert read("runner.period_ms", run) == pytest.approx(25.0)
    assert read("runner.host_ms_per_step", run) == pytest.approx(10.0)
    assert read("runner.fetch_wait_pct", run) == pytest.approx(60.0)
    assert read("runner.ingest_ms_per_step", run) == pytest.approx(5.0)
    assert read("runner.draft_ms_per_step", run) == pytest.approx(0.9)
    assert read("runner.admit_ms_per_request", run) == pytest.approx(10.0)
    assert read("engine.admit_wait_mean_ms", run) == pytest.approx(2.5)
    # what the acceptance holds them to: period >= fetch + ingest, and the
    # fetch share and the host phases' share are the whole
    host_pct = 100.0 * read("runner.host_ms_per_step", run) / read("runner.period_ms", run)
    assert read("runner.fetch_wait_pct", run) + host_pct == pytest.approx(100.0)


def test_dispatch_reader_wants_nine_tenths_of_the_finished_requests():
    run = a_run()
    assert read("path.dispatch_mean_ms", run) == pytest.approx(4.0)   # 0.8 s over 200
    run["gateway_after"] = gateway(150, 1.8)                           # 140 of 204
    assert read("path.dispatch_mean_ms", run) is None


def test_roofline_readers_on_a_hand_made_run():
    run = a_run()
    kv = 4000 * 20 * 2 * 8 * 128 * 2           # tokens x layers x K,V x heads x dim x bf16
    assert phases.kv_bytes_per_launch(run) == pytest.approx(kv)
    weights = costs.step_weight_bytes(SPEC)
    assert read("step.verify_mem_mfu_pct", run) == pytest.approx(
        100.0 * ((weights + kv) / 819e9) / 0.017)
    # the chunk program's ragged calls are not the decode kernel's
    assert read("kernel.ragged_decode_roofline_pct", run) == pytest.approx(
        100.0 * (kv / 819e9) / (0.3 / 200))
    assert 0 < read("kernel.ragged_decode_roofline_pct", run) < 100
    # a CPU rehearsal has no roofline: nothing, not a KeyError from peaks.json
    run["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert read("step.verify_mem_mfu_pct", run) is None
    assert read("kernel.ragged_decode_roofline_pct", run) is None


def before_this_pr(run: dict) -> dict:
    """The two shares as their readers computed them before a chip's share
    existed (PR 23-25's formulas, kept here as the record)."""
    import re

    import readers

    spec = run["config"]
    ends = run["trace_counters"]
    launches = phases.between(*ends)[phases.LAUNCH][1]
    tokens = (harness.st.metric_sum(ends[1], phases.CTX_TOKENS)
              - harness.st.metric_sum(ends[0], phases.CTX_TOKENS))
    kv = tokens / launches * costs.kv_bytes_per_token(spec)
    secs, n = phases.verify_launches(run)
    ragged = sum(o["seconds"] for o in readers.ops(run, readers.RAGGED_OPS)
                 if re.search(readers.VERIFY_PROGRAMS, o["program"]))
    return {"step.verify_mem_mfu_pct":
            100.0 * ((costs.step_weight_bytes(spec) + kv) / 819e9) / (secs / n),
            "kernel.ragged_decode_roofline_pct": 100.0 * (kv / 819e9) / (ragged / n)}


def test_rooflines_take_one_chips_share():
    """One chip: bit-identical to what the readers gave before. ``tp:4``:
    a quarter of the bytes against the same (first chip's) times."""
    one = a_run()
    was = before_this_pr(one)
    for name, value in was.items():
        assert read(name, one) == value, name          # ==, not approx
    four = a_run()
    four["config"] = dict(SPEC, mesh="tp:4", chips=4)
    four["device"]["count"] = 4
    for name, value in was.items():
        assert read(name, four) == pytest.approx(value / 4, rel=1e-12), name
    assert phases.kv_bytes_per_launch(four) == phases.kv_bytes_per_launch(one) / 4
    # an axis the costs have no rule for: nothing, never a guess
    four["config"] = dict(SPEC, mesh="ep:4", chips=4)
    for name in was:
        assert read(name, four) is None, name
    assert phases.kv_bytes_per_launch(four) is None


NEW = ["runner.period_ms", "runner.host_ms_per_step", "runner.fetch_wait_pct",
       "runner.ingest_ms_per_step", "runner.draft_ms_per_step",
       "runner.admit_ms_per_request", "engine.admit_wait_mean_ms",
       "step.verify_mem_mfu_pct", "kernel.ragged_decode_roofline_pct"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reads_as_nothing(name):
    """The parent commit has no phase series and no context counter: the
    reader returns None (the metric is left out of the line), traced or not."""
    run = a_run(with_phases=False)
    assert read(name, run) is None
    run["trace_counters"], run["trace"] = None, {}
    assert read(name, run) is None


def test_idle_gaps_of_a_recorded_trace_carry_the_runners_phases(tmp_path):
    """Recorded here (CPU; the XLA operations of a CPU trace stand in for
    the device's line) through the program's own capture, Python tracer
    off, and its phase clock: the gaps between launches are the runner's
    idle_wait, and the launch itself lies inside gridllm.fetch."""
    import jax
    import jax.numpy as jnp

    from gridllm_tpu.obs.perf import PhaseClock, ProfilerCapture

    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    step(x).block_until_ready()
    prof = ProfilerCapture(base_dir=str(tmp_path))
    clock = PhaseClock("m", profiler=prof)
    info = prof.capture(60.0)
    assert info["python"] is False

    def runner():
        for gen in range(4):
            clock.mark("dispatch_verify", gen=gen, slots=1, ctx_tokens=3)
            y = step(x)
            clock.mark("fetch")
            y.block_until_ready()
            clock.mark("idle_wait")
            time.sleep(0.03)
        clock.pause()

    t = threading.Thread(target=runner, name="engine-m")
    t.start()
    t.join()
    prof.stop()
    planes = trace_reduce.read(trace_reduce.find_xplane(info["path"]))
    spans = [(s, e, n) for s, e, n in planes["host"] if "gridllm." in n]
    assert {n.split(":", 1)[1] for _, _, n in spans} >= {
        "gridllm.fetch", "gridllm.idle_wait"}
    r = trace_reduce.reduce(planes)
    top = r["breakdown"]["idle_gaps"][:3]
    assert [n.split(":", 1)[1] for n, _ in top] == ["gridllm.idle_wait"] * 3
    assert all(0.025 < s < 0.2 for _, s in top)
    assert not any("sleep" in n for n, _ in r["breakdown"]["idle_gaps"])
    # a gap inside a fetch is the fetch's (or an event nested in it)
    s, e, _ = next(x for x in spans if x[2].endswith("gridllm.fetch"))
    inside = [n for hs, he, n in planes["host"] if hs >= s and he <= e]
    assert trace_reduce.attribute((s + 1, e - 1), planes["host"]) in inside
    assert clock.counts["idle_wait"] == 4 and clock.seconds["idle_wait"] > 0.1
