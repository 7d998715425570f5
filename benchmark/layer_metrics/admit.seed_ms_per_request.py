"""The ``dispatch_prefill`` phase's stage ``seed`` an admission: the
sampler row's program, a state restore and one ``window_seed`` launch for
every chunk of cached tokens, each a jitted call returning.
``gridllm_engine_stage_seconds{phase="dispatch_prefill",stage="seed"}``
``_sum`` over ``gridllm_engine_phase_seconds_count{phase="admit"}``
(admissions tried) of the window."""
import stages

NAME, UNIT, LAYER, MOVES = "admit.seed_ms_per_request", "ms", "engine admission", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "dispatch_prefill", "seed", per="admit")
