"""The ``admit`` phase's stage ``tokenize`` an admission: the prompt's
text to ids on the runner thread (``_tokenize``), image expansion and the
cut to the context. The benchmark sends text, so this is the tokenizer
over the whole prompt. ``gridllm_engine_stage_seconds{phase="admit",
stage="tokenize"}``, ``_sum`` over ``_count`` of the window."""
import stages

NAME, UNIT, LAYER, MOVES = "admit.tokenize_ms_per_request", "ms", "engine admission", "itl_p95_ms"


def compute(run):
    return stages.stage_ms(run, "admit", "tokenize")
