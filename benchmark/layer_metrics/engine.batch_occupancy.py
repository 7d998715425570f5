"""Mean active slots at a step's dispatch:
``gridllm_engine_batch_occupancy`` (a histogram), sum over count of its
change over the window."""
import readers

NAME, UNIT, LAYER, MOVES = "engine.batch_occupancy", "slots", "engine admission", "out_tok_s"


def compute(run):
    return readers.hist_mean(run, "worker", "gridllm_engine_batch_occupancy")
