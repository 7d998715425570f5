"""Of the experts the chip holds, the share a verify / decode launch's
live rows touch, over the window: ``gridllm_moe_experts_touched_total``
(experts with at least one live row, summed over layers and launches) over
experts x layers x launches (``_count{phase="dispatch_verify"}``). It is
the share of the held experts' bytes that ``moe.expert_mem_roofline_pct``
and ``step.verify_mem_mfu_pct`` charge a launch; lower means the
all-experts form, which reads every expert, stands further above it."""
import moe
import phases

NAME, UNIT, LAYER, MOVES = ("moe.experts_touched_pct", "%", "routed experts",
                            "itl_p95_ms")
CELLS = ["smallthinker21b.chat", "smallthinker21b.long_doc"]


def compute(run):
    s = moe.shapes(run["config"])
    launches = phases.window(run).get(phases.LAUNCH, (0.0, 0.0))[1]
    touched = moe.touched(run["worker_before"], run["worker_after"])
    if s is None or launches <= 0 or touched <= 0:
        return None
    return 100.0 * touched / (s[0] * run["config"]["num_hidden_layers"] * launches)
