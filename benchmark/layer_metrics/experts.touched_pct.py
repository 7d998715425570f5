"""Of the routed experts the chip holds, the share a verify / decode
launch's live rows touch, over the window:
``gridllm_moe_experts_touched_total`` (experts with at least one live row,
summed over routed layers and launches) over experts x routed layers x
launches (``_count{phase="dispatch_verify"}``): the
``moe.experts_touched_pct`` of a deepseek_v2 configuration, whose leading
dense layers have no router. Lower means a launch could read fewer expert
bytes than ``experts.mem_roofline_pct`` charges it."""
import costs
import moe
import phases

NAME, UNIT, LAYER, MOVES = ("experts.touched_pct", "%", "routed experts",
                            "itl_p95_ms")
CELLS = ["dsv2lite.shared_doc"]


def compute(run):
    spec, count = run["config"], costs.of(run["config"])
    launches = phases.window(run).get(phases.LAUNCH, (0.0, 0.0))[1]
    touched = moe.touched(run["worker_before"], run["worker_after"])
    if (launches <= 0 or touched <= 0 or "n_routed_experts" not in spec
            or not hasattr(count, "layer_counts")):
        return None
    return 100.0 * touched / (
        spec["n_routed_experts"] * count.layer_counts(spec)[1] * launches)
