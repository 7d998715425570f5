"""The comparisons that have to FAIL, over the served records of several
runs in one process (the weights are made once): read on the chip when a
configuration's limits are set, never by a run of the benchmark.

    python benchmark/reference_controls.py --config <file> --records <json> [<json> ...]
                                           [--switch name ...] [--rehearse]

For each records file (a run's ``records.json``): the sound check
(``reference_check.check``, unedited), the same with one layer left out
(``reference_check.py --control``'s comparison), and one more for each
``--switch``: a keyword of the configuration's reference module's own
``logits`` that breaks one mechanism (``reference/smallthinker_f32.py``:
``rope_everywhere``, ``window`` (given as ``window=False``),
``router_post_attn``, and ``round_to=float8_e4m3fn``: every weight rounded
through the nearest precision below the configuration's own). Prints one ``CONTROL=<json>`` line a file and
``CONTROLS=<json>`` last: the sound runs' largest readings, each control's
smallest, and whether every control failed on every file.
"""

from __future__ import annotations

import argparse
import json
import sys


class Switched:
    """The reference with keywords fixed on its ``logits``."""

    def __init__(self, ref, **switch):
        self.ref, self.switch = ref, switch

    def logits(self, params, sizes, tokens, skip_layer=None):
        return self.ref.logits(params, sizes, tokens, skip_layer=skip_layer,
                               **self.switch)

    def margins(self, *args, **kw):
        return self.ref.margins(*args, **kw)


def parse_switch(text: str) -> tuple[str, dict]:
    """``rope_everywhere`` -> on; ``window=False`` -> off;
    ``round_to=float8_e4m3fn`` -> that word."""
    name, _, value = text.partition("=")
    words = {"": True, "true": True, "on": True, "false": False, "off": False}
    return text, {name: words.get(value.lower(), value)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--records", nargs="+", required=True)
    ap.add_argument("--switch", nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        spec = json.load(f)

    import jax
    import jax.numpy as jnp

    import launch_worker
    import reference_check as rc

    name = launch_worker.config_name(args.config)
    cfg = launch_worker.model_config(spec, name, args.rehearse)
    params = rc.init_params(cfg, getattr(jnp, spec["dtype"]), rc.build_mesh(spec))
    jax.block_until_ready(params)
    ref = rc.load_reference(spec["reference"]["module"])
    sizes = rc.reference_sizes(ref, cfg, spec, args.rehearse)
    limits = spec["reference"]
    variants = {"sound": (ref, None), "layer_skipped": (ref, cfg.num_layers // 2)}
    for text in args.switch:
        label, switch = parse_switch(text)
        variants[label] = (Switched(ref, **switch), None)
    rows = []
    for path in args.records:
        with open(path) as f:
            records = json.load(f)
        row = {"records": path}
        for label, (module, skip) in variants.items():
            got = rc.check(module, params, sizes, cfg.vocab_size, limits,
                           records, skip_layer=skip)
            row[label] = {
                "agrees": got["agrees"], "mean_shortfall": got["mean_shortfall"],
                "worst_shortfall": max((r["worst_shortfall"]
                                        for r in got["records"]), default=None),
                "worst_over_allowed": max((r["worst_shortfall"] / r["allowed_there"]
                                           for r in got["records"]), default=None),
                "max_abs_logit": max((r["max_abs_logit"]
                                      for r in got["records"]), default=None),
                "positions_over": sum(r["positions_over"] for r in got["records"])}
        rows.append(row)
        print("CONTROL=" + json.dumps(row), flush=True)
    controls = [v for v in variants if v != "sound"]
    out = {
        "files": len(rows), "platform": jax.devices()[0].platform,
        "sound_agrees_everywhere": all(r["sound"]["agrees"] for r in rows),
        "sound_largest": {k: max(r["sound"][k] for r in rows)
                          for k in ("worst_shortfall", "mean_shortfall")},
        "controls_smallest": {c: {k: min(r[c][k] for r in rows)
                                  for k in ("worst_shortfall", "mean_shortfall")}
                              for c in controls},
        "every_control_fails_everywhere": all(
            not r[c]["agrees"] for r in rows for c in controls)}
    print("CONTROLS=" + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
