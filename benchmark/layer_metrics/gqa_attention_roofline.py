"""`gqa_attention_roofline`: least time the chip could take for the attention
proper of the grouped-query layers in the traced window, over the device
time its events took.

Least time: per layer and pass the larger of required flops over the bf16
peak and boundary bytes over the HBM peak (`flops.least_seconds` of
`flops_mellum2.attention`: the band on sliding layers, the triangle on full
ones, `k` and `v` once a K/V head), forward and backward, over all layers,
times the updates finished;
a second forward that remat runs is not required work.  Device time: summed
durations of the events whose `op_name` lies under the scope the data file
beside this one names, in every pass, whatever instruction runs there
(Mosaic kernels, unrolled tiles, the heads-major copies around them): read
by scope, not by a kernel's name.  The time by block and pass goes to the
log.  No events under the scope, no reading."""
import json
import os

import flops
import flops_mellum2
import scope_time

UNIT = "%"


def read(run: dict):
    profile = scope_time.program_profile()
    if not run.get("ops") or profile is None:
        return None
    names = scope_time.trace_op_names()
    if not names:
        return None
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        spec = json.load(f)
    under = f"/{spec['layer']}_/{spec['scope']}"
    by_block: dict = {}
    for name, _, duration in run["ops"]:
        op_name = names.get(name, "")
        if under + "/" not in op_name and not op_name.endswith(under):
            continue
        pass_, block, layer = profile.step_scope(op_name)
        if layer == spec["layer"]:
            key = f"{block}/{pass_}"
            by_block[key] = by_block.get(key, 0.0) + duration / 1e9
    spent, steps = sum(by_block.values()), run["result"]["steps"]
    if not spent or not steps:
        return None
    scope_time.log("gqa_attention_ms_per_update " + json.dumps(
        {k: round(1e3 * s / steps, 3) for k, s in sorted(by_block.items())}))
    least = sum(flops.least_seconds(work, run["peak"])
                for work in flops_mellum2.attention_passes(run["model"]))
    return 100.0 * least * steps / spent
