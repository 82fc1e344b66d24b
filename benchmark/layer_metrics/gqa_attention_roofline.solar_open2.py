"""`gqa_attention_roofline.solar_open2`: least time the chip could take for
the attention proper of the gated grouped-query layers without positions in
the traced window, over the device time its events took.

Least time: per layer and pass the larger of required flops over the bf16
peak and boundary bytes over the HBM peak (`flops.least_seconds` of
`flops_solar_open2.attention`: the triangle, `k` and `v` once a K/V head,
the heads held here), forward and backward, over all `gqa` layers, times the
updates finished; a second forward that remat runs is not required work.
Device time: summed durations of the events whose `op_name` lies under the
scope the data file beside this one names, in every pass, whatever
instruction runs there (`sub_scope_time.py`): read by scope, not by a
kernel's name.  The gate lies outside that scope and outside the work
counted.  The time by block and pass goes to the log.  No events under the
scope, or a configuration of another family, no reading."""
import flops
import flops_solar_open2
import sub_scope_time

UNIT = "%"


def read(run: dict):
    if "use_gqa_gate" not in run["model"]:
        return None
    by_block = sub_scope_time.seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("gqa_attention_ms_per_update", by_block,
                                     steps)
    least = sum(flops.least_seconds(work, run["peak"])
                for work in flops_solar_open2.attention_passes(run["model"]))
    return 100.0 * least * steps / sum(by_block.values())
