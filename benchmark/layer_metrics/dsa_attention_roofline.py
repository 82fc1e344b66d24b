"""`dsa_attention_roofline`: least time the chip could take for the learned
sparse attention's selected attention in the traced window, over the device
time its events took.

Least time: per layer and pass the larger of required flops over the bf16
peak and boundary bytes over the HBM peak (`flops.least_seconds` of
`flops_keye_vl2.attention`: the kept pairs only, `min(t + 1, topk)` keys a
row, `k` and `v` once a K/V head), forward and backward, over all sparse
layers, times the updates finished; a second forward that remat runs is not
required work.  Device time: summed durations of the events whose `op_name`
lies under `gqa_/attention` (the data file beside this one), in every pass,
whatever runs there (`sub_scope_time.py`): read by scope, not by a kernel's
name.  No events under the scope, or a configuration without `sa_config`,
no reading."""
import flops
import flops_keye_vl2
import sub_scope_time

UNIT = "%"


def read(run: dict):
    if "sa_config" not in run["model"]:
        return None
    by_block = sub_scope_time.seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("dsa_attention_ms_per_update", by_block,
                                     steps)
    least = sum(flops.least_seconds(work, run["peak"])
                for work in flops_keye_vl2.attention_passes(run["model"]))
    return 100.0 * least * steps / sum(by_block.values())
