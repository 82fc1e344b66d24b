"""`mla_attention_roofline`: least time the chip could take for the attention
proper of the latent-attention layers in the traced window, over the device
time its events took.

Least time: per layer and pass the larger of required flops over the bf16
peak and boundary bytes over the HBM peak (`flops.least_seconds` of
`flops_kanana2.attention`: the causal triangle at `qk_nope + qk_rope` and
`v_head_dim` a head), forward and backward, over all layers, times the
updates finished; a second forward that remat runs is not required work.
Device time: summed durations of the events whose `op_name` lies under
`mla_/attention` (the data file beside this one), in every pass, whatever
runs there (`sub_scope_time.py`): read by scope, not by a kernel's name.  No
events under the scope (a program without the sub-scope), or a
configuration with other parts than `flops_kanana2.KINDS`, no reading."""
import flops
import flops_kanana2
import sub_scope_time

UNIT = "%"


def read(run: dict):
    if not set(flops_kanana2.kinds(run["model"])) <= set(
            flops_kanana2.KINDS):
        return None
    by_block = sub_scope_time.seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("mla_attention_ms_per_update", by_block,
                                     steps)
    least = sum(flops.least_seconds(work, run["peak"])
                for work in flops_kanana2.attention_passes(run["model"]))
    return 100.0 * least * steps / sum(by_block.values())
