"""`dsa_indexer_roofline`: least time the chip could take for the learned
sparse attention's indexer scores in the traced window, over the device
time of the events under `gqa_/indexer` and `gqa_/select`.

Least time: per layer the larger of required flops over the bf16 peak and
boundary bytes over the HBM peak (`flops.least_seconds` of
`flops_keye_vl2.indexer`: the causal triangle at `indexer_num_heads` x
`indexer_head_dim`, forward only, since the selection takes no gradient,
and the indexer's three projections forward and their weights' gradient,
which `gqa_/indexer` holds), over all sparse layers, times the updates
finished; what remat computes again is not required work.  Device time: both scopes, every pass
(read by scope through `dsa_indexer_ms.seconds_by_block` and the data file
beside this one).  No events under either scope, or a configuration
without `sa_config`, no reading."""
import importlib.util
import os

import flops
import flops_keye_vl2
import sub_scope_time

UNIT = "%"


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metrics_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(run: dict):
    if "sa_config" not in run["model"]:
        return None
    by_block = _sibling("dsa_indexer_ms").seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("dsa_indexer_select_ms_per_update",
                                     by_block, steps)
    least = sum(flops.least_seconds(work, run["peak"])
                for work in flops_keye_vl2.indexer_passes(run["model"]))
    return 100.0 * least * steps / sum(by_block.values())
