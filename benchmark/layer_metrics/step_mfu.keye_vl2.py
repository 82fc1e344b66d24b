"""`step_mfu.keye_vl2`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops_keye_vl2.train_step_flops`: from the configuration's sizes,
recomputation not counted, attention over the kept pairs only, the
indexer's scores over the triangle, the held experts at their expected
load), over the window's wall time and the peak of `peaks.json`.  A
configuration without `sa_config` gives no reading."""
import flops_keye_vl2

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s or "sa_config" not in run["model"]:
        return None
    need = flops_keye_vl2.train_step_flops(run["model"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
