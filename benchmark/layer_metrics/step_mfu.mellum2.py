"""`step_mfu.mellum2`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops_mellum2.train_step_flops`: from the configuration's sizes,
recomputation not counted, attention over the band on sliding layers and the
triangle on full ones, the held experts at their expected load), over the
window's wall time and the peak of `peaks.json`.  `step_mfu` and
`step_mfu.kimi_linear` read other families' keys; a `benchmark` PR may fold
the three."""
import flops_mellum2

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s or "rope_parameters" not in run["model"]:
        return None
    need = flops_mellum2.train_step_flops(run["model"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
