"""`step_mfu.solar_open2`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops_solar_open2.train_step_flops`: from the configuration's sizes,
recomputation not counted, the delta rule's three state products a token,
attention over the triangle, the held experts at their expected load, all
over the heads held here), over the window's wall time and the peak of
`peaks.json`.  `step_mfu`, `step_mfu.kimi_linear` and `step_mfu.mellum2`
read other families' keys; a `benchmark` PR may fold the four."""
import flops_solar_open2

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s or "use_gqa_gate" not in run["model"]:
        return None
    need = flops_solar_open2.train_step_flops(run["model"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
