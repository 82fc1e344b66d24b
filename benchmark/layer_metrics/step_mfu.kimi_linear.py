"""`step_mfu.kimi_linear`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops_kimi_linear.train_step_flops`: from the configuration's sizes,
recomputation not counted, attention as triangles, the held experts at their
expected load), over the window's wall time and the peak of `peaks.json`.
`step_mfu` reads the mixer family's keys; a `benchmark` PR may fold the two."""
import flops_kimi_linear

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s or "linear_attn_config" not in run["model"]:
        return None
    need = flops_kimi_linear.train_step_flops(run["model"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
