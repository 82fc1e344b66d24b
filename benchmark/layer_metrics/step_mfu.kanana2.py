"""`step_mfu.kanana2`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops_kanana2.train_step_flops`: from the configuration's sizes,
recomputation not counted, attention over the causal triangle, the held
experts at their expected load), over the window's wall time and the peak of
`peaks.json`.  A configuration with other parts than `flops_kanana2.KINDS`
gives no reading."""
import flops_kanana2

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s or not set(flops_kanana2.kinds(
            run["model"])) <= set(flops_kanana2.KINDS):
        return None
    need = flops_kanana2.train_step_flops(run["model"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
