"""`step_mfu`: the whole update's share of the chip's bf16 peak.

Flops the model requires for the updates finished in the traced window
(`flops.train_step_flops`: from the configuration's sizes, recomputation not
counted, the causal maps counted as triangles), over the window's wall time
and the peak of `peaks.json`."""
import flops

UNIT = "%"


def read(run: dict):
    steps, window_s = run["result"]["steps"], run["result"]["window_s"]
    if not steps or not window_s:
        return None
    need = flops.train_step_flops(run["model"], run["model"]["train_batch_size"])
    return 100.0 * need * steps / window_s / (
        run["peak"]["bf16_flops_per_s"] * run["chips"])
