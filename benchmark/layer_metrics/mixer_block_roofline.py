"""`mixer_block_roofline`: least time the chip could take for the mixer
blocks of the traced window, over the device time their events took.

Least time: per call the larger of required flops over the bf16 peak and
call-boundary bytes over the HBM peak (`flops.mixer_block`), forward and
backward, times depth, times the updates finished.  Device time: summed
durations of the events whose own instruction the data file beside this one
names (an operation that takes the kernel's result is not the kernel).  No
events, no reading."""
import json
import os

import flops
import trace_reduce as tr

UNIT = "%"


def read(run: dict):
    if not run.get("ops"):
        return None
    with open(os.path.splitext(__file__)[0] + ".json") as f:
        names = json.load(f)["events"]
    events = tr.matching(run["ops"], names)
    spent = sum(d for _, _, d in events) / 1e9
    if not spent:
        return None
    model = run["model"]
    work = flops.mixer_block(model, model["train_batch_size"])
    least = sum(flops.least_seconds(w, run["peak"]) for w in work.values())
    calls = model["depth"] * run["result"]["steps"]
    return 100.0 * least * calls / spent
