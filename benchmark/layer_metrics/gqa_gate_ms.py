"""`gqa_gate_ms`: device time of the gate of the gated grouped-query layers
(nd sub-scope `gqa_/gate`: `gate_proj` from the layer's input, the float32
sigmoid, its product with the attention's result), every pass, per update.
Read by scope (`sub_scope_time.py`; the data file beside this one names it);
the time by block and pass goes to the log.  A program whose `gqa` has no
such scope gives no reading."""
import sub_scope_time

UNIT = "ms"


def read(run: dict):
    by_block = sub_scope_time.seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("gqa_gate_ms_per_update", by_block, steps)
    return 1e3 * sum(by_block.values()) / steps
