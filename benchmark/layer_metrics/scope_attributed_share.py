"""`scope_attributed_share`: device time of the events that resolved to a
layer of the model or to the optimizer, over the device's busy time in the
traced window (`scope_time.py`; the data file beside this one names what
does not count).  What is left is step-level glue and instructions that
carry no `op_name`."""
import scope_time

UNIT = "%"


def read(run: dict):
    seconds = scope_time.selected_seconds(run, __file__)
    busy = (run.get("device") or {}).get("busy_s")
    if not seconds or not busy:
        return None
    return 100.0 * seconds / busy
