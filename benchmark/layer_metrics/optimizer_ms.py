"""`optimizer_ms`: device time of the optimizer's instructions per update
(`scope_time.py`; the scope is in the data file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
