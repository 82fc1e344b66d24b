"""`moe_ms`: device time of the routed expert layers (nd layer scope routed_moe_:
router, dispatch, experts, shared, combine), every pass, per update
(`scope_time.py`; the scopes are in the data file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
