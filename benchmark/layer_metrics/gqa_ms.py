"""`gqa_ms`: device time of the grouped-query attention mixers (nd layer scope
gqa_ under d<i>_<c>/block_: projections, rotation, the attention proper,
output), every pass, per update (`scope_time.py`; the scopes are in the data
file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
