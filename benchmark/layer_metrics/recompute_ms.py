"""`recompute_ms`: device time spent running a block's forward again, per
update: the reversible backward's replay of each block and, under
`reversible_remat_blocks`, the remat recompute (`scope_time.py`; the passes
are in the data file beside this one).  It cuts across the layers' metrics."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
