"""`map_ms`: device time of the map layers (`attention`, `activation`), every
pass, per update (`scope_time.py`; the scopes are in the data file beside this
one).  In a fused block this is the whole block: the Mosaic kernel, which
holds the block's norms too, and the layout copies and glue around it."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
