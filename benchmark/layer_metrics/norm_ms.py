"""`norm_ms`: device time of the norm layers' instructions, every pass, per update
(`scope_time.py`; the scopes are in the data file beside this one).  A norm
that XLA fused into a neighbour's instruction counts with the neighbour, and
a fused block's norms run inside its kernel: they are in `map_ms`."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
