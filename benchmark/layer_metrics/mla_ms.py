"""`mla_ms`: device time of the latent-attention mixers (nd layer scope mla_
under d<i>_1/block_), every pass, per update (`scope_time.py`; the scopes are
in the data file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
