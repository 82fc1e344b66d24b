"""`kda_ms`: device time of the gated delta-rule mixers (nd layer scope kda_
under d<i>_0/block_: conv, gates, chunk_scan, out), every pass, per update
(`scope_time.py`; the scopes are in the data file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
