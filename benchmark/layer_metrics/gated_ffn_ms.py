"""`gated_ffn_ms`: device time of the dense gated feed-forward (nd layer scope
gated_feed_forward_), every pass, per update (`scope_time.py`; the scopes are
in the data file beside this one)."""
import scope_time

UNIT = "ms"


def read(run: dict):
    return scope_time.ms_per_update(run, __file__)
