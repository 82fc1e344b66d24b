"""`dsa_select_ms`: device time of the learned sparse attention's selection
(sub-scope `gqa_/select`: the indexer's scores of the causal triangle, one
block of rows at a time, and each row's top-k kept as a mask), every pass,
per update.  Read by scope (`sub_scope_time.py`; the data file beside this
one names it); the time by block and pass goes to the log.  A program whose
`gqa` has no such scope gives no reading."""
import sub_scope_time

UNIT = "ms"


def read(run: dict):
    by_block = sub_scope_time.seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("dsa_select_ms_per_update", by_block,
                                     steps)
    return 1e3 * sum(by_block.values()) / steps
