"""`dsa_indexer_ms`: device time of the learned sparse attention's indexer
outside the selection: sub-scopes `gqa_/indexer` (projections, key norm,
rotation, their gradients) and `gqa_/indexer_loss` (the indexer's KL loss
and its gradient, one kernel), every pass, per update.  Read by scope (the
data file beside this one names both; `sub_scope_time.py` reads one a
call); the time by block and pass goes to the log.  A program whose `gqa`
has neither scope gives no reading."""
import json
import os

import scope_time
import sub_scope_time

UNIT = "ms"


def seconds_by_block(run: dict, reader_file: str):
    """`{"<block>/<pass>": seconds}` of the events under any of the
    sub-scopes that the data file beside `reader_file` lists, as
    `sub_scope_time.seconds_by_block` reads one."""
    profile = scope_time.program_profile()
    names = scope_time.trace_op_names() if profile is not None else None
    if not run.get("ops") or not names:
        return None
    with open(os.path.splitext(reader_file)[0] + ".json") as f:
        spec = json.load(f)
    under = [f"/{spec['layer']}_/{scope}" for scope in spec["scopes"]]
    by_block: dict = {}
    for name, _, duration in run["ops"]:
        op_name = names.get(name, "")
        if not any(u + "/" in op_name or op_name.endswith(u) for u in under):
            continue
        pass_, block, layer = profile.step_scope(op_name)
        if layer == spec["layer"]:
            key = f"{block}/{pass_}"
            by_block[key] = by_block.get(key, 0.0) + duration / 1e9
    return by_block or None


def read(run: dict):
    by_block = seconds_by_block(run, __file__)
    steps = run["result"]["steps"]
    if not by_block or not steps:
        return None
    sub_scope_time.log_ms_per_update("dsa_indexer_ms_per_update", by_block,
                                     steps)
    return 1e3 * sum(by_block.values()) / steps
