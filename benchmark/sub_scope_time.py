"""Device time of the traced window under one sub-scope of a layer.

`scope_time.py` sums by `(pass_, layer)`; a reader of a part of a layer (the
gate of `gqa`, its attention proper) needs the instructions whose `op_name`
lies under `<layer>_/<scope>`, whatever runs there.  The data file beside
the reader names both.  Nothing to read (no trace, a program without
`step_scope`, no event under the scope): None.
"""
from __future__ import annotations

import json
import os
import typing

import scope_time


def seconds_by_block(run: dict, reader_file: str
                     ) -> typing.Optional[typing.Dict[str, float]]:
    """`{"<block>/<pass>": seconds}` of the events under the sub-scope that
    the data file beside `reader_file` names."""
    profile = scope_time.program_profile()
    if not run.get("ops") or profile is None:
        return None
    names = scope_time.trace_op_names()
    if not names:
        return None
    with open(os.path.splitext(reader_file)[0] + ".json") as f:
        spec = json.load(f)
    under = f"/{spec['layer']}_/{spec['scope']}"
    by_block: typing.Dict[str, float] = {}
    for name, _, duration in run["ops"]:
        op_name = names.get(name, "")
        if under + "/" not in op_name and not op_name.endswith(under):
            continue
        pass_, block, layer = profile.step_scope(op_name)
        if layer == spec["layer"]:
            key = f"{block}/{pass_}"
            by_block[key] = by_block.get(key, 0.0) + duration / 1e9
    return by_block or None


def log_ms_per_update(label: str, by_block: dict, steps: int) -> None:
    scope_time.log(label + " " + json.dumps(
        {k: round(1e3 * s / steps, 3) for k, s in sorted(by_block.items())}))
