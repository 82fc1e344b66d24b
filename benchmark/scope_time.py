"""Device time of the traced window by the program's model scopes.

The program mirrors every model scope into `jax.named_scope`, so each HLO
instruction's `metadata.op_name` says which layer of which block it belongs
to and in which pass of the update it runs.  The grammar of those names is
the program's own: `homebrewnlp_tpu.obs.profile.step_scope(op_name)` gives
`(pass_, block, layer)`.  This file joins it to the device trace:

    event name (the whole HLO instruction)
      -> op_name     the event metadata's `tf_op` stat in the `.xplane.pb`,
                     read by the program's `xplane_op_names`
      -> (pass_, layer)                               `step_scope`
      -> seconds     summed flat over `run["ops"]`, as `top_ops` does

A fusion counts for the scope of its own (root) instruction.  An event with
no `op_name` (async copies and slices that XLA put in, other executables'
instructions) counts under `("other", "other")`, so the table is a
partition of the window's summed device-operation time.

The trace is the one `run.py` wrote for this window (`_trace/` beside this
file; it is removed after the readers have run) and is read once a run.
Where the program has no `step_scope` (a commit before PR 25) or the trace
names no instruction, `seconds_by_scope` is None and every reader built on
it reports nothing.
"""
from __future__ import annotations

import json
import os
import sys
import time
import typing

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, "_trace")
OTHER = ("other", "other")

Key = typing.Tuple[str, str]  # pass_, layer


def log(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def program_profile():
    """The program's `obs.profile` module, or None where it has no
    `step_scope` yet."""
    try:
        from homebrewnlp_tpu.obs import profile
    except ImportError:
        return None
    return profile if hasattr(profile, "step_scope") else None


def trace_op_names(trace_dir: str = TRACE_DIR) -> typing.Optional[dict]:
    """`{event name: op_name}` of the newest trace under `trace_dir`; None
    where there is no trace or no program to read it."""
    profile = program_profile()
    path = profile.find_xplane_file(trace_dir) if profile else None
    if not path:
        log("scope_time: no step_scope in the program or no trace: the "
            "per-scope metrics are left out")
        return None
    t0 = time.perf_counter()
    names = profile.xplane_op_names(path)
    log(f"scope_names_read_s={time.perf_counter() - t0:.3f} "
        f"instructions_with_op_name={len(names)}")
    return names


def reduce(events: typing.Sequence[typing.Tuple[str, float, float]],
           op_names: typing.Dict[str, str], step_scope
           ) -> typing.Optional[typing.Dict[Key, float]]:
    """Seconds by `(pass_, layer)` over `(name, start_ns, duration_ns)`
    events; None when not one event resolved to a scope."""
    total: typing.Dict[Key, float] = {}
    for name, _, duration in events:
        key = OTHER
        if name in op_names:
            pass_, _, layer = step_scope(op_names[name])
            key = (pass_, layer)
        total[key] = total.get(key, 0.0) + duration
    if not any(key != OTHER for key in total):
        return None
    return {key: ns / 1e9 for key, ns in total.items()}


def seconds_by_scope(run: dict) -> typing.Optional[typing.Dict[Key, float]]:
    """The traced window's device seconds by `(pass_, layer)`.  Kept on
    `run`, so that the six readers share one reading of the trace."""
    if "scope_s" not in run:
        names = trace_op_names() if run.get("ops") else None
        run["scope_s"] = reduce(run["ops"], names,
                                program_profile().step_scope) if names else None
        if run["scope_s"]:
            steps = max(1, run["result"]["steps"])
            log("scope_ms_per_update " + json.dumps(
                {f"{layer}/{pass_}": round(1e3 * s / steps, 3) for
                 (pass_, layer), s in sorted(run["scope_s"].items())}))
    return run["scope_s"]


def selected(key: Key, spec: dict) -> bool:
    """Whether a reader's data file names this `(pass_, layer)`: `layers`
    and `passes` are lists or `"*"`, `except_layers` a list."""
    pass_, layer = key
    return ((spec.get("layers", "*") == "*" or layer in spec["layers"])
            and layer not in spec.get("except_layers", ())
            and (spec.get("passes", "*") == "*" or pass_ in spec["passes"]))


def selected_seconds(run: dict, reader_file: str) -> typing.Optional[float]:
    """Seconds of the scopes that the data file beside a reader names."""
    table = seconds_by_scope(run)
    if not table:
        return None
    with open(os.path.splitext(reader_file)[0] + ".json") as f:
        spec = json.load(f)
    return sum(s for key, s in table.items() if selected(key, spec))


def ms_per_update(run: dict, reader_file: str) -> typing.Optional[float]:
    seconds = selected_seconds(run, reader_file)
    steps = run["result"]["steps"]
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
