"""Set-up by phase, read from the program's own compile log.

The program registers one listener on JAX's trace, lowering, compile and
cache-load events (`homebrewnlp_tpu.obs.compile_log`, installed by
`utils.enable_compilation_cache()`, which `run.py` calls before its first
device use) and keeps a record per event: `kind` (`trace`, `lower`,
`build`), `fun`, `t0`, `t1` on `time.perf_counter()` and, on a `build`,
`cache` (`hit`, `miss`, `unstored`).  This file cuts that log at the
window's open and reduces it to the six `setup_*` metrics:

    cut       records with `t1 <=` the earliest start in `run["spans"]`:
              `run.py` clears its spans when the window opens and both are
              on `perf_counter`.  The reference's and the comparison's
              builds come after the window and are cut.
    trace_s, lower_s          the union of that kind's intervals (an inner
                              jit's trace lies inside its caller's: a sum
                              would count it twice)
    compile_s, cache_load_s   summed `build` records by `cache`: `miss` and
                              `unstored` compiled, `hit` loaded
    programs                  the number of `build` records
    other_s   (window open - `installed_at`) - the union of every record:
              backend start, weights and transfers, the executed followed
              and warm-up updates, Python.  So `other_s` plus the union
              closes the interval exactly; where kinds overlap the four
              sums exceed the union by `overlap_s`, which is logged with
              the ten longest records and the six longest stretches that no
              record covers (`gaps`), named by their neighbours.

What lies before `installed_at` (the imports) is `run.py`'s `import_s` line.
Where the program has no compile log (a commit before PR 35), `phases` is
None and every reader built on it reports nothing.
"""
from __future__ import annotations

import sys
import typing


def log(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def program_log():
    """The program's installed compile log, or None where it has none."""
    try:
        from homebrewnlp_tpu.obs import compile_log
    except ImportError:
        return None
    installed = compile_log.LOG.installed_at is not None
    return compile_log.LOG if installed else None


def union_s(intervals: typing.Iterable[typing.Tuple[float, float]]) -> float:
    """Seconds covered by at least one of the `(t0, t1)` intervals."""
    covered, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            covered += t1 - max(t0, end)
            end = t1
    return covered


def partition(records, installed_at: float, window_open: float) -> dict:
    """The six numbers, and `overlap_s`, over the records that had ended by
    `window_open`; a record is anything with `kind`, `t0`, `t1`, `cache`."""
    kept = [r for r in records if r.t1 <= window_open]

    def spans_of(*kinds):
        return [(max(r.t0, installed_at), r.t1) for r in kept
                if r.kind in kinds]

    builds = [r for r in kept if r.kind == "build"]
    out = {
        "trace_s": union_s(spans_of("trace")),
        "lower_s": union_s(spans_of("lower")),
        "compile_s": sum((r.t1 - r.t0 for r in builds if r.cache != "hit"),
                         0.0),
        "cache_load_s": sum((r.t1 - r.t0 for r in builds
                             if r.cache == "hit"), 0.0),
        "programs": len(builds),
    }
    covered = union_s(spans_of("trace", "lower", "build"))
    out["other_s"] = (window_open - installed_at) - covered
    out["overlap_s"] = (out["trace_s"] + out["lower_s"] + out["compile_s"]
                        + out["cache_load_s"]) - covered
    return out


def gaps(records, installed_at: float, window_open: float, n: int = 6):
    """The `n` longest stretches between `installed_at` and `window_open`
    that none of the records, cut already, covers, and which together are
    `other_s`: `(seconds, offset from installed_at, what ended before it,
    what began after it)`."""
    found, end, before = [], installed_at, "installed_at"
    for r in sorted(records, key=lambda r: r.t0) + [None]:
        t0 = window_open if r is None else r.t0
        if t0 > end:
            after = "the window" if r is None else f"{r.kind} {r.fun}"
            found.append((t0 - end, end - installed_at, before, after))
        if r is not None and r.t1 > end:
            end, before = r.t1, f"{r.kind} {r.fun}"
    return sorted(found, reverse=True)[:n]


def phases(run: dict) -> typing.Optional[dict]:
    """`partition` of this run's set-up.  Kept on `run`, so that the six
    readers share one reading of the log."""
    if "setup_phases" not in run:
        run["setup_phases"] = None
        compile_log = program_log()
        starts = [start for _, start, _ in run.get("spans", ())]
        if compile_log is not None and starts:
            window_open = min(starts)
            records = compile_log.events(before=window_open)
            out = partition(records, compile_log.installed_at, window_open)
            run["setup_phases"] = out
            log("setup_phases " + " ".join(
                f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in out.items())
                + f" of {window_open - compile_log.installed_at:.3f}s from "
                "installed_at to the window's open")
            longest = sorted(records, key=lambda r: r.t0 - r.t1)[:10]
            log("setup_longest " + "; ".join(
                f"{r.kind} {r.fun} {r.t1 - r.t0:.3f}s"
                + (f" {r.cache}" if r.cache else "") for r in longest))
            log("setup_gaps " + "; ".join(
                f"{seconds:.3f}s at {offset:.3f}s after {before} before "
                f"{after}" for seconds, offset, before, after in gaps(
                    records, compile_log.installed_at, window_open)))
    return run["setup_phases"]


def read(run: dict, key: str):
    found = phases(run)
    return None if found is None else found[key]
