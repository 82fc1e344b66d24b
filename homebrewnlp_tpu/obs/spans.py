"""Thread-aware host span tracing -> Chrome trace-event JSON + jax mirror.

The async-dispatch loop (main.py) runs three concurrent host actors — the
train loop, the ``DeviceFeeder`` producer thread, and the deferred metric
drain — whose interleaving is invisible in ``metrics.jsonl``.  A
``SpanTracer`` records named, nested spans from any thread and exports them
as Chrome trace-event JSON (the ``{"traceEvents": [...]}`` format Perfetto
and ``chrome://tracing`` load directly): overlapping spans on one thread
nest visually, and each thread gets its own labelled track.

Every span is also mirrored into ``jax.profiler.TraceAnnotation`` so a
``--profile`` capture shows the SAME host spans aligned with XLA's device
timeline — one trace answers "was the device idle while the host did X".

Zero-overhead contract: the module-level ``span()`` / ``traced()`` helpers
consult the ambient tracer installed by ``obs.Obs.start()``; with no tracer
installed they return a shared no-op context manager (one global load + one
identity call), so instrumented code paths cost nothing when observability
is off and the synchronous parity path stays bit-identical.
"""
from __future__ import annotations

import collections
import functools
import json
import os
import threading
import time
import typing

from ..sync import make_lock


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self._ann = None

    def __enter__(self) -> "_Span":
        if self.tracer._mirror is not None:
            self._ann = self.tracer._mirror(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        self.tracer._opened(self.name)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        self.tracer._record(self.name, self._t0, t1, self.args, closes=True)
        return False


class SpanTracer:
    """Collects host spans; thread-safe; exports Chrome trace-event JSON.

    ``mirror_jax=True`` (default) wraps each span in a
    ``jax.profiler.TraceAnnotation`` — free when no profiler trace is
    active, and the host/device alignment story when one is.

    ``max_events`` bounds host memory on long runs: the buffer is a ring
    keeping the MOST RECENT spans (a post-mortem wants the window before
    the event, not the first hours), and the export notes how many were
    dropped.  ``phase_totals`` accumulates separately, so bench phase sums
    stay exact regardless of the ring."""

    def __init__(self, mirror_jax: bool = True, max_events: int = 1_000_000):
        self._lock = make_lock("obs.spans.SpanTracer._lock")
        # (name, t0, t1, tid, args) with t relative to tracer creation
        self._events: typing.Deque[tuple] = collections.deque(
            maxlen=max_events)
        self._recorded = 0
        self._totals: typing.Dict[str, float] = {}
        self._thread_names: typing.Dict[int, str] = {}
        # names of the live spans each thread is inside, outermost first:
        # what a hang report names (``open_spans``)
        self._open: typing.Dict[int, typing.List[str]] = {}
        # virtual tracks (serving lane timelines): negative synthetic tids,
        # allocated per track name, so they can never collide with a real
        # thread ident and sort ahead of the thread tracks in the viewer
        self._track_ids: typing.Dict[str, int] = {}
        self._epoch = time.perf_counter()
        self._wall_epoch = time.time()
        self._pid = os.getpid()
        self._mirror = None
        if mirror_jax:
            try:
                from jax.profiler import TraceAnnotation
                self._mirror = TraceAnnotation
            except Exception:
                self._mirror = None

    # -- recording -----------------------------------------------------------
    def span(self, name: str, **args) -> _Span:
        """Context manager recording one span on the calling thread."""
        return _Span(self, name, args)

    def trace(self, name: typing.Optional[str] = None):
        """Decorator form: ``@tracer.trace("checkpoint")``."""
        def deco(fn):
            span_name = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapped(*a, **kw):
                with self.span(span_name):
                    return fn(*a, **kw)
            return wrapped
        return deco

    def add(self, name: str, t0: float, t1: float,
            track: typing.Optional[str] = None, **args) -> None:
        """Record an already-measured span from explicit ``perf_counter``
        timestamps.  A request's phase trail (serve/slo.py) is stamped
        across three threads — handler, queue worker, JAX callback — and
        only assembled once the request finishes; this records each phase
        retroactively on the calling thread's track, which a live context
        manager cannot do.

        ``track`` places the span on a named VIRTUAL track instead of the
        calling thread's — the serving engine's per-lane occupancy
        timelines (docs/observability.md "Streaming and inter-token
        latency") are not threads, but each lane still deserves its own
        swimlane in the exported Chrome trace."""
        if t1 < t0:
            t0, t1 = t1, t0
        self._record(name, t0, t1, args, track=track)

    def _opened(self, name: str) -> None:
        th = threading.current_thread()
        with self._lock:
            self._thread_names[th.ident] = th.name
            self._open.setdefault(th.ident, []).append(name)

    def open_spans(self) -> typing.Dict[str, typing.List[str]]:
        """{thread name: names of the live spans it is inside, outermost
        first} for every thread inside one: where each actor stands now,
        which the closed spans of the ring cannot say."""
        with self._lock:
            return {self._thread_names.get(tid, str(tid)): list(stack)
                    for tid, stack in self._open.items() if stack}

    def _record(self, name: str, t0: float, t1: float, args: dict,
                track: typing.Optional[str] = None,
                closes: bool = False) -> None:
        with self._lock:
            if closes:
                # a span closed by another thread than opened it finds no
                # stack of its own; a server makes a thread a request, so
                # an empty one goes
                stack = self._open.get(threading.get_ident())
                if stack:
                    stack.pop()
                if not stack:
                    self._open.pop(threading.get_ident(), None)
            if track is not None:
                tid = self._track_ids.get(track)
                if tid is None:
                    tid = -(len(self._track_ids) + 1)
                    self._track_ids[track] = tid
                    self._thread_names[tid] = track
            else:
                th = threading.current_thread()
                tid = th.ident
                self._thread_names[tid] = th.name
            self._events.append((name, t0 - self._epoch, t1 - self._epoch,
                                 tid, args))
            self._recorded += 1
            self._totals[name] = self._totals.get(name, 0.0) + (t1 - t0)

    # -- export --------------------------------------------------------------
    def event_count(self) -> int:
        """Spans currently held in the ring (the rotation trigger)."""
        with self._lock:
            return len(self._events)

    def snapshot_events(self, limit: typing.Optional[int] = None
                        ) -> typing.List[dict]:
        """The most recent ``limit`` spans as JSON-ready dicts anchored to
        WALL-CLOCK seconds (``t0_s``/``t1_s``) — the flight recorder's
        bundle format, directly comparable across processes without the
        per-tracer perf_counter epoch."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
            wall = self._wall_epoch
        if limit is not None:
            events = events[-limit:]
        return [{"name": name,
                 "t0_s": round(wall + t0, 6),
                 "t1_s": round(wall + t1, 6),
                 "track": names.get(tid, str(tid)),
                 "args": {k: str(v) for k, v in args.items()}}
                for name, t0, t1, tid, args in events]

    def chrome_events(self) -> typing.List[dict]:
        """Chrome trace-event dicts: complete ('X') events plus thread/process
        name metadata ('M') events.  Timestamps are microseconds from tracer
        creation (Perfetto renders relative times; ``otherData`` carries the
        wall-clock anchor)."""
        with self._lock:
            events = list(self._events)
            names = dict(self._thread_names)
        out: typing.List[dict] = [
            {"ph": "M", "name": "process_name", "pid": self._pid, "tid": 0,
             "args": {"name": "homebrewnlp_tpu host"}}]
        for tid, tname in sorted(names.items()):
            out.append({"ph": "M", "name": "thread_name", "pid": self._pid,
                        "tid": tid, "args": {"name": tname}})
        for name, t0, t1, tid, args in events:
            ev = {"name": name, "ph": "X", "cat": "host",
                  "ts": round(t0 * 1e6, 3),
                  "dur": round((t1 - t0) * 1e6, 3),
                  "pid": self._pid, "tid": tid}
            if args:
                ev["args"] = {k: str(v) for k, v in args.items()}
            out.append(ev)
        return out

    def chrome_trace(self) -> dict:
        """The full Perfetto-loadable document as an in-memory dict —
        what :meth:`export` writes, also served live by the REST layer's
        ``GET /debugz/trace`` so ``graftload --trace-out`` can merge
        server spans without filesystem access to the server."""
        with self._lock:
            dropped = self._recorded - len(self._events)
        return {"traceEvents": self.chrome_events(),
                "displayTimeUnit": "ms",
                "otherData": {"wall_epoch": self._wall_epoch,
                              "pid": self._pid,
                              "dropped_events": dropped}}

    def export(self, path: str) -> str:
        """Write the Perfetto-loadable trace JSON; returns the path."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def rotate(self, path: str) -> str:
        """Export the current ring to ``path`` and CLEAR it (track/thread
        names and phase totals survive, so later segments keep their
        swimlane labels and bench sums stay exact).  The serving engine
        rotates whenever the ring fills, so a crash loses at most one
        ring of spans instead of the whole trace."""
        out = self.export(path)
        with self._lock:
            self._events.clear()
            # the exported spans were persisted, not dropped: reset the
            # drop accounting so later segments report only real ring loss
            self._recorded = 0
        return out

    def phase_totals(self) -> typing.Dict[str, float]:
        """Total seconds per span name — the flat per-phase breakdown bench.py
        embeds in its JSON line.  Accumulated at record time (exact even
        when the event ring has dropped spans); nested spans double-count
        into their parent by design (each name answers 'how long was X
        open')."""
        with self._lock:
            return {k: self._totals[k] for k in sorted(self._totals)}


# -- ambient tracer ----------------------------------------------------------
# Installed by obs.Obs.start(); consulted per call so long-lived objects
# (DeviceFeeder, AsyncMetricWriter, the REST handler) need no plumbing.
_TRACER: typing.Optional[SpanTracer] = None


def set_tracer(tracer: typing.Optional[SpanTracer]
               ) -> typing.Optional[SpanTracer]:
    """Install (or clear, with None) the process-ambient tracer."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def get_tracer() -> typing.Optional[SpanTracer]:
    return _TRACER


def span(name: str, **args):
    """Span on the ambient tracer; shared no-op when tracing is off."""
    t = _TRACER
    if t is None:
        return NULL_SPAN
    return t.span(name, **args)


def add(name: str, t0: float, t1: float,
        track: typing.Optional[str] = None, **args) -> None:
    """Retroactive span on the ambient tracer; no-op when tracing is off."""
    t = _TRACER
    if t is not None:
        t.add(name, t0, t1, track=track, **args)


def traced(name: str):
    """Decorator on the ambient tracer (resolved per CALL, so functions
    decorated at import time still trace once a tracer is installed)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with span(name):
                return fn(*a, **kw)
        return wrapped
    return deco
