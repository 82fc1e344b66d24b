"""What JAX built, when, and from where: one listener on ``jax.monitoring``.

JAX stamps every build of a program itself: tracing a jitted function to a
jaxpr, lowering the jaxpr to an MLIR module, and the backend's compile, which
with the persistent cache on is a load of the stored executable or a compile
and a store.  A second call of a compiled function emits nothing, so a steady
update costs what it cost without the listener.  ``install()`` (called by
``utils.enable_compilation_cache``, the site every entry point passes before
its first device use) registers one duration listener and one event listener
and keeps a record per build step:

    kind    ``trace`` | ``lower`` | ``build``
    fun     JAX's ``fun_name`` (``step_fn``, ``jit(step_fn)``)
    t0, t1  on ``time.perf_counter()``, the clock of ``SpanTracer``; ``t1``
            is when the listener ran, ``t0 = t1 - duration``
    tid     the thread that built
    cache   on a ``build``: ``hit`` (read from the persistent cache),
            ``miss`` (compiled and stored), ``unstored`` (compiled and not
            kept: quicker than ``jax_persistent_cache_min_compile_time_secs``,
            or the cache is off)
    load_s  on a ``hit``: JAX's ``cache_retrieval_time_sec``
    again   on a ``build``: this process built a ``fun`` of that name before

JAX stamps a ``trace`` for every call of a jitted function inside a trace,
also of one it has traced before, which is a cache lookup of microseconds:
tracing the flagship's update makes 20,000 of them.  A ``trace`` shorter
than ``MIN_TRACE_S`` is counted in the registry and not kept; nearly all of
them lie inside their caller's record anyway.

The same numbers go to the registry (``hbnlp_jax_*``, ``hbnlp_recompiles_
total{fun}``; and ``hbnlp_attention_path_total{path}``, the walk of each
causal-attention call, which ``ops/block_attention.py`` emits as it is
traced) and, with an ambient tracer, into ``trace.json`` as retroactive
``jax/<kind>`` spans on the building thread's track, where they nest by
containment under the program span that caused them (docs/observability.md
"Set-up and compiles").
"""
from __future__ import annotations

import collections
import threading
import time
import typing

from . import spans
from .registry import REGISTRY, MetricsRegistry
from ..sync import make_lock

KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
         "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
         "/jax/core/compile/backend_compile_duration": "build"}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: ``ops/block_attention.py::WALK_EVENT``: a causal-attention call traced,
#: with the walk it takes as ``path``
WALK_EVENT = "/hbnlp/attention/walk"
MAX_RECORDS = 4096
MIN_TRACE_S = 1e-4


class Record(typing.NamedTuple):
    kind: str
    fun: str
    t0: float
    t1: float
    tid: int
    cache: typing.Optional[str] = None
    load_s: typing.Optional[float] = None
    again: bool = False


class CompileLog:
    """The two listeners and what they keep.  Any thread may build: the
    records go by ``deque.append``, a build's cache events wait in a
    ``threading.local``, and the lock covers what a ``build`` alone
    touches, the names built so far and their count."""

    def __init__(self, registry: MetricsRegistry = REGISTRY,
                 max_records: int = MAX_RECORDS):
        self.installed_at: typing.Optional[float] = None
        #: builds of a ``fun`` built before, so far (the loop polls it)
        self.recompiles = 0
        self._records: typing.Deque[Record] = collections.deque(
            maxlen=max_records)
        self._built: typing.Set[str] = set()
        self._lock = make_lock("obs.compile_log.CompileLog._lock")
        # the cache events of the build a thread is inside: they fire
        # before the ``backend_compile_duration`` that closes it
        self._inside = threading.local()
        self._seconds = {
            "trace": registry.counter(
                "hbnlp_jax_trace_seconds_total",
                "seconds tracing functions to jaxprs, summed by function "
                "(an inner jit counts again in its caller's)"),
            "lower": registry.counter(
                "hbnlp_jax_lower_seconds_total",
                "seconds lowering jaxprs to MLIR modules")}
        self._build_seconds = registry.counter(
            "hbnlp_jax_build_seconds_total",
            "seconds in the backend's compile-or-load, by what the "
            "persistent cache did", ("cache",))
        self._builds = registry.counter(
            "hbnlp_jax_builds_total",
            "programs built, by what the persistent cache did", ("cache",))
        self._recompiles = registry.counter(
            "hbnlp_recompiles_total",
            "builds of a function this process had built before", ("fun",))
        self._walks = registry.counter(
            "hbnlp_attention_path_total",
            "causal attention calls traced, by the walk they take "
            "(ops/block_attention.py::walk)", ("path",))

    def on_event(self, event: str, **kwargs) -> None:
        if event == WALK_EVENT:
            self._walks.labels(path=kwargs["path"]).inc()
            return
        cache = CACHE_EVENTS.get(event)
        if cache is not None:
            self._inside.cache = cache
            self._inside.at = time.perf_counter()

    def _built_before(self, fun: str) -> bool:
        with self._lock:
            again = fun in self._built
            # not ``.add``: graftsync resolves a call by its name, and
            # ``SpanTracer.add`` takes a lock
            self._built |= {fun}
            self.recompiles += again
        return again

    def _cache_of_build(self, t0: float):
        """(``cache``, ``load_s``) of the build this thread is closing,
        from the cache events that fired since it began at ``t0``; one from
        before ``t0`` belongs to a build that raised."""
        inside, cache, load_s = self._inside, "unstored", None
        if getattr(inside, "at", t0) > t0:
            cache = inside.cache
            if cache == "hit":
                load_s = getattr(inside, "load_s", None)
        inside.__dict__.clear()
        return cache, load_s

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = KINDS.get(event)
        if kind is None:
            if event == RETRIEVAL_EVENT:
                self._inside.load_s = seconds
            return
        t1 = time.perf_counter()
        t0 = t1 - seconds
        fun = str(kw.get("fun_name", "?"))
        cache = load_s = None
        again = False
        if kind != "build":
            self._seconds[kind].inc(seconds)
            if kind == "trace" and seconds < MIN_TRACE_S:
                return
        else:
            cache, load_s = self._cache_of_build(t0)
            again = self._built_before(fun)
            self._build_seconds.labels(cache=cache).inc(seconds)
            self._builds.labels(cache=cache).inc()
            if again:
                self._recompiles.labels(fun=fun).inc()
        self._records.append(Record(kind, fun, t0, t1, threading.get_ident(),
                                    cache, load_s, again))
        spans.add("jax/" + kind, t0, t1, fun=fun,
                  **({} if cache is None else {"cache": cache}))

    def events(self, before: typing.Optional[float] = None
               ) -> typing.List[Record]:
        """A copy of the records, oldest first; with ``before`` those that
        had ended by then."""
        records = list(self._records)
        if before is not None:
            records = [r for r in records if r.t1 <= before]
        return records

    def rebuilt(self, after: float) -> typing.List[Record]:
        """The builds since ``after`` of a ``fun`` built before."""
        return [r for r in list(self._records) if r.again and r.t1 > after]

    def last(self) -> typing.Optional[Record]:
        return self._records[-1] if self._records else None


#: the process's log: ``jax.monitoring`` is process-wide, so is its reader
LOG = CompileLog()
events = LOG.events


def install() -> CompileLog:
    """Register ``LOG``'s listeners with ``jax.monitoring``, once a process
    however often it is called, and stamp ``LOG.installed_at``."""
    from jax import monitoring
    if LOG.installed_at is None:
        LOG.installed_at = time.perf_counter()
        monitoring.register_event_duration_secs_listener(LOG.on_duration)
        monitoring.register_event_listener(LOG.on_event)
    return LOG


def describe(record: Record) -> str:
    """One record in a line, for the loop's recompile lines and the
    watchdog's report."""
    text = f"{record.kind} of {record.fun} ({record.t1 - record.t0:.3f}s"
    if record.cache is not None:
        text += f", cache {record.cache}"
    return text + ")"
