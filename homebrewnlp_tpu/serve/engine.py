"""Continuous-batching inference engine (docs/observability.md
"Continuous batching").

The reference serves one request at a time behind a single lock (its
Manager-queue bridge, /root/reference/src/rest_api.py), and our port kept
that shape: ``serve/interface.py::InterfaceWrapper`` serializes every
sampler call — the cost the serving-SLO layer's
``serialization_overhead_s`` was built to expose.  This module replaces it
with a real scheduler over the KV-cache sampler (Orca-style continuous /
in-flight batching, Yu et al. 2022; block-allocated KV accounting after
vLLM's PagedAttention, Kwon et al. 2023):

* one persistent DECODE loop over a fixed pool of ``serve_max_batch``
  lanes, each lane a row of the pooled per-layer KV caches
  (``infer/kv_cache.py``'s per-lane-position decode step);
* new requests are admitted BETWEEN decode steps — a finishing request's
  lane is re-prefilled while decode continues on the others;
* two separately compiled executables: ``prefill`` (one full-length
  forward writes a prompt's K/V into its lane) and ``decode`` (one
  incremental row per active lane, per-lane traced sampling knobs —
  one compilation serves every request mix);
* a :class:`~homebrewnlp_tpu.infer.kv_cache.BlockAllocator` prices
  admission in KV-pool blocks (``serve_kv_blocks`` x
  ``serve_block_tokens``): a request's whole footprint is taken up front
  and recycled on completion, a footprint that can NEVER fit is shed
  immediately (503 + Retry-After, like ``serve_queue_limit``);
* AOT executable serialization: both executables are compiled
  ahead-of-time and — when ``serve_aot_cache_dir`` is set — serialized to
  disk keyed by config hash + mesh + toolchain, so a second server start
  deserializes in seconds instead of re-paying the compile+warmup
  (BENCH_r05 measured ~135 s), which is what makes replica autoscaling
  plausible.

``serve_max_batch=1`` (the default) never constructs this engine: the
REST layer keeps the serialized ``InterfaceWrapper`` path byte-identical
to the pre-engine behavior (parity-tested).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import queue
import threading
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..data.feed import TEXT_AXES
from ..infer import kv_cache as kvc
from ..infer.sampler import _fire_first_token, _gumbel_argmax_lanes
from ..reliability import faults
from ..sync import make_condition
from . import slo
from .interface import (QueueDeadlineExceeded, RequestCancelled, _RowStream,
                        effective_truncation, tokenizer_for)

#: bump when the executable calling convention changes (AOT cache keying).
#: Donation does NOT affect this: AOT-persisted executables are exactly the
#: ones compiled WITHOUT donation (serialize_executable cannot round-trip
#: input-output aliasing — see jit_executables), so the serialized calling
#: convention is unchanged and existing caches stay valid.
#: 2: the rng carry became a [n_lanes] key array (per-lane streams seeded
#: by fold_in(request id) — :func:`lane_key`) instead of one shared key.
#: 3: chunked prefill added a third executable (:func:`prefill_chunk_body`,
#: persisted as ``prefill_chunk-<key>.jaxexec`` when
#: ``serve_prefill_chunk_tokens > 0``) — the cache-hit contract now spans
#: all executables the knobs require, so pre-chunk caches must not
#: half-hit.
AOT_FORMAT = 3

#: donated argument positions of the jitted executables (relative to the
#: bound callables :func:`jit_executables` builds).  The pooled KV caches,
#: token pool, per-lane positions and the rng carry are pure step state:
#: without donation they round-trip as ordinary jit args and the device
#: pays a FULL POOL COPY per decode step.  The ``donation`` graph rule
#: audits these against the abstract serving traces (analysis), so a
#: dropped donate_argnums fails graftcheck before it doubles serving HBM.
DECODE_DONATE_ARGNUMS = (1, 2, 3, 10)  # caches, toks, pos, rng
PREFILL_DONATE_ARGNUMS = (1, 2)  # caches, toks
PREFILL_CHUNK_DONATE_ARGNUMS = (1, 2)  # caches, toks
#: human names for the donated positions above, keyed per executable so
#: the donation audit's messages stay in lockstep with the signatures —
#: update these tables together when reordering body arguments
DECODE_DONATE_ARG_NAMES = {1: "pooled KV caches", 2: "token pool",
                           3: "lane positions", 10: "rng carry"}
PREFILL_DONATE_ARG_NAMES = {1: "pooled KV caches", 2: "token pool"}
PREFILL_CHUNK_DONATE_ARG_NAMES = {1: "pooled KV caches", 2: "token pool"}


def lane_key(seed: int, rid: int) -> jax.Array:
    """The decode RNG stream for one admitted request: the run seed folded
    with the request id.  A pure function of ``(seed, rid)`` — never of
    lane index or admission order — so a request's sampled tokens are
    reproducible under ANY interleaving, and lane 0 parity-pins against
    the serialized sampler called with this same key
    (tests/serve_engine_test.py)."""
    return jax.random.fold_in(jax.random.key(seed), rid)


def decode_body(cfg: Config, rows: int, n_lanes: int,
                first_token_cb: typing.Optional[typing.Callable],
                params, caches, toks, pos, active, end_row,
                first_gen, temps, ks, ps, rng, tags):
    """One continuous-batching decode step: every ACTIVE lane decodes the
    row at its own position, samples under its own traced knobs, and
    writes the sampled row at position+1; inactive lanes carry through
    untouched.  Mirrors the serialized cached sampler's body
    (infer/kv_cache.py) with per-lane positions.  Module-level (bound via
    ``functools.partial``) so the static donation audit traces the exact
    function the engine compiles.

    ``rng`` is a [n_lanes] key array — one stream per lane, seeded at
    admission from :func:`lane_key`.  A lane's carry advances only on
    steps it actually decodes, so the stream is a pure function of
    (seed, rid, tokens generated so far): idle steps between admissions
    cannot shift a request's samples."""
    # the same carry/sub discipline as the serialized sampler's body
    # (``key, sub = split(key)``), vmapped over lanes
    pair = jax.vmap(jax.random.split)(rng)
    advanced, subs = pair[:, 0], pair[:, 1]
    row = jnp.take_along_axis(toks, pos[:, None, None], axis=1)
    logits, caches = kvc._decode_logits(cfg, params, row, pos, caches,
                                        rows, TEXT_AXES)
    sampled = _gumbel_argmax_lanes(logits, temps, subs, ks, ps)
    nxt = pos + 1
    write = active & (nxt < end_row) & (nxt < rows)
    tgt = jnp.minimum(nxt, rows - 1)
    cur = jnp.take_along_axis(toks, tgt[:, None, None], axis=1)
    new_row = jnp.where(write[:, None, None],
                        sampled.astype(toks.dtype), cur)
    row_at = (jnp.arange(rows)[None, :] == tgt[:, None])[:, :, None]
    toks = jnp.where(row_at, new_row, toks)
    if first_token_cb is not None:
        # per-lane TTFT: n_lanes is static, so this unrolls to one gated
        # callback per lane — each fires at most once per request (its
        # first generated row), tagged with that lane's request id
        for b in range(n_lanes):
            _fire_first_token(first_token_cb, tags[b],
                              write[b] & (nxt[b] == first_gen[b]),
                              new_row[b])
    pos = jnp.where(active, nxt, pos)
    # advance only the lanes that decoded (typed keys: select on the raw
    # key data, then re-wrap under the same impl)
    data = jax.random.key_data(rng)
    keep = active.reshape((-1,) + (1,) * (data.ndim - 1))
    rng = jax.random.wrap_key_data(
        jnp.where(keep, jax.random.key_data(advanced), data))
    return caches, toks, pos, rng, logits


def prefill_body(cfg: Config, rows: int,
                 params, caches, toks, prompt, lane, prompt_rows):
    """Prefill one request into lane ``lane``: a single full-length
    forward writes every prompt position's K/V at once (batch of 1,
    scalar position 0 — the serialized sampler's prefill), then the lane
    rows of every pooled cache and the token pool are overwritten (both
    donated — the update happens in the pool's own buffers).  An empty
    prompt skips the forward; its lane decodes from scratch."""
    lane0 = {k: tuple(jnp.zeros((1,) + v.shape[1:], v.dtype) for v in kv)
             for k, kv in caches.items()}
    filled = jax.lax.cond(
        prompt_rows > 0,
        lambda c: kvc._decode_logits(cfg, params, prompt, jnp.int32(0),
                                     c, rows, TEXT_AXES)[1],
        lambda c: c, lane0)
    out = {}
    for name, kv in caches.items():
        out[name] = tuple(
            jax.lax.dynamic_update_slice(
                pool, jnp.asarray(one, pool.dtype),
                (lane,) + (0,) * (pool.ndim - 1))
            for pool, one in zip(kv, filled[name]))
    toks = jax.lax.dynamic_update_slice(toks, prompt, (lane, 0, 0))
    return out, toks


def prefill_chunk_rows(cfg: Config) -> int:
    """Decode rows per prefill chunk — ``serve_prefill_chunk_tokens`` in
    rows, clamped to the sequence; 0 = chunking off (the monolithic
    :func:`prefill_body` path, byte-identical graphs)."""
    tokens = int(getattr(cfg, "serve_prefill_chunk_tokens", 0) or 0)
    if tokens <= 0:
        return 0
    rows = cfg.sequence_length // cfg.token_patch_size
    return max(1, min(rows, tokens // cfg.token_patch_size))


def prefill_chunk_body(cfg: Config, rows: int, chunk_rows: int,
                       params, caches, toks, chunk, lane, start_row):
    """Prefill ONE chunk of a request into lane ``lane``: a forward over
    ``chunk_rows`` rows at scalar position ``start_row`` against the
    lane's own cache (the model's cached-attention path is exact for any
    row count at a scalar position — masked positions contribute exact
    0.0 to every full-length reduction, so N chunk forwards are bitwise
    the monolithic prefill), then ONLY the chunk's KV rows and token rows
    are scatter-written back into the (donated) pools at the lane's
    running position.  The scheduler dispatches at most one chunk between
    decode steps and never blocks on the result — prefill device time
    hides under decode device time (docs/observability.md "Streaming and
    inter-token latency")."""
    lane_caches = kvc.lane_view(caches, lane)
    filled = kvc._decode_logits(cfg, params, chunk, start_row,
                                lane_caches, rows, TEXT_AXES)[1]
    caches = kvc.write_lane_rows(caches, filled, lane, start_row, chunk_rows)
    toks = jax.lax.dynamic_update_slice(toks, chunk, (lane, start_row, 0))
    return caches, toks


def jit_executables(cfg: Config, rows: int, n_lanes: int,
                    first_token_cb: typing.Optional[
                        typing.Callable] = None,
                    donate: bool = True):
    """The engine's jitted (not yet compiled) step functions with their
    donation contract applied — shared by :class:`BatchEngine` and the
    ``donation`` graph rule's abstract serving trace.  Returns
    ``(decode, prefill, prefill_chunk)``; the third element is ``None``
    when ``serve_prefill_chunk_tokens`` is 0 (the monolithic path — the
    compiled graph set is byte-identical to the pre-chunking engine).

    ``donate=False`` is the AOT-cache compromise: this toolchain's
    ``serialize_executable`` does not round-trip input-output aliasing
    safely (a deserialized donated executable intermittently corrupts the
    pool — reproduced on CPU as non-repeatable decode outputs), so
    engines persisting to ``serve_aot_cache_dir`` compile WITHOUT
    donation, the same class of tradeoff as their host-side TTFT stamp
    (docs/observability.md "Continuous batching")."""
    import functools
    dec = functools.partial(decode_body, cfg, rows, n_lanes, first_token_cb)
    pre = functools.partial(prefill_body, cfg, rows)
    chunk_rows = prefill_chunk_rows(cfg)
    chk = (functools.partial(prefill_chunk_body, cfg, rows, chunk_rows)
           if chunk_rows else None)
    if not donate:
        return jax.jit(dec), jax.jit(pre), (jax.jit(chk) if chk else None)
    return (jax.jit(dec, donate_argnums=DECODE_DONATE_ARGNUMS),
            jax.jit(pre, donate_argnums=PREFILL_DONATE_ARGNUMS),
            (jax.jit(chk, donate_argnums=PREFILL_CHUNK_DONATE_ARGNUMS)
             if chk else None))


def abstract_exec_args(cfg: Config, params_tree, rows: int, n_lanes: int):
    """Abstract (ShapeDtypeStruct) argument tuples for the decode,
    prefill and (when ``serve_prefill_chunk_tokens > 0``, else ``None``)
    prefill-chunk executables — ``params_tree`` may already be abstract
    (the static analysis path passes the traced param shapes)."""
    s = jax.ShapeDtypeStruct
    tree = jax.tree_util.tree_map(
        lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
        else s(jnp.shape(a), jnp.asarray(a).dtype), params_tree)
    caches = kvc.cache_shapes(cfg, tree, n_lanes, rows)
    lanes = (n_lanes,)
    common = (tree, caches, s((n_lanes, rows, cfg.token_patch_size),
                              jnp.int32))
    rng = jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                  n_lanes))
    decode = common + (s(lanes, jnp.int32), s(lanes, jnp.bool_),
                       s(lanes, jnp.int32), s(lanes, jnp.int32),
                       s(lanes, jnp.float32), s(lanes, jnp.int32),
                       s(lanes, jnp.float32), rng, s(lanes, jnp.int32))
    prefill = common + (s((1, rows, cfg.token_patch_size), jnp.int32),
                        s((), jnp.int32), s((), jnp.int32))
    chunk_rows = prefill_chunk_rows(cfg)
    chunk = (common + (s((1, chunk_rows, cfg.token_patch_size), jnp.int32),
                       s((), jnp.int32), s((), jnp.int32))
             if chunk_rows else None)
    return decode, prefill, chunk


def use_batch_engine(cfg: Config) -> bool:
    """Whether serving should run the continuous-batching scheduler:
    opted in (``serve_max_batch > 1``) and the config's whole layer stack
    decodes against a KV cache (``infer/kv_cache.py::cache_eligible``)."""
    return int(getattr(cfg, "serve_max_batch", 1)) > 1 and kvc.cache_eligible(cfg)


def aot_cache_key(cfg: Config, params: dict, n_lanes: int) -> str:
    """Executable identity for the AOT cache: full derived config hash
    (train/metrics.py::config_hash) + parameter tree structure + mesh
    (device platform/kind/count) + toolchain versions + the engine's
    calling-convention format.  Any drift produces a different key, so a
    stale cache entry is simply never read — invalidation is by keying,
    never by mutation."""
    from ..train.metrics import config_hash
    leaves = [f"{k}:{tuple(v.shape)}:{jnp.asarray(v).dtype}"
              for k, v in sorted(params.items())]
    import jaxlib
    dev = jax.devices()[0]
    doc = json.dumps({
        "config": config_hash(cfg),
        "params": hashlib.sha256("|".join(leaves).encode()).hexdigest()[:16],
        "lanes": int(n_lanes),
        "mesh": [dev.platform, dev.device_kind, jax.device_count()],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "format": AOT_FORMAT,
    }, sort_keys=True, default=str)
    return hashlib.sha256(doc.encode()).hexdigest()[:24]


def _aot_save(path: str, compiled) -> bool:
    """Best-effort serialize of a ``jax.stages.Compiled`` (atomic rename so
    a torn write is never read back as a cache hit)."""
    try:
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except Exception:  # noqa: BLE001 - AOT persistence is an optimization
        return False


def _aot_load(path: str):
    """Deserialize a cached executable; None on any failure (the caller
    falls back to a fresh compile — a corrupt cache entry costs nothing
    but the compile it failed to save)."""
    try:
        from jax.experimental import serialize_executable as se
        with open(path, "rb") as f:
            payload, in_tree, out_tree = pickle.loads(f.read())
        return se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception:  # noqa: BLE001
        return None


class _BatchRequest:
    """One admitted-or-queued completion: prompt/knobs, the 1-slot result
    queue, the ambient SLO record snapshotted at submit, the optional
    streaming ``sink`` (token chunks + ``None`` sentinel, delivered while
    the lane decodes), and the cancellation event the queue-deadline
    protocol honors while the request is still QUEUED (an admitted request
    always finishes)."""

    __slots__ = ("rid", "prompt", "temperature", "max_tokens", "top_k",
                 "top_p", "rec", "out", "t_enq", "cancelled", "admitted",
                 "end", "end_row", "first_gen", "prompt_rows", "tag",
                 "sink", "rstream", "t_admitted",
                 # KV usage accounting: blocks the allocator granted and
                 # the wall instant it granted them — every free site
                 # integrates blocks x held-wall onto the SLO record
                 "n_blocks", "t_alloc",
                 # chunked-prefill state machine: the padded [1, rows,
                 # patch] token layout chunks are sliced from, the next
                 # chunk's start row, and the rows chunks must cover
                 # before the lane arms for decode
                 "padded", "next_chunk_row", "prefill_rows")

    def __init__(self, rid: int, prompt, temperature, max_tokens,
                 top_k, top_p, rec, sink=None):
        self.rid = rid
        self.prompt = list(prompt)
        self.temperature = temperature
        self.max_tokens = max_tokens
        self.top_k = top_k
        self.top_p = top_p
        self.rec = rec
        self.out: "queue.Queue[tuple]" = queue.Queue(1)
        self.t_enq = time.monotonic()
        self.cancelled = threading.Event()
        self.admitted = threading.Event()
        self.sink = sink
        self.rstream: typing.Optional[_RowStream] = None
        self.t_admitted: typing.Optional[float] = None
        self.n_blocks = 0
        self.t_alloc: typing.Optional[float] = None
        self.padded: typing.Optional[np.ndarray] = None
        self.next_chunk_row = 0
        self.prefill_rows = 0


class BatchEngine:
    """The scheduler: owns the pooled device state (per-layer KV caches
    ``[serve_max_batch, seq_rows, ...]``, the token pool, per-lane
    positions), the AOT executables (decode, prefill, and — when
    ``serve_prefill_chunk_tokens > 0`` — prefill-chunk), and one worker
    thread running admit -> prefill-chunk -> decode-step -> complete
    forever.

    ``first_token_callback`` is the serving TTFT hook (host
    ``(tag, token)``): the decode step fires it per lane at that lane's
    first generated row, carrying the request id its SLO record supplied —
    the traced-tag design (serve/slo.py) already supports many in-flight
    requests on one compilation."""

    def __init__(self, cfg: Config, params: dict,
                 first_token_callback: typing.Optional[
                     typing.Callable] = None):
        if not kvc.cache_eligible(cfg):
            raise ValueError(
                "continuous batching needs a KV-cache-eligible config "
                "(every sequence mixer an attention layer); this one keeps "
                "the serialized rebuild path")
        from ..models import pipeline_params_stacked, unstack_pipeline_params
        if pipeline_params_stacked(cfg, params):
            params = unstack_pipeline_params(cfg, params)
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer_for(cfg)
        self._first_token_cb = first_token_callback
        # TTFT source: the in-graph tagged callback serves the default
        # path, but a host callback is a PyCapsule the AOT pickler cannot
        # serialize — with ``serve_aot_cache_dir`` set the decode
        # executable is built callback-free and TTFT is stamped HOST-side
        # at the step boundary instead (the loop syncs every step, so the
        # stamp is one decode step coarse; docs/observability.md
        # "Continuous batching")
        self._graph_ttft = (first_token_callback is not None
                            and not getattr(cfg, "serve_aot_cache_dir", ""))
        self.patch = cfg.token_patch_size
        self.rows = cfg.sequence_length // self.patch
        self.n_lanes = int(cfg.serve_max_batch)
        self._chunk_rows = prefill_chunk_rows(cfg)
        self.allocator = kvc.BlockAllocator(
            kvc.pool_blocks(cfg), kvc.block_rows(cfg) * self.patch)
        # cold-start accounting (bench.py serving row: cold_start_s =
        # compile_s OR aot_reload_s + warmup)
        self.compile_s: typing.Optional[float] = None
        self.aot_reload_s: typing.Optional[float] = None
        self.aot_cache_hit: typing.Optional[bool] = None
        self._build_executables()
        # device state (pooled): lanes hold stale data between occupants by
        # design — decode rewrites each row before any query can see it
        # causally, so recycling never needs a zeroing pass (pinned by the
        # slot-reuse parity test)
        self._caches = kvc.init_caches(cfg, params, self.n_lanes, self.rows)
        self._toks = jnp.zeros((self.n_lanes, self.rows, self.patch),
                               jnp.int32)
        self._pos = jnp.zeros((self.n_lanes,), jnp.int32)
        # per-lane RNG carries; every admission overwrites its lane with
        # lane_key(seed, rid), so these initial streams never sample
        self._rngs = jax.random.split(jax.random.key(cfg.data_seed),
                                      self.n_lanes)
        # host mirrors (the scheduler thread is the only writer)
        self._pos_h = np.zeros(self.n_lanes, np.int32)
        self._end_row = np.zeros(self.n_lanes, np.int32)
        self._first_gen = np.zeros(self.n_lanes, np.int32)
        self._temps = np.zeros(self.n_lanes, np.float32)
        self._ks = np.zeros(self.n_lanes, np.int32)
        self._ps = np.ones(self.n_lanes, np.float32)
        self._tags = np.zeros(self.n_lanes, np.int32)
        self._logits = None  # last decode step's logits (tests/debug)
        self._lane_req: typing.List[typing.Optional[_BatchRequest]] = (
            [None] * self.n_lanes)
        # lanes mid-chunked-prefill, in admission order: the head lane
        # receives at most ONE chunk per loop iteration (between decode
        # steps), then arms for decode once its chunks cover the prompt
        self._prefill_fifo: typing.List[int] = []
        # scheduler plumbing
        self._cv = make_condition("serve.engine.BatchEngine._cv")
        self._queue: typing.List[_BatchRequest] = []
        self._pending = 0  # submitted, not yet admitted (queue_depth)
        self._closed = False
        self._batch_observer: typing.Optional[typing.Callable] = None
        self._step_observer: typing.Optional[typing.Callable] = None
        # decode-loop watchdog feed (slo.EngineHealth): the loop stamps
        # iteration start/end so /healthz can report a wedged scheduler
        self._health = None
        # serving trace (docs/observability.md "Streaming and inter-token
        # latency"): decode-loop phase spans on the scheduler thread's
        # track plus one virtual track per lane (prefilling/occupied with
        # request ids — idle shows as gaps), exported Chrome-trace JSON at
        # close(), alongside the training trace's format
        self.tracer = None
        self._trace_path = str(getattr(cfg, "serve_trace_path", "") or "")
        # flight_buffer_spans caps the ring AND arms rotation: when the
        # ring fills, the full segment rolls to <path>.NNN.json instead of
        # silently evicting — a crash loses at most one ring of spans
        self._trace_cap = int(getattr(cfg, "flight_buffer_spans", 0) or 0)
        self._trace_seq = 0
        self.trace_segments: typing.List[str] = []
        if self._trace_path:
            from ..obs.spans import SpanTracer
            self.tracer = (SpanTracer(max_events=self._trace_cap)
                           if self._trace_cap else SpanTracer())
        self._rid = 0
        self._pad_rng = np.random.default_rng(cfg.data_seed)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="batch-engine")
        self._thread.start()

    # -- executables ---------------------------------------------------------
    def _build_executables(self) -> None:
        """AOT-compile (or AOT-deserialize) the prefill + decode (and,
        when chunking is on, prefill-chunk) executables — all with the
        pooled state DONATED
        (``DECODE_DONATE_ARGNUMS``/``PREFILL_DONATE_ARGNUMS``/
        ``PREFILL_CHUNK_DONATE_ARGNUMS``): the caches, token pool,
        positions and rng are step-carried state, and without
        input-output aliasing every decode step pays a full pool copy on
        device.  The cache key covers config + params structure + mesh +
        toolchain (``aot_cache_key``); a hit requires EVERY executable
        the knobs call for; a miss compiles and then best-effort
        persists all of them."""
        cfg = self.cfg
        decode_abs, prefill_abs, chunk_abs = abstract_exec_args(
            cfg, self.params, self.rows, self.n_lanes)
        cache_dir = getattr(cfg, "serve_aot_cache_dir", "")
        dec_path = pre_path = chk_path = None
        self._prefill_chunk = None
        if cache_dir:
            key = aot_cache_key(cfg, self.params, self.n_lanes)
            os.makedirs(cache_dir, exist_ok=True)
            dec_path = os.path.join(cache_dir, f"decode-{key}.jaxexec")
            pre_path = os.path.join(cache_dir, f"prefill-{key}.jaxexec")
            if chunk_abs is not None:
                chk_path = os.path.join(cache_dir,
                                        f"prefill_chunk-{key}.jaxexec")
            t0 = time.perf_counter()
            dec = _aot_load(dec_path)
            pre = _aot_load(pre_path) if dec is not None else None
            chk = (_aot_load(chk_path)
                   if chk_path is not None and pre is not None else None)
            if (dec is not None and pre is not None
                    and (chk_path is None or chk is not None)):
                self._decode, self._prefill = dec, pre
                self._prefill_chunk = chk
                self.aot_reload_s = time.perf_counter() - t0
                self.aot_cache_hit = True
                return
            self.aot_cache_hit = False
        dec_jit, pre_jit, chk_jit = jit_executables(
            cfg, self.rows, self.n_lanes,
            self._first_token_cb if self._graph_ttft else None,
            donate=not cache_dir)
        t0 = time.perf_counter()
        self._decode = dec_jit.lower(*decode_abs).compile()
        self._prefill = pre_jit.lower(*prefill_abs).compile()
        if chk_jit is not None:
            self._prefill_chunk = chk_jit.lower(*chunk_abs).compile()
        self.compile_s = time.perf_counter() - t0
        if dec_path is not None:
            _aot_save(dec_path, self._decode)
            _aot_save(pre_path, self._prefill)
            if chk_path is not None:
                _aot_save(chk_path, self._prefill_chunk)

    # -- submission (any thread) ---------------------------------------------
    def queue_depth(self) -> int:
        with self._cv:
            return self._pending

    def kv_blocks_free(self) -> int:
        return self.allocator.free_blocks

    def active_lanes(self) -> int:
        # _cv wraps an RLock, so the scheduler loop's locked wait
        # predicate re-enters here safely
        with self._cv:
            return sum(1 for r in self._lane_req if r is not None)

    def set_batch_observer(self, fn: typing.Optional[typing.Callable]
                           ) -> None:
        """Per-decode-step occupancy sink (``ServeSLO.observe_batch``):
        called with the number of active lanes after each step."""
        with self._cv:
            self._batch_observer = fn

    def set_step_observer(self, fn: typing.Optional[typing.Callable]
                          ) -> None:
        """Per-iteration phase sink (``ServeSLO.observe_step``): called
        with ``(wall_s, phases, n_active, prefill_stall_s, stepped)`` after
        every scheduler-loop iteration that did work.  The phase dict's
        values are contiguous host segments of the iteration, so they sum
        to ``wall_s`` (docs/observability.md "Streaming and inter-token
        latency")."""
        with self._cv:
            self._step_observer = fn

    def set_health(self, health) -> None:
        """Attach the decode-loop liveness probe (``slo.EngineHealth``):
        the scheduler stamps each iteration that has work, so a wedged
        dispatch flips ``/healthz`` to stalled while an idle loop stays
        healthy."""
        with self._cv:
            self._health = health

    def submit(self, prompt: typing.Sequence[int], temperature: float,
               max_tokens: typing.Optional[int],
               top_k: typing.Optional[int],
               top_p: typing.Optional[float],
               token_sink: typing.Optional["queue.Queue"] = None
               ) -> _BatchRequest:
        """Queue a completion; sheds immediately (503 semantics) when the
        backlog exceeds ``serve_queue_limit`` or the request's whole KV
        footprint can never fit the pool.  ``token_sink`` (streaming):
        completion-token chunks are pushed in generation order while the
        lane decodes, then a ``None`` sentinel — always delivered, success
        or failure."""
        cfg = self.cfg
        prompt = list(prompt)[:self.rows * self.patch]
        depth = self.queue_depth()
        limit = int(getattr(cfg, "serve_queue_limit", 0))
        if limit and depth >= limit:
            raise QueueDeadlineExceeded(
                0.0, float(getattr(cfg, "serve_queue_deadline_s", 0.0)),
                depth, shed=True)
        end = (self.rows * self.patch if max_tokens is None
               else min(self.rows * self.patch, len(prompt) + max_tokens))
        if not self.allocator.fits(end):
            raise QueueDeadlineExceeded(
                0.0, float(getattr(cfg, "serve_queue_deadline_s", 0.0)),
                depth, shed=True)
        rec = slo.current()
        if rec is not None:
            rec.mark_enqueued(queue_depth=depth)
        k, p = effective_truncation(cfg, top_k, top_p)
        with self._cv:
            if self._closed:
                raise RuntimeError("engine is closed")
            self._rid += 1
            req = _BatchRequest(self._rid, prompt, float(temperature),
                                max_tokens, int(k), float(p), rec,
                                sink=token_sink)
            req.end = end
            self._queue.append(req)
            self._pending += 1
            self._cv.notify_all()
        return req

    def complete_tokens(self, prompt: typing.Sequence[int],
                        temperature: typing.Optional[float] = None,
                        max_tokens: typing.Optional[int] = None,
                        top_k: typing.Optional[int] = None,
                        top_p: typing.Optional[float] = None,
                        token_sink: typing.Optional[
                            "queue.Queue"] = None) -> np.ndarray:
        """Blocking convenience with the CompletionEngine signature."""
        cfg = self.cfg
        req = self.submit(prompt,
                          cfg.sampling_temperature if temperature is None
                          else temperature, max_tokens, top_k, top_p,
                          token_sink=token_sink)
        return self.fetch(req)

    def fetch(self, req: _BatchRequest,
              deadline_s: typing.Optional[float] = None) -> np.ndarray:
        """Block for ``req``'s result; a still-QUEUED request past the
        deadline is cancelled and raises :class:`QueueDeadlineExceeded`
        (an admitted one always finishes — its lane is already decoding)."""
        deadline = (float(getattr(self.cfg, "serve_queue_deadline_s", 0.0))
                    if deadline_s is None else deadline_s)
        poll = max(0.01, float(self.cfg.default_sleep_duration))
        while True:
            try:
                status, value = req.out.get(timeout=poll)
                break
            except queue.Empty:
                waited = time.monotonic() - req.t_enq
                if (deadline and waited > deadline
                        and not req.admitted.is_set()):
                    req.cancelled.set()
                    raise QueueDeadlineExceeded(waited, deadline,
                                                self.queue_depth())
        if status == "err":
            raise value
        return value

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=30.0)
        self.export_trace()

    def export_trace(self) -> typing.Optional[str]:
        """Write the serving Chrome trace (``serve_trace_path``): decode
        phase spans + per-lane occupancy tracks; None when tracing is
        off.  Safe to call repeatedly (close() calls it; a test may call
        earlier for a mid-flight snapshot)."""
        if self.tracer is None or not self._trace_path:
            return None
        try:
            return self.tracer.export(self._trace_path)
        except OSError:
            return None

    # -- scheduler thread ----------------------------------------------------
    def _pad_prompt(self, req: _BatchRequest) -> np.ndarray:
        """Prompt laid out row-major over the lane's full context, padded
        with random tokens the decode loop overwrites (the serialized
        engine's padding contract; only an empty prompt's row 0 ever
        influences sampling, as its seed row)."""
        flat = self._pad_rng.integers(
            0, self.cfg.vocab_size, size=self.rows * self.patch,
            dtype=np.int64).astype(np.int32)
        flat[:len(req.prompt)] = np.asarray(req.prompt, np.int32)
        return flat.reshape(1, self.rows, self.patch)

    def _admit(self, prefill_segs: typing.List[tuple],
               stall: typing.List[float]) -> None:
        """Fill free lanes from the queue between decode steps: allocate
        the KV-block footprint, then either prefill the lane and arm the
        mirrors (monolithic) or enqueue it on the chunked-prefill FIFO
        (``serve_prefill_chunk_tokens > 0`` — chunks dispatch one per loop
        iteration, :meth:`_advance_prefill`).  Stops at the first request
        the pool cannot hold RIGHT NOW (FIFO — a small request never
        starves a big one already at the head).

        ``prefill_segs`` collects each prefill dispatch's
        ``(t0, t1, lane, rid, xid)`` host segment; ``stall[0]`` accumulates
        stalled-lane-seconds — the monolithic path's BLOCKING prefill wall
        times the lanes that held active requests while the scheduler
        thread was pinned (docs/observability.md).  The chunked path never
        blocks, so it never stalls."""
        while True:
            with self._cv:
                # snapshot the cancel flags ONCE: a deadline-cancel landing
                # between two separate is_set() sweeps would put a request
                # in BOTH lists — kept queued yet counted as dropped, and
                # decremented again on the next prune (queue_depth
                # underflow)
                flags = [(r, r.cancelled.is_set()) for r in self._queue]
                live = [r for r, c in flags if not c]
                dropped = [r for r, c in flags if c]
                if dropped:
                    self._queue[:] = live
                    self._pending -= len(dropped)
            for r in dropped:
                if r.sink is not None:  # cancelled before admission: the
                    r.sink.put(None)    # stream ends with just the sentinel
                try:  # unblock a fetcher that didn't initiate the cancel
                    r.out.put_nowait(("err", RequestCancelled(r.rid)))
                except queue.Full:
                    pass  # deadline-cancel already consumed its slot
            with self._cv:
                if not self._queue:
                    return
                try:
                    lane = self._lane_req.index(None)
                except ValueError:
                    return
                req = self._queue[0]
                blocks = self.allocator.alloc(req.rid, req.end)
                if blocks is None:
                    return
                req.n_blocks = len(blocks)
                req.t_alloc = time.perf_counter()
                self._queue.pop(0)
                self._pending -= 1
            self._start_request(req, lane, prefill_segs, stall)

    def _start_request(self, req: _BatchRequest, lane: int,
                       prefill_segs: typing.List[tuple],
                       stall: typing.List[float]) -> None:
        rec = req.rec
        req.admitted.set()
        prompt_rows = len(req.prompt) // self.patch
        req.prompt_rows = prompt_rows
        req.end_row = (self.rows if req.max_tokens is None
                       else min(self.rows,
                                -(-(len(req.prompt) + req.max_tokens)
                                  // self.patch)))
        req.first_gen = max(prompt_rows, 1)
        req.tag = rec.rid if rec is not None and self._graph_ttft else 0
        if rec is not None:
            rec.mark_started()
            rec.tokens_generated = max(0, req.end - len(req.prompt))
        if req.tag:
            slo.register_first_token(req.tag, rec.mark_first_token)
        padded = self._pad_prompt(req)
        req.padded = padded
        if req.sink is not None:
            # streaming: chunks concatenate to exactly the completion; the
            # host-built padded layout covers positions decode never
            # rewrites (the seed row of an empty prompt)
            req.rstream = _RowStream(req.sink, len(req.prompt), req.end,
                                     self.patch, req.first_gen,
                                     initial_tokens=padded.reshape(-1),
                                     rec=rec)
        if self._chunk_rows:
            # chunked prefill: the lane is occupied (holds the request and
            # its blocks) but NOT armed for decode (_end_row stays 0, so
            # the decode mask skips it) until _advance_prefill has covered
            # the prompt.  Coverage is max(prompt_rows, 1): decode starts
            # at row prompt_rows - 1 and writes every later row itself,
            # and an empty prompt's seed row still needs its token written
            # (monolithic prefill writes the whole padded layout)
            req.prefill_rows = max(prompt_rows, 1)
            req.next_chunk_row = 0
            self._lane_req[lane] = req
            self._prefill_fifo.append(lane)
            return
        # monolithic (serve_prefill_chunk_tokens=0): timed INCLUDING the
        # device wall (block_until_ready) — the scheduler thread would pay
        # it at the next step's sync anyway, and attributing it here is the
        # whole point.  This wall, times the lanes concurrently holding
        # active requests, is hbnlp_serve_prefill_stall_seconds
        # (stalled-lane-seconds: an idle-engine admission stalls nobody)
        n_stalled = self.active_lanes()
        t_p0 = time.perf_counter()
        try:
            self._caches, self._toks = self._prefill(
                self.params, self._caches, self._toks, padded,
                np.int32(lane), np.int32(prompt_rows))
            jax.block_until_ready(self._toks)
        except Exception as e:  # noqa: BLE001 - fail THIS request, keep serving
            self._fail_admission(req, e)
            return
        t_p1 = time.perf_counter()
        prefill_segs.append((t_p0, t_p1, lane, req.rid,
                             rec.xid if rec is not None else ""))
        stall[0] += (t_p1 - t_p0) * n_stalled
        self._lane_req[lane] = req
        self._arm_lane(req, lane)

    def _settle_kv(self, req: _BatchRequest) -> None:
        """Integrate KV/lane occupancy onto the SLO record at the instant
        the blocks go back to the pool — every free site calls this first,
        so block-seconds is exactly blocks x (free wall - alloc wall) no
        matter which exit path (finish, prefill failure, cancel, pool
        loss) released them."""
        rec = req.rec
        if rec is None:
            return
        now = time.perf_counter()
        rec.kv_blocks = req.n_blocks
        if req.t_alloc is not None:
            rec.kv_block_seconds = req.n_blocks * (now - req.t_alloc)
        t0 = req.t_admitted if req.t_admitted is not None else req.t_alloc
        if t0 is not None:
            rec.lane_seconds = now - t0

    def _fail_admission(self, req: _BatchRequest, e: BaseException) -> None:
        """Fail ONE request whose prefill (monolithic or a chunk) raised,
        keep serving: the request is already admitted (deadline-cancel
        disabled) and holds blocks — an unhandled prefill error would leak
        both and leave its fetch() blocking forever.  Re-raises when the
        failed dispatch consumed the donated pool (the other lanes' state
        is gone too), escalating to the loop's fail-everything path, which
        reinitializes the pool."""
        self._settle_kv(req)
        self.allocator.free(req.rid)
        if req.tag:
            slo.unregister_first_token(req.tag)
        if req.rec is not None:
            req.rec.mark_engine_done()
        if req.rstream is not None:
            req.rstream.close()
        req.out.put(("err", e))
        if self._pool_deleted():
            raise e

    def _advance_prefill(self, prefill_segs: typing.List[tuple]) -> None:
        """Dispatch AT MOST ONE prefill chunk — the head-of-FIFO lane's
        next ``_chunk_rows`` rows — per scheduler iteration, WITHOUT
        blocking (overlapped dispatch): the chunk executable donates the
        pools, so the next decode step consumes its output by data
        dependence and the host never waits on prefill device time; a
        lane's readiness is synced implicitly at the first step that reads
        its state.  A long prompt therefore admits over N iterations while
        every armed lane keeps decoding.  The last chunk arms the lane.

        The final chunk's start row is clamped so the executable stays
        static-shaped: re-writing already-covered rows recomputes
        bit-identical values (same tokens against the same cache prefix),
        so a ragged last chunk costs overlap, never correctness."""
        lane = self._prefill_fifo[0]
        req = self._lane_req[lane]
        start = max(0, min(req.next_chunk_row, self.rows - self._chunk_rows))
        t_c0 = time.perf_counter()
        try:
            chunk = jnp.asarray(
                req.padded[:, start:start + self._chunk_rows, :])
            self._caches, self._toks = self._prefill_chunk(
                self.params, self._caches, self._toks, chunk,
                np.int32(lane), np.int32(start))
        except Exception as e:  # noqa: BLE001 - fail THIS request, keep serving
            # partially-admitted: release the lane and its whole block
            # footprint before failing the request
            self._prefill_fifo.pop(0)
            self._lane_req[lane] = None
            self._fail_admission(req, e)
            return
        t_c1 = time.perf_counter()
        prefill_segs.append((t_c0, t_c1, lane, req.rid,
                             req.rec.xid if req.rec is not None else ""))
        req.next_chunk_row += self._chunk_rows
        if req.next_chunk_row >= req.prefill_rows:
            self._prefill_fifo.pop(0)
            req.padded = None  # the chunks are on device; drop the host copy
            self._arm_lane(req, lane)

    def _arm_lane(self, req: _BatchRequest, lane: int) -> None:
        """Arm a prefilled lane for decode: host mirrors, the per-request
        RNG stream, the device position vector.  Completes the request
        immediately when there is nothing to generate (full prompt / zero
        budget) — the lane never joins the decode loop."""
        req.t_admitted = time.perf_counter()
        self._pos_h[lane] = max(req.prompt_rows - 1, 0)
        self._end_row[lane] = req.end_row
        self._first_gen[lane] = req.first_gen
        self._temps[lane] = req.temperature
        self._ks[lane] = req.top_k
        self._ps[lane] = req.top_p
        self._tags[lane] = req.tag
        # arm the lane's RNG stream: fold_in(seed, rid) — independent of
        # lane placement and admission order (typed keys have no .at, so
        # splice on the raw key data)
        data = jax.random.key_data(self._rngs)
        self._rngs = jax.random.wrap_key_data(data.at[lane].set(
            jax.random.key_data(lane_key(self.cfg.data_seed, req.rid))))
        self._pos = jnp.asarray(self._pos_h)
        if self._pos_h[lane] >= req.end_row - 1:
            self._finish_lane(lane)

    def _step(self, segs: typing.List[tuple], t_start: float) -> int:
        """One decode step over every active lane, then completion checks,
        attributed into contiguous host segments appended to ``segs``:

        - **dispatch** — building the active mask + the async decode call;
        - **sync** — blocking on the returned positions (the loop's pacing
          D2H; the device's decode wall lands here);
        - **sample** — materializing sampled rows on host: streamed lanes'
          new rows, finished lanes' outputs;
        - **emit** — observer callbacks, TTFT/ITL stamps, sink pushes,
          lane completion bookkeeping.

        Returns the number of lanes that shared the step."""
        prev_pos = self._pos_h.copy()
        active = (np.array([r is not None for r in self._lane_req])
                  & (self._pos_h < self._end_row - 1))
        self._caches, self._toks, self._pos, self._rngs, self._logits = (
            self._decode(self.params, self._caches, self._toks, self._pos,
                         active, self._end_row, self._first_gen, self._temps,
                         self._ks, self._ps, self._rngs, self._tags))
        t_dispatch = time.perf_counter()
        segs.append(("dispatch", t_start, t_dispatch))
        # blocks until the step lands (the loop's pacing sync); copy — the
        # zero-copy view over the device buffer is read-only, and admission
        # writes lanes into this mirror
        self._pos_h = np.array(self._pos, np.int32)
        t_sync = time.perf_counter()
        segs.append(("sync", t_dispatch, t_sync))
        n_active = int(active.sum())
        # sample pass: pull every token this step made visible — streamed
        # lanes' new rows, finished lanes' full outputs — so the emit pass
        # below never blocks on the device
        emissions: typing.List[tuple] = []
        finished: typing.List[tuple] = []
        for lane, req in enumerate(self._lane_req):
            if req is None or not active[lane]:
                continue
            new_pos = int(self._pos_h[lane])
            written = (new_pos > int(prev_pos[lane])
                       and new_pos < int(self._end_row[lane])
                       and new_pos < self.rows)
            if written:
                row = (np.asarray(self._toks[lane, new_pos]).reshape(-1)
                       if req.rstream is not None else None)
                emissions.append((lane, req, new_pos, row))
            if new_pos >= int(self._end_row[lane]) - 1:
                finished.append(
                    (lane,
                     np.asarray(self._toks[lane]).reshape(-1)[:req.end]))
        t_sample = time.perf_counter()
        segs.append(("sample", t_sync, t_sample))
        with self._cv:
            obs = self._batch_observer
        if obs is not None:
            try:
                obs(n_active)
            except Exception:  # noqa: BLE001 - metrics must not kill serving
                pass
        for lane, req, new_pos, row in emissions:
            if (not self._graph_ttft and req.rec is not None
                    and new_pos == int(self._first_gen[lane])):
                # host-side TTFT (AOT-cached executables carry no host
                # callback): the lane's first generated row landed in the
                # step that just synced — mark_first_token keeps the
                # first stamp, so a repeated hit is a no-op
                req.rec.mark_first_token()
            if req.rstream is not None:
                req.rstream.on_row(new_pos, row)  # stamps mark_token
            elif req.rec is not None:
                # no sink: stamp the emission instant anyway — ITL is the
                # engine's token cadence, what a streaming client of this
                # request WOULD have seen
                req.rec.mark_token()
        for lane, out in finished:
            self._finish_lane(lane, out=out)
        segs.append(("emit", t_sample, time.perf_counter()))
        return n_active

    def _finish_lane(self, lane: int,
                     out: typing.Optional[np.ndarray] = None) -> None:
        req = self._lane_req[lane]
        if out is None:
            out = np.asarray(self._toks[lane]).reshape(-1)[:req.end]
        rec = req.rec
        if req.tag:
            # flush the in-flight TTFT callback before unrouting
            jax.effects_barrier()
            slo.unregister_first_token(req.tag)
        # settle + engine-done BEFORE publishing (the stream close below or
        # the out-queue put): the waiting handler's finish() runs the
        # instant fetch() wakes (serve/interface.py contract) and its usage
        # finalize must see the KV block-seconds already on the record
        self._settle_kv(req)
        if rec is not None:
            rec.mark_engine_done()
        if req.rstream is not None:
            req.rstream.flush_final(out)
            req.rstream.close()
        if self.tracer is not None and req.t_admitted is not None:
            args = {"rid": req.rid}
            if rec is not None:
                args["request"] = rec.rid
                if rec.xid:
                    args["xid"] = rec.xid
            self.tracer.add("occupied", req.t_admitted, time.perf_counter(),
                            track=f"lane{lane}", **args)
        self._lane_req[lane] = None
        self._end_row[lane] = 0
        self._tags[lane] = 0
        self.allocator.free(req.rid)
        req.out.put(("ok", out))

    def _decode_armed(self) -> bool:
        """Whether any lane is armed for decode.  Lanes mid-chunked-prefill
        occupy a lane (``active_lanes`` counts them, keeping the loop
        awake) but keep ``_end_row`` at 0 until :meth:`_arm_lane`, so a
        decode step never runs for prefill-only iterations."""
        return any(r is not None and self._end_row[lane] > 0
                   for lane, r in enumerate(self._lane_req))

    def _loop(self) -> None:
        while True:
            with self._cv:
                while (not self._queue and self.active_lanes() == 0
                       and not self._closed):
                    self._cv.wait(timeout=0.5)
                if self._closed and self.active_lanes() == 0 and not self._queue:
                    return
            with self._cv:
                health = self._health
            if health is not None:
                health.iteration_started()
            t0 = time.perf_counter()
            segs: typing.List[tuple] = []  # contiguous (name, t0, t1)
            prefill_segs: typing.List[tuple] = []
            stall = [0.0]
            stepped = False
            n_active = 0
            try:
                self._chaos_serve_step()
                self._reap_cancelled()
                self._admit(prefill_segs, stall)
                if self._prefill_fifo:
                    self._advance_prefill(prefill_segs)
                t_admit = time.perf_counter()
                segs.append(("admit", t0, t_admit))
                if self._decode_armed():
                    n_active = self._step(segs, t_admit)
                    stepped = True
            except Exception as e:  # noqa: BLE001 - fail every in-flight req
                self._fail_all(e)
                if health is not None:
                    health.iteration_completed(time.perf_counter() - t0)
                continue
            self._report_iteration(t0, segs, prefill_segs, stall[0],
                                   n_active, stepped)
            if health is not None:
                health.iteration_completed(time.perf_counter() - t0)

    def _chaos_serve_step(self) -> None:
        """Poll the ``serve_step`` fault site once per iteration that has
        work (reliability/faults.py; take-only — the actions need loop
        context): ``stall`` wedges THIS iteration past the watchdog bound
        (``HBNLP_SERVE_STALL_S`` overrides the 2 s default — drills hold
        the stall long enough for a router poll to observe it), ``fail``
        raises into the loop's fail-everything path."""
        for action in faults.take("serve_step"):
            if action == "stall":
                time.sleep(float(os.environ.get("HBNLP_SERVE_STALL_S",
                                                "2.0")))
            elif action == "fail":
                raise faults.FaultInjectedIOError(
                    "injected serve_step failure (chaos)")

    def _reap_cancelled(self) -> None:
        """Free lanes whose client walked away (SSE disconnect → the REST
        handler set ``req.cancelled``): release the lane and its KV blocks
        for queued work instead of decoding an abandoned stream to
        completion.  Mid-chunked-prefill lanes leave the FIFO too.  The
        result queue gets :class:`RequestCancelled` so any thread still
        blocked in ``fetch()`` unblocks."""
        reaped: typing.List[tuple] = []
        for lane, req in enumerate(self._lane_req):
            if req is None or not req.cancelled.is_set():
                continue
            if lane in self._prefill_fifo:
                self._prefill_fifo.remove(lane)
            generated = max(0, int(self._pos_h[lane])
                            - max(req.prompt_rows - 1, 0))
            self._lane_req[lane] = None
            self._end_row[lane] = 0
            if req.tag:
                slo.unregister_first_token(req.tag)
                self._tags[lane] = 0
            self._settle_kv(req)
            self.allocator.free(req.rid)
            reaped.append((lane, req, generated))
        for lane, req, generated in reaped:
            if req.rstream is not None:
                req.rstream.close()
            elif req.sink is not None:
                req.sink.put(None)
            if req.rec is not None:
                # the ACTUAL generation, not the plan: a disconnect stops
                # the lane mid-decode, and metering bills what was decoded
                plan = max(0, req.end - len(req.prompt))
                req.rec.tokens_generated = min(plan,
                                               generated * self.patch)
                req.rec.mark_engine_done()
            if self.tracer is not None and req.t_admitted is not None:
                self.tracer.add("occupied", req.t_admitted,
                                time.perf_counter(), track=f"lane{lane}",
                                rid=req.rid, cancelled=True)
            try:
                req.out.put_nowait(("err",
                                    RequestCancelled(req.rid, generated)))
            except queue.Full:
                pass

    def _report_iteration(self, t0: float, segs: typing.List[tuple],
                          prefill_segs: typing.List[tuple],
                          stall_s: float, n_active: int,
                          stepped: bool) -> None:
        """Close the books on one scheduler iteration: derive the phase
        decomposition (contiguous segments, prefill carved out of admit —
        the sum equals the iteration wall by construction), feed the step
        observer, and record the spans/lane tracks on the serving trace."""
        t_end = segs[-1][2] if segs else t0
        wall = t_end - t0
        if wall <= 0 or not segs:
            return
        prefill_s = sum(t1 - t0_ for t0_, t1, *_ in prefill_segs)
        phases = {name: 0.0 for name in slo.STEP_PHASES}
        for name, s0, s1 in segs:
            phases[name] = phases.get(name, 0.0) + (s1 - s0)
        phases["admit"] = max(0.0, phases["admit"] - prefill_s)
        phases["prefill"] = prefill_s
        with self._cv:
            observer = self._step_observer
        if observer is not None:
            try:
                observer(wall, phases, n_active, stall_s, stepped)
            except Exception:  # noqa: BLE001 - metrics must not kill serving
                pass
        tracer = self.tracer
        if tracer is not None:
            tracer.add("engine/step", t0, t_end, active=n_active)
            for name, s0, s1 in segs:
                tracer.add(f"engine/{name}", s0, s1)
            for s0, s1, lane, rid, xid in prefill_segs:
                args = {"rid": rid}
                if xid:
                    args["xid"] = xid
                tracer.add("engine/prefill", s0, s1, **args)
                tracer.add("prefilling", s0, s1, track=f"lane{lane}",
                           **args)
            if (self._trace_path and self._trace_cap
                    and tracer.event_count() >= self._trace_cap):
                self._rotate_trace()

    def _rotate_trace(self) -> None:
        """Roll the filled span ring out to the next ``<path>.NNN.json``
        segment and clear it: the capped serving trace persists in rolling
        segments instead of silently evicting its oldest spans, so a crash
        loses at most one ring (docs/observability.md "Request tracing").
        ``close()``'s final :meth:`export_trace` still writes the base
        path with whatever the last partial ring holds."""
        base, ext = os.path.splitext(self._trace_path)
        self._trace_seq += 1
        path = f"{base}.{self._trace_seq:03d}{ext or '.json'}"
        try:
            self.trace_segments.append(self.tracer.rotate(path))
        except OSError:
            pass  # tracing is evidence, not a gate

    def _pool_deleted(self) -> bool:
        """Whether a donated call consumed the pooled device state without
        returning replacements (an exception after dispatch)."""
        try:
            leaves = jax.tree_util.tree_leaves(self._caches)
            leaves += [self._toks, self._pos]
            return any(getattr(x, "is_deleted", lambda: False)()
                       for x in leaves)
        except Exception:  # noqa: BLE001 - conservative: assume dead
            return True

    def _reset_pool(self) -> None:
        """Fresh zeroed pool state (caches/toks/pos/rng) after a failure
        consumed the donated buffers — every lane was already failed, so
        losing their K/V is the correct outcome, not a data loss."""
        cfg = self.cfg
        self._caches = kvc.init_caches(cfg, self.params, self.n_lanes,
                                       self.rows)
        self._toks = jnp.zeros((self.n_lanes, self.rows, self.patch),
                               jnp.int32)
        self._pos = jnp.zeros((self.n_lanes,), jnp.int32)
        self._rngs = jax.random.split(jax.random.key(cfg.data_seed),
                                      self.n_lanes)
        self._pos_h = np.zeros(self.n_lanes, np.int32)

    def _fail_all(self, e: BaseException) -> None:
        self._prefill_fifo.clear()
        for lane, req in enumerate(self._lane_req):
            if req is not None:
                self._lane_req[lane] = None
                self._end_row[lane] = 0
                self._settle_kv(req)
                self.allocator.free(req.rid)
                if req.tag:
                    slo.unregister_first_token(req.tag)
                if req.rstream is not None:
                    req.rstream.close()
                if req.rec is not None:
                    # stamp engine-done even on failure: an unstamped
                    # record silently drops its engine/decode observations
                    # (serve/interface.py contract) — exactly during the
                    # failures the histograms should show
                    req.rec.mark_engine_done()
                req.out.put(("err", e))
        with self._cv:
            pending, self._queue = self._queue, []
            self._pending = 0
        for req in pending:
            if req.sink is not None:
                req.sink.put(None)
            req.out.put(("err", e))
        if self._pool_deleted():
            self._reset_pool()


class BatchInterface:
    """``InterfaceWrapper``-shaped facade over :class:`BatchEngine` so the
    REST layer (and bench/tests) swap engines by config: ``complete(...,
    asynchronous=True)`` returns a ``fetch`` callable, ``queue_depth`` /
    ``kv_blocks_free`` feed the SLO gauges, ``close`` drains the
    scheduler.  There are no worker threads to serialize behind — the
    queue here is the ADMISSION queue, drained between decode steps."""

    def __init__(self, engine: BatchEngine):
        self.engine = engine

    def queue_depth(self) -> int:
        return self.engine.queue_depth()

    def kv_blocks_free(self) -> int:
        return self.engine.kv_blocks_free()

    def set_batch_observer(self, fn) -> None:
        self.engine.set_batch_observer(fn)

    def set_step_observer(self, fn) -> None:
        self.engine.set_step_observer(fn)

    def set_health(self, health) -> None:
        self.engine.set_health(health)

    def lane_count(self) -> int:
        """Concurrent drain width (serve_max_batch) — Retry-After pricing
        divides the backlog by it (``ServeSLO.set_lane_count``)."""
        return self.engine.n_lanes

    def active_lanes(self) -> int:
        return self.engine.active_lanes()

    def complete(self, prompt: typing.Sequence[int], temperature: float = 0.0,
                 response_len: int = 64, asynchronous: bool = False,
                 top_k: typing.Optional[int] = None,
                 top_p: typing.Optional[float] = None,
                 token_sink: typing.Optional["queue.Queue"] = None):
        req = self.engine.submit(prompt, temperature, response_len,
                                 top_k, top_p, token_sink=token_sink)

        def fetch():
            return self.engine.fetch(req)

        # client-abandonment hook (SSE disconnect): the scheduler's reap
        # pass frees the lane + KV blocks at the next iteration
        fetch.cancel = req.cancelled.set
        return fetch if asynchronous else fetch()

    def close(self) -> None:
        self.engine.close()
