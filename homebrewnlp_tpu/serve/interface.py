"""Serving bridge: tokenizers, completion engine, async wrapper.

The reference couples serving to the TF session loop through a
multiprocessing-Manager queue (``InterfaceWrapper``, /root/reference/src/
interface.py:231-280); in JAX the sampler is an ordinary jitted function, so
the engine is a plain object and the async wrapper is a worker thread + queue
(same API: blocking or async ``complete``).

Tokenizers mirror the reference's two modes (interface.py:184-198): raw
byte-level for vocab<=256, HuggingFace GPT2 BPE otherwise.
"""
from __future__ import annotations

import queue
import threading
import time
import typing

import jax
import numpy as np

from ..config import Config
from ..data.feed import TEXT_AXES
from ..infer.sampler import make_text_sampler
from ..nd import NT
from . import slo
from ..sync import make_lock


class QueueDeadlineExceeded(RuntimeError):
    """A completion request spent longer than ``cfg.serve_queue_deadline_s``
    waiting on the serialized engine queue (or arrived past
    ``serve_queue_limit`` and was shed at admission).  The REST layer maps
    this to 503 + Retry-After (docs/observability.md "Serving SLOs")."""

    def __init__(self, waited_s: float, deadline_s: float, queue_depth: int,
                 shed: bool = False):
        self.waited_s = float(waited_s)
        self.deadline_s = float(deadline_s)
        self.queue_depth = int(queue_depth)
        self.shed = bool(shed)
        if shed:
            msg = (f"engine queue full ({queue_depth} waiting >= "
                   f"serve_queue_limit); request shed at admission")
        else:
            msg = (f"queue wait {waited_s:.2f}s exceeded "
                   f"serve_queue_deadline_s={deadline_s:g}s "
                   f"({queue_depth} still queued)")
        super().__init__(msg)


class RequestCancelled(RuntimeError):
    """The client abandoned this completion (SSE disconnect mid-stream,
    or an explicit ``fetch.cancel()``): the scheduler reaped the lane and
    freed its KV blocks instead of decoding to completion
    (docs/reliability.md "Serving resilience").  Raised from ``fetch()``
    so any thread still blocked on the result unblocks promptly."""

    def __init__(self, rid: int, generated: int = 0):
        self.rid = int(rid)
        self.generated = int(generated)
        super().__init__(
            f"request rid={rid} cancelled by client after "
            f"{generated} generated row(s); lane and KV blocks reclaimed")


class ByteTokenizer:
    def encode(self, text: str) -> typing.List[int]:
        return list(text.encode("utf-8", errors="replace"))

    def decode(self, ids: typing.Sequence[int]) -> str:
        return bytes(int(i) & 0xFF for i in ids).decode("utf-8", errors="replace")


class Gpt2Tokenizer:
    def __init__(self):
        from transformers import GPT2TokenizerFast
        self._tok = GPT2TokenizerFast.from_pretrained("gpt2")

    def encode(self, text: str) -> typing.List[int]:
        return self._tok.encode(text)

    def decode(self, ids: typing.Sequence[int]) -> str:
        return self._tok.decode(list(ids))


class HbnlpBpeTokenizer:
    """Serving-side codec for a tools/train_tokenizer.py artifact
    (byte-fallback BPE: ids < first_new_id are raw bytes, id
    first_new_id+i expands to merge i's pair).  Encoding runs the same
    heap-driven native encoder the tfrecord builder uses, so serving and
    training tokenize identically."""

    def __init__(self, path: str):
        import json

        import numpy as np
        with open(path) as f:
            art = json.load(f)
        self._merges = np.asarray(art["merges"], np.int32)
        self._first = int(art.get("first_new_id", 256))
        # id -> bytes, built bottom-up (merge i only references ids < i)
        table: typing.List[bytes] = [bytes([b]) for b in range(self._first)]
        for left, right in self._merges:
            table.append(table[int(left)] + table[int(right)])
        self._bytes = table

    def encode(self, text: str) -> typing.List[int]:
        import numpy as np

        from ..native import bpe_encode
        raw = np.frombuffer(text.encode("utf-8", errors="replace"),
                            np.uint8).astype(np.int32)
        return [int(t) for t in bpe_encode(raw, self._merges, self._first)]

    def decode(self, ids: typing.Sequence[int]) -> str:
        out = b"".join(self._bytes[int(i)] for i in ids
                       if 0 <= int(i) < len(self._bytes))
        return out.decode("utf-8", errors="replace")


def tokenizer_for(cfg: Config):
    if getattr(cfg, "tokenizer_path", ""):
        return HbnlpBpeTokenizer(cfg.tokenizer_path)
    if cfg.vocab_size <= 256:
        return ByteTokenizer()
    try:
        return Gpt2Tokenizer()
    except Exception:  # offline image: fall back to bytes
        return ByteTokenizer()


def effective_truncation(cfg: Config, top_k, top_p) -> typing.Tuple[int, float]:
    """The (k, p) bucket a request's truncation knobs actually compile to:
    k rounds up to the next power of two (capped at vocab), p snaps to a
    0.05 grid.  None keeps the config's exact value, un-bucketed.  Exposed
    so the REST layer can echo the EFFECTIVE values back to callers (e.g.
    requested top_k=3 samples top-4)."""
    if top_k is None:
        k = cfg.sampling_top_k
    else:
        k = max(0, int(top_k))
        if k > 0:
            k = min(1 << (k - 1).bit_length(), cfg.vocab_size)
    if top_p is None:
        p = cfg.sampling_top_p
    else:
        p = float(top_p)
        p = (1.0 if p >= 1.0
             else max(0.05, round(round(p / 0.05) * 0.05, 2)))
    return k, p


class _RowStream:
    """In-order visible-token emission from per-row callbacks
    (docs/observability.md "Streaming and inter-token latency").

    The samplers' row callback is UNORDERED (``_fire_token_row``), so rows
    are buffered and released in sequence; each release pushes the slice of
    the row that belongs to the COMPLETION — clipped against the prompt
    tail on the left (a partial prompt row is regenerated but its prompt
    tokens are not new output) and ``end`` on the right — into ``sink`` and
    stamps the ambient request record (``RequestRecord.mark_token``), so
    the concatenated stream is byte-identical to the buffered response.

    ``initial_tokens`` (the host-built padded layout) covers positions in
    rows the decode loop never rewrites — e.g. the seed row of an empty
    prompt under the KV sampler — which are emitted up front, unstamped
    (they carry no decode-cadence information).  ``flush_final`` emits any
    remainder from the final materialized output; ``close`` always delivers
    the ``None`` sentinel, success or not."""

    def __init__(self, sink, prompt_len: int, end: int, patch: int,
                 first_row: int, initial_tokens=None, rec=None):
        self.sink = sink
        self.rec = rec
        self.patch = int(patch)
        self.end = int(end)
        self.emitted = min(int(prompt_len), self.end)
        self.next_row = int(first_row)
        self.buf: typing.Dict[int, typing.List[int]] = {}
        self._lock = make_lock("serve.interface._RowStream._lock")
        self._closed = False
        if initial_tokens is not None:
            gap_hi = min(self.next_row * self.patch, self.end)
            if gap_hi > self.emitted:
                self._push(
                    [int(t) for t in initial_tokens[self.emitted:gap_hi]],
                    stamp=False)
                self.emitted = gap_hi

    def _push(self, toks: typing.List[int], stamp: bool = True) -> None:
        if not toks:
            return
        if stamp and self.rec is not None:
            self.rec.mark_token()
        if self.sink is not None:
            self.sink.put(list(toks))

    def on_row(self, pos: int, row_tokens: typing.Sequence[int]) -> None:
        """Callback sink: buffer row ``pos``, release everything in order."""
        with self._lock:
            self.buf[int(pos)] = [int(t) for t in row_tokens]
            while self.next_row in self.buf:
                row = self.buf.pop(self.next_row)
                lo = max(self.emitted, self.next_row * self.patch)
                hi = min((self.next_row + 1) * self.patch, self.end)
                if hi > lo:
                    off = lo - self.next_row * self.patch
                    self._push(row[off:off + (hi - lo)])
                    self.emitted = hi
                self.next_row += 1

    def flush_final(self, out_tokens: typing.Sequence[int]) -> None:
        """Emit whatever the row callbacks did not cover, from the final
        output — makes the stream complete regardless of which rows fired
        (callbacks are best-effort by contract)."""
        with self._lock:
            if self.emitted < self.end:
                self._push([int(t)
                            for t in out_tokens[self.emitted:self.end]])
                self.emitted = self.end

    def close(self) -> None:
        with self._lock:
            if not self._closed and self.sink is not None:
                self._closed = True
                self.sink.put(None)


class CompletionEngine:
    """Jit-compiled prompt completion (the reference's query loop,
    interface.py:177-220, with the padding behavior of ``complete``:
    the prompt is padded to full context with random tokens which the sampler
    overwrites)."""

    def __init__(self, cfg: Config, params: dict,
                 force_rebuild: bool = False,
                 first_token_callback: typing.Optional[
                     typing.Callable] = None,
                 token_callback: typing.Optional[
                     typing.Callable] = None):
        """``force_rebuild`` pins the rebuild-everything sampler even for
        KV-cache-eligible configs (the similarity debug mode exercises the
        production rebuild path, reference interface.py:283-302).

        ``first_token_callback`` (host ``(tag, token)``) arms the serving
        TTFT hook in every sampler this engine compiles: the graph notifies
        the host at the first generated position, carrying the request id
        the ambient :mod:`slo` record supplied.  ``token_callback`` (host
        ``(tag, pos, row)``) arms the per-row streaming hook the same way
        (runtime-gated per request by the traced stream flag, so only
        ``complete_tokens(..., token_sink=...)`` calls ever fire it).
        None (the default, and every non-serving caller) keeps the sampler
        graphs byte-identical to the pre-hook ones."""
        self.cfg = cfg
        self._first_token_cb = first_token_callback
        self._token_cb = token_callback
        from ..models import pipeline_params_stacked, unstack_pipeline_params
        if pipeline_params_stacked(cfg, params):
            # pipeline-trained checkpoints store body params stage-stacked;
            # decode runs the plain sequential chain, so flatten once here
            params = unstack_pipeline_params(cfg, params)
        self.params = params
        self.tokenizer = tokenizer_for(cfg)
        self._force_rebuild = force_rebuild
        # prompt completion is inherently autoregressive: the engine always
        # uses an AR sampler (use_autoregressive_sampling=False only affects
        # the dataset-driven sample run mode, reference inference.py:136-170)
        self._sampler = self._make_sampler(cfg)
        self._samplers: typing.Dict[tuple, typing.Callable] = {}
        self._samplers_lock = make_lock(
            "serve.interface.CompletionEngine._samplers_lock")
        self._rng = jax.random.key(cfg.data_seed)
        self._rng_lock = make_lock(
            "serve.interface.CompletionEngine._rng_lock")

    def _make_sampler(self, cfg: Config):
        from ..infer.kv_cache import cache_eligible, make_cached_text_sampler
        if cache_eligible(cfg) and not self._force_rebuild:
            return make_cached_text_sampler(
                cfg, self.params, first_token_callback=self._first_token_cb,
                token_callback=self._token_cb)
        return make_text_sampler(cfg, self.params,
                                 first_token_callback=self._first_token_cb,
                                 token_callback=self._token_cb)

    def _sampler_for(self, top_k, top_p):
        """Per-request truncation: the knobs are compile-time static, so
        REQUESTED values are BUCKETED (``effective_truncation``) and one
        sampler is compiled and cached per bucket — a handful of
        compilations serves every request mix.  An absent knob keeps the
        config's exact value, un-bucketed."""
        if top_k is None and top_p is None:
            return self._sampler
        cfg = self.cfg
        k, p = effective_truncation(cfg, top_k, top_p)
        if (k, p) == (cfg.sampling_top_k, cfg.sampling_top_p):
            return self._sampler
        # a dedicated lock: a cold-bucket compile must not stall the RNG
        # splits of concurrent knob-free requests
        with self._samplers_lock:
            if (k, p) not in self._samplers:
                import copy
                bcfg = copy.copy(cfg)
                bcfg.sampling_top_k, bcfg.sampling_top_p = k, p
                self._samplers[(k, p)] = self._make_sampler(bcfg)
            return self._samplers[(k, p)]

    def complete_tokens(self, prompt: typing.Sequence[int],
                        temperature: typing.Optional[float] = None,
                        max_tokens: typing.Optional[int] = None,
                        top_k: typing.Optional[int] = None,
                        top_p: typing.Optional[float] = None,
                        token_sink: typing.Optional[
                            "queue.Queue"] = None) -> np.ndarray:
        """Returns the flat token stream (prompt + completion), truncated to
        ``len(prompt) + max_tokens`` tokens.  The sampler works in rows of
        ``token_patch_size`` tokens; the prompt is laid out row-major and the
        loop stops at the last row needed.

        ``token_sink`` (streaming, needs the engine's ``token_callback``
        armed): completion tokens are pushed into the queue in generation
        order WHILE the sampler runs — row-callback chunks, then a final
        remainder, then a ``None`` sentinel (always delivered, success or
        error); the concatenated chunks equal the returned completion."""
        cfg = self.cfg
        patch = cfg.token_patch_size
        rows = cfg.sequence_length // patch
        prompt = list(prompt)[:rows * patch]
        with self._rng_lock:  # web_workers threads share this engine
            self._rng, pad_key, sample_key = jax.random.split(self._rng, 3)
        flat = jax.random.randint(pad_key, (rows * patch,), 0, cfg.vocab_size)
        flat = flat.at[:len(prompt)].set(np.asarray(prompt, np.int32))
        toks = flat.reshape(1, rows, patch)
        prompt_rows = len(prompt) // patch
        if max_tokens is None:
            end_row = rows
        else:
            end_row = min(rows, -(-(len(prompt) + max_tokens) // patch))
        end = (rows * patch if max_tokens is None
               else min(rows * patch, len(prompt) + max_tokens))
        # TTFT hook: route the graph's first-token callback to the ambient
        # request record (set by the InterfaceWrapper worker) via its id —
        # the tag is a TRACED argument, so every request shares one
        # compilation.  Tag 0 = no request / hook unarmed (never dispatched).
        rec = slo.current()
        streaming = token_sink is not None and self._token_cb is not None
        tag = (rec.rid if rec is not None
               and (self._first_token_cb is not None or streaming)
               else (slo.allocate_tag() if streaming else 0))
        if rec is not None:
            rec.tokens_generated = max(0, end - len(prompt))
        if tag and self._first_token_cb is not None and rec is not None:
            slo.register_first_token(tag, rec.mark_first_token)
        rstream = None
        if streaming:
            from ..infer.kv_cache import cache_eligible
            # the KV sampler's loop never rewrites rows before
            # max(initial_pos, 1) (row 0 of an empty prompt is the seed
            # row); the rebuild sampler fires from initial_pos itself
            first_row = (max(prompt_rows, 1)
                         if cache_eligible(cfg) and not self._force_rebuild
                         else prompt_rows)
            rstream = _RowStream(token_sink, len(prompt), end, patch,
                                 first_row,
                                 initial_tokens=np.asarray(flat), rec=rec)
            slo.register_token_sink(tag, rstream.on_row)
        elif token_sink is not None:
            # streaming requested but the engine's token hook is unarmed:
            # degrade to one final chunk (the sentinel contract holds)
            rstream = _RowStream(token_sink, len(prompt), end, patch,
                                 end_row, rec=rec)
        try:
            out = self._sampler_for(top_k, top_p)(
                NT(toks, TEXT_AXES), np.int32(prompt_rows),
                np.float32(cfg.sampling_temperature if temperature is None
                           else temperature),
                sample_key, np.int32(end_row), np.int32(tag),
                np.int32(1 if streaming else 0))
            out = np.asarray(out).reshape(-1)
            if rstream is not None:
                rstream.flush_final(out[:end])
        finally:
            if tag:
                # flush any in-flight debug callback before unrouting
                jax.effects_barrier()
                slo.unregister_first_token(tag)
                if streaming:
                    slo.unregister_token_sink(tag)
            if rstream is not None:
                rstream.close()
        return out[:end]

    def complete_text(self, prompt: str, temperature=None, max_tokens=None,
                      top_k=None, top_p=None) -> str:
        ids = self.tokenizer.encode(prompt)
        out = self.complete_tokens(ids, temperature, max_tokens, top_k, top_p)
        return self.tokenizer.decode(out[len(ids):])


class _Job:
    """One queued completion: callable + args, the 1-slot result queue, the
    ambient SLO record snapshotted at enqueue, and the two state events the
    queue-deadline protocol needs.  ``cancelled`` is only honored while the
    job is still queued — a worker that already set ``started`` finishes
    the engine call (its result is simply dropped; the race window between
    the caller's started-check and the worker's cancelled-check is one
    instruction wide, so the waste is rare and bounded by one request)."""

    __slots__ = ("fn", "args", "out", "rec", "t_enq", "started", "cancelled",
                 "retired")

    def __init__(self, fn, args, rec):
        self.fn = fn
        self.args = args
        self.out: "queue.Queue[tuple]" = queue.Queue(1)
        self.rec = rec
        self.t_enq = time.monotonic()
        self.started = threading.Event()
        self.cancelled = threading.Event()
        self.retired = False  # left the pending count (claimed OR cancelled)


class InterfaceWrapper:
    """Serialized async facade over the engine — the reference's shape,
    and the default serving path; ``serve_max_batch > 1`` swaps it for
    the continuous-batching scheduler (serve/engine.py), which replaces
    the worker-thread queue below with lane admission between decode
    steps.  (Reference interface.py:231-280):
    ``complete(..., asynchronous=True)`` returns a handle whose ``fetch()``
    blocks for the result.  ``workers`` (cfg.web_workers, reference
    rest_api.py:86) sets the number of worker threads; ``fetch`` polls its
    result queue every cfg.default_sleep_duration seconds (the reference's
    Manager-dict poll, interface.py:243).

    Serving-SLO duties (docs/observability.md "Serving SLOs"): the ambient
    request record is stamped at enqueue (queue depth), claim (queue wait
    ends / engine busy starts) and completion (engine busy ends), and
    carried across the thread hop so the engine's TTFT hook can resolve the
    request id.  ``queue_deadline_s``/``queue_limit`` (default: the
    config's ``serve_*`` knobs) bound the wait: a request still unclaimed
    past the deadline — or arriving with ``queue_limit`` jobs already
    waiting — raises :class:`QueueDeadlineExceeded` instead of hanging."""

    def __init__(self, engine: CompletionEngine,
                 workers: typing.Optional[int] = None,
                 sleep_duration: typing.Optional[float] = None,
                 queue_deadline_s: typing.Optional[float] = None,
                 queue_limit: typing.Optional[int] = None):
        self.engine = engine
        cfg = engine.cfg
        self.sleep_duration = (cfg.default_sleep_duration
                               if sleep_duration is None else sleep_duration)
        self.queue_deadline_s = float(
            getattr(cfg, "serve_queue_deadline_s", 0.0)
            if queue_deadline_s is None else queue_deadline_s)
        self.queue_limit = int(getattr(cfg, "serve_queue_limit", 0)
                               if queue_limit is None else queue_limit)
        n = max(1, int(cfg.web_workers if workers is None else workers))
        self._q: "queue.Queue[typing.Optional[_Job]]" = queue.Queue()
        # live backlog, not _q.qsize(): deadline-cancelled jobs stay in _q
        # until a worker pops them, and counting those corpses would shed
        # healthy arrivals, inflate hbnlp_serve_queue_depth, and overprice
        # Retry-After for as long as the workers stay busy
        self._pending = 0
        self._pending_lock = make_lock(
            "serve.interface.InterfaceWrapper._pending_lock")
        self._threads = []
        for _ in range(n):
            t = threading.Thread(target=self._worker, daemon=True)
            t.start()
            self._threads.append(t)

    def queue_depth(self) -> int:
        with self._pending_lock:
            return self._pending

    def _retire(self, job: _Job) -> None:
        # exactly-once under the claim/cancel race (worker sets started
        # while fetch sets cancelled): whoever gets here first counts
        with self._pending_lock:
            if not job.retired:
                job.retired = True
                self._pending -= 1

    def _worker(self):
        while True:
            job = self._q.get()
            if job is None:
                self._q.put(None)  # let sibling workers drain too
                return
            self._retire(job)
            if job.cancelled.is_set():
                continue  # caller gave up while queued (deadline 503)
            job.started.set()
            rec = job.rec
            # the record travels with the job: the engine (this thread)
            # resolves slo.current() for the TTFT tag
            prev = slo.set_current(rec)
            if rec is not None:
                rec.mark_started()
            try:
                result = ("ok", job.fn(*job.args))
            except Exception as e:  # propagate to caller
                result = ("err", e)
            # engine-done must be stamped BEFORE the result is published:
            # the handler's finish() runs the instant fetch() wakes, and an
            # unstamped record silently drops its engine/decode observations
            if rec is not None:
                rec.mark_engine_done()
            slo.set_current(prev)
            job.out.put(result)

    def complete(self, prompt: typing.Sequence[int], temperature: float = 0.0,
                 response_len: int = 64, asynchronous: bool = False,
                 top_k: typing.Optional[int] = None,
                 top_p: typing.Optional[float] = None,
                 token_sink: typing.Optional["queue.Queue"] = None):
        depth = self.queue_depth()
        if self.queue_limit and depth >= self.queue_limit:
            raise QueueDeadlineExceeded(0.0, self.queue_deadline_s, depth,
                                        shed=True)
        rec = slo.current()
        if rec is not None:
            rec.mark_enqueued(queue_depth=depth)
        args = (prompt, temperature, response_len, top_k, top_p)
        if token_sink is not None:
            # streamed completions ride the same worker queue; the engine
            # delivers chunks + the None sentinel through the sink while
            # the job runs (complete_tokens' sentinel contract)
            args = args + (token_sink,)
        job = _Job(self.engine.complete_tokens, args, rec)
        with self._pending_lock:
            self._pending += 1
        self._q.put(job)
        deadline = self.queue_deadline_s

        def fetch():
            while True:
                try:
                    status, value = job.out.get(timeout=self.sleep_duration)
                    break
                except queue.Empty:
                    waited = time.monotonic() - job.t_enq
                    if (deadline and waited > deadline
                            and not job.started.is_set()):
                        job.cancelled.set()
                        self._retire(job)
                        raise QueueDeadlineExceeded(waited, deadline,
                                                    self.queue_depth())
                    continue
            if status == "err":
                raise value
            return value

        def cancel():
            # honored while queued (a worker drops cancelled jobs unrun);
            # a started job finishes its serialized engine call — this
            # wrapper decodes one request at a time, so there is no lane
            # or KV pool to reclaim early (BatchInterface has the real
            # mid-decode reap)
            job.cancelled.set()
            self._retire(job)

        fetch.cancel = cancel
        return fetch if asynchronous else fetch()

    def close(self):
        self._q.put(None)
