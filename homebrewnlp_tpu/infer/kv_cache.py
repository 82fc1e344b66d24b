"""KV-cache incremental decoding — the inference fast path the reference
lacks (its while-loop sampler rebuilds the full forward per token,
/root/reference/src/run/inference.py:75-124; SURVEY.md §7 item 7 names the
cache as the intended improvement).

Eligibility: every sequence-mixing layer must be an ``attention`` layer —
causal ``dot_product`` (K/V cached) or the learned-map family
(``biased_softmax`` / ``biased_attention_map`` / ``scale_attention_map``,
the flagship mixer: V cached, map rows gathered per step —
models/layers.py::_cached_attention).  cumsum/cummean, convolution and
transpose_sequence_features carry different cross-position state and keep
the rebuild-everything sampler (infer/sampler.py).

The cached sampler PREFILLS the prompt with one full-length forward that
writes every prompt position's K/V at once, then runs one model call per
generated position on a length-1 row: attention layers write the row's K/V
into per-layer caches (models/layers.py::_cached_attention) and attend over
the cached prefix, so a full sample costs one full forward plus
O(generated) length-1 forwards instead of O(seq) full-length forwards.  Greedy (temperature 0) token outputs match the rebuild sampler:
both paths compute the same math, differing only in XLA fusion order, so
logits agree to float-rounding (measured <= 4e-3 absolute at seq 512 with
random weights, argmax identical at every teacher-forced position); a
randomly-initialized model whose top-2 logits tie within that noise can
still diverge mid-rollout.  Stochastic sampling draws an equivalent but
differently-shaped Gumbel noise stream.
"""
from __future__ import annotations

import logging
import typing

import jax
import jax.numpy as jnp

from ..config import Config, SEQUENCE
from ..models import build
from ..models.ctx import Ctx, DecodeState
from ..nd import NT
from .sampler import _gumbel_argmax
from ..sync import make_lock

_SEQUENCE_MIXERS = ("cumsum", "cummean", "convolution",
                    "transpose_sequence_features")
_MAP_FLAGS = ("biased_softmax", "biased_attention_map", "scale_attention_map")
# mixers that train here and are not served yet (ROADMAP R4, R5): what a
# cache for each would have to hold
_NO_CACHE_YET = {
    "kda": "decoding needs a state cache (one [d_k, d_v] state a head and "
           "the last taps of q, k and v of each short convolution); without "
           "it every token rebuilds the whole sequence",
    "mla": "decoding needs a latent cache (the normed latent and the shared "
           "key part of every position, with the up-projection absorbed "
           "into q and the output; under mla-rope the shared key part "
           "rotated at its own position when it is written, and the "
           "decoded token's q_pe at its offset); without it every token "
           "rebuilds the whole sequence",
    "gqa": "decoding needs a cache of the K/V heads alone (k and v of "
           "num_key_value_heads heads a position, k rotated at its own "
           "offset when it is written), a ring of sliding_window positions "
           "for the sliding layers beside a whole one for the full layers "
           "(a layer without positions, gqa-nope, writes k as it is; a "
           "gated one reads its gate from the decoded token's own input "
           "and caches nothing for it; a sparse one, gqa-...-sparse, also "
           "the indexer's key of every position, which the decoded token's "
           "indexer scores to keep its top-k rows); without it every token "
           "rebuilds the whole sequence",
}


def cache_eligible(cfg: Config) -> bool:
    """True when the config's whole layer stack decodes against a KV cache."""
    if cfg.use_video:
        return False
    # use_initial_position_embedding is cache-compatible: the body builds
    # the table full-length and slices the decoded rows at ctx.decode.pos
    # (models/__init__.py::_body), same as attention's positional keys
    for block in (list(cfg.input_block_config) + list(cfg.block_config)
                  + list(cfg.output_block_config)):
        for spec in block.layer:
            parts = spec.replace(":", "-").split("-")
            name = parts[0]
            if name in _SEQUENCE_MIXERS:
                return False
            if name in _NO_CACHE_YET:
                logging.getLogger(__name__).info(
                    "layer %s: %s", name, _NO_CACHE_YET[name])
                return False
            if name == "attention":
                # dot_product caches K/V; the learned-map family caches V and
                # gathers map rows (flagship mixer).  input_as_value is
                # positionwise — cacheable under either.  An attention with
                # neither flag family raises in the layer itself.
                if "dot_product" not in parts and not any(
                        f in parts for f in _MAP_FLAGS):
                    return False
                if any(f in parts for f in _MAP_FLAGS) and 0 not in tuple(
                        cfg.masked_attention_dimensions):
                    # an UNMASKED map attends to future positions; the cache
                    # holds stale prefill values there while the rebuild
                    # sampler recomputes them per step — silent divergence,
                    # so unmasked map layers keep the rebuild path.  (The
                    # pure dot-product softmax is causal unconditionally,
                    # reference spatial.py:68, hence exempt.)
                    return False
    return True


def _decode_logits(cfg: Config, params: dict, row: jnp.ndarray,
                   pos, caches: typing.Dict[str, tuple], seq: int,
                   names: typing.Tuple[str, ...]
                   ) -> typing.Tuple[jnp.ndarray, typing.Dict[str, tuple]]:
    """One incremental step: logits for the single row at ``pos`` plus the
    updated caches."""
    dc = DecodeState(pos, dict(caches), seq)
    ctx = Ctx(cfg, params=params, train=False, rng=None, decode=dc)
    batch = {"token_x": NT(row, names),
             "token_y": NT(jnp.zeros_like(row), names)}
    out = build(ctx, batch)
    return out.token_out.x, dc.caches


def cache_shapes(cfg: Config, params: dict, batch_size: int,
                 seq: typing.Optional[int] = None
                 ) -> typing.Dict[str, tuple]:
    """Abstract per-layer cache shapes (``{layer: (ShapeDtypeStruct, ...)}``)
    for a ``batch_size`` x ``seq`` decode, discovered by abstract evaluation
    of one decode step — no FLOPs run and no memory allocated, so the static
    cost model (analysis/cost_model.py) prices serving KV HBM for any
    batch x context point without touching a device.  ``params`` may be
    ShapeDtypeStructs."""
    seq = cfg.sequence_length // cfg.token_patch_size if seq is None else seq
    names = ("batch", SEQUENCE, "language_token_patch")
    row = jax.ShapeDtypeStruct((batch_size, 1, cfg.token_patch_size), jnp.int32)

    def probe(params):
        return _decode_logits(cfg, params, jnp.zeros(row.shape, row.dtype),
                              jnp.int32(0), {}, seq, names)[1]

    return jax.eval_shape(probe, params)


def cache_nbytes(shapes: typing.Dict[str, tuple]) -> int:
    """Total bytes of a cache pytree from :func:`cache_shapes` — the
    KV-cache term of the per-device HBM prediction (caches follow the
    batch's data sharding, so divide by the data-axis size separately)."""
    import numpy as np
    total = 0
    for kv in shapes.values():
        for s in kv:
            n = 1
            for d in s.shape:
                n *= int(d)
            total += n * np.dtype(s.dtype).itemsize
    return int(total)


def init_caches(cfg: Config, params: dict, batch_size: int,
                seq: typing.Optional[int] = None
                ) -> typing.Dict[str, tuple]:
    """Zeroed cache pytree, discovered by abstract evaluation of one decode
    step (no FLOPs run)."""
    shapes = cache_shapes(cfg, params, batch_size, seq)
    return {k: tuple(jnp.zeros(s.shape, s.dtype) for s in kv)
            for k, kv in shapes.items()}


def block_rows(cfg: Config) -> int:
    """Decode rows (``token_patch_size`` tokens each) per KV-pool block.
    ``serve_block_tokens=0`` means one whole-sequence block, which makes
    the pool byte-identical to the monolithic per-lane cache."""
    rows = cfg.sequence_length // cfg.token_patch_size
    if not getattr(cfg, "serve_block_tokens", 0):
        return rows
    return max(1, min(rows, cfg.serve_block_tokens // cfg.token_patch_size))


def blocks_per_sequence(cfg: Config) -> int:
    """Blocks a full-length request occupies (admission takes the whole
    footprint up front — the engine never grows a request mid-decode)."""
    rows = cfg.sequence_length // cfg.token_patch_size
    return -(-rows // block_rows(cfg))


def pool_blocks(cfg: Config) -> int:
    """Effective pool capacity in blocks: ``serve_kv_blocks`` when set,
    else the physical pool (``serve_max_batch`` lanes x blocks/sequence)."""
    return (getattr(cfg, "serve_kv_blocks", 0)
            or getattr(cfg, "serve_max_batch", 1) * blocks_per_sequence(cfg))


def pool_shapes(cfg: Config, params: dict,
                seq: typing.Optional[int] = None) -> typing.Dict[str, tuple]:
    """Abstract shapes of the engine's pooled caches — ``cache_shapes`` at
    a batch of ``serve_max_batch`` lanes (``params`` may be
    ShapeDtypeStructs; nothing runs)."""
    return cache_shapes(cfg, params, getattr(cfg, "serve_max_batch", 1), seq)


def pool_nbytes(cfg: Config, params: dict,
                seq: typing.Optional[int] = None) -> int:
    """Bytes of the block-allocated KV pool under the serve knobs: the
    allocator's block geometry (``pool_blocks x block_rows``) times the
    per-row cache bytes summed over layers — the ``kv`` term the static
    cost model prices for serving (analysis/cost_model.py).  Defaults
    (one lane, whole-sequence blocks) equal the monolithic batch-1 cache
    exactly."""
    rows = (cfg.sequence_length // cfg.token_patch_size if seq is None
            else int(seq))
    per_row = cache_nbytes(cache_shapes(cfg, params, 1, rows)) / max(1, rows)
    return int(round(pool_blocks(cfg) * block_rows(cfg) * per_row))


def lane_view(caches: typing.Dict[str, tuple], lane) -> typing.Dict[str, tuple]:
    """One lane's rows of every pooled cache as batch-1 arrays
    (``dynamic_slice`` at a traced lane index) — the per-lane cache a
    chunk-granular prefill forward runs against
    (serve/engine.py::prefill_chunk_body)."""
    out = {}
    for name, kv in caches.items():
        out[name] = tuple(
            jax.lax.dynamic_slice(p, (lane,) + (0,) * (p.ndim - 1),
                                  (1,) + p.shape[1:])
            for p in kv)
    return out


def write_lane_rows(caches: typing.Dict[str, tuple],
                    lane_caches: typing.Dict[str, tuple],
                    lane, start_row, n_rows: int) -> typing.Dict[str, tuple]:
    """Scatter ``n_rows`` cache rows (sequence axis 1) of the batch-1
    ``lane_caches`` into lane ``lane`` of the pooled caches at row
    ``start_row`` — the chunk-granular write over the block pool: only the
    chunk's rows move, every other lane's (and the lane's own other) blocks
    are byte-untouched, so chunked and monolithic prefill leave identical
    cache prefixes."""
    out = {}
    for name, kv in caches.items():
        updated = []
        for pool, one in zip(kv, lane_caches[name]):
            rows = jax.lax.dynamic_slice_in_dim(one, start_row, n_rows, 1)
            updated.append(jax.lax.dynamic_update_slice(
                pool, jnp.asarray(rows, pool.dtype),
                (lane, start_row) + (0,) * (pool.ndim - 2)))
        out[name] = tuple(updated)
    return out


class BlockAllocator:
    """Fixed-capacity KV-pool accountant (docs/observability.md
    "Continuous batching"): ``n_blocks`` blocks of ``block_tokens`` tokens,
    handed out per request at ADMISSION (the whole footprint — prompt +
    response — is known up front, so a request never grows mid-decode) and
    recycled on completion.  Blocks are fungible — any block serves any
    lane — so the free list cannot fragment: an allocation succeeds iff
    enough blocks are free, regardless of the alloc/free history.

    Thread-safe: the scheduler thread allocates/frees while the admission
    path and the ``hbnlp_serve_kv_blocks_free`` gauge probe read."""

    def __init__(self, n_blocks: int, block_tokens: int):
        if n_blocks < 1:
            raise ValueError("BlockAllocator needs n_blocks >= 1")
        if block_tokens < 1:
            raise ValueError("BlockAllocator needs block_tokens >= 1")
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self._lock = make_lock("infer.kv_cache.BlockAllocator._lock")
        # LIFO free list: a finishing request's blocks go straight to the
        # next admission (warm reuse), and ids stay stable for tests
        self._free = list(range(self.n_blocks - 1, -1, -1))
        self._held: typing.Dict[typing.Hashable, typing.Tuple[int, ...]] = {}

    def blocks_needed(self, tokens: int) -> int:
        return max(1, -(-max(0, int(tokens)) // self.block_tokens))

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def held(self, owner: typing.Hashable) -> typing.Tuple[int, ...]:
        with self._lock:
            return self._held.get(owner, ())

    def fits(self, tokens: int) -> bool:
        """Whether a ``tokens``-long request could EVER be admitted (its
        footprint fits the whole pool) — the admission path sheds
        impossible requests immediately instead of queueing them forever."""
        return self.blocks_needed(tokens) <= self.n_blocks

    def alloc(self, owner: typing.Hashable, tokens: int
              ) -> typing.Optional[typing.Tuple[int, ...]]:
        """Take ``blocks_needed(tokens)`` blocks for ``owner``; None when
        the pool is too empty right now (caller keeps the request queued).
        One live allocation per owner."""
        need = self.blocks_needed(tokens)
        with self._lock:
            if owner in self._held:
                raise ValueError(f"owner {owner!r} already holds blocks")
            if need > len(self._free):
                return None
            ids = tuple(self._free.pop() for _ in range(need))
            self._held[owner] = ids
            return ids

    def free(self, owner: typing.Hashable) -> int:
        """Recycle ``owner``'s blocks; returns how many came back (0 for
        an unknown owner — freeing twice is a no-op, not a leak)."""
        with self._lock:
            ids = self._held.pop(owner, ())
            self._free.extend(ids)
            return len(ids)


def make_cached_text_sampler(cfg: Config, params: dict,
                             first_token_callback: typing.Optional[
                                 typing.Callable] = None,
                             token_callback: typing.Optional[
                                 typing.Callable] = None):
    """Jitted KV-cached sampler with the same signature as
    ``make_text_sampler``: (token_x NT, initial_pos, temperature, rng,
    end_iterations[, first_token_tag[, stream]]) -> int32 tokens.

    ``first_token_callback``: the serving-SLO TTFT hook (host
    ``(tag, token)``), fired exactly once — on the FIRST generated
    position, i.e. after the one-shot prompt prefill above has run — so
    TTFT measured here covers prefill + first incremental step, matching
    the rebuild sampler's semantics.  ``token_callback`` (host
    ``(tag, pos, row)``): the per-row streaming hook, fired on every
    written row when the traced ``stream`` flag is set (same traced-tag
    design — one compilation serves streaming and buffered requests)."""
    if not cache_eligible(cfg):
        raise ValueError("config is not KV-cache eligible; use make_text_sampler")

    def fn(params, token_x: NT, initial_pos, temperature, rng,
           end_iterations=None, first_token_tag=0, stream=0):
        names = token_x.names
        toks = token_x.x.astype(jnp.int32)
        seq_axis = names.index(SEQUENCE)
        assert seq_axis == 1, "cached decode expects [batch, sequence, patch]"
        seq = toks.shape[seq_axis]
        end = jnp.int32(seq) if end_iterations is None else end_iterations
        caches = init_caches(cfg, params, toks.shape[0], seq)
        # PREFILL: one full-length forward writes every position's K/V in a
        # single pass, so the incremental loop below starts at the end of the
        # prompt instead of decoding it token by token.  Rows past the prompt
        # hold padding K/V, but each is rewritten by the loop at its own
        # position before any later query can see it causally.  An empty
        # prompt (initial_pos 0) has nothing to prefill — the loop generates
        # every row anyway, so skip the full-length forward entirely.
        caches = jax.lax.cond(
            jnp.int32(initial_pos) > 0,
            lambda c: _decode_logits(cfg, params, toks, jnp.int32(0), c,
                                     seq, names)[1],
            lambda c: c, caches)
        start = jnp.maximum(jnp.int32(initial_pos) - 1, 0)

        def body(carry):
            pos, toks, caches, key = carry
            key, sub = jax.random.split(key)
            row = jax.lax.dynamic_slice_in_dim(toks, pos, 1, seq_axis)
            logits, caches = _decode_logits(cfg, params, row, pos, caches,
                                            seq, names)
            sampled = _gumbel_argmax(logits, jnp.float32(temperature), sub,
                                     cfg.sampling_top_k, cfg.sampling_top_p)
            # the sampled row is the prediction for position pos+1; write it
            # only into sampleable positions [initial_pos, end)
            nxt = pos + 1
            write = (nxt >= initial_pos) & (nxt < end) & (nxt < seq)
            cur = jax.lax.dynamic_slice_in_dim(toks, jnp.minimum(nxt, seq - 1),
                                               1, seq_axis)
            new_row = jnp.where(write, sampled.astype(toks.dtype), cur)
            toks = jax.lax.dynamic_update_slice_in_dim(
                toks, new_row, jnp.minimum(nxt, seq - 1), seq_axis)
            if first_token_callback is not None:
                # the first generated position is max(initial_pos, 1): the
                # loop starts one row early (start = initial_pos - 1) to
                # source the last prompt row's logits, and an empty prompt
                # generates from row 1 (row 0 is the random-pad seed row)
                from .sampler import _fire_first_token
                _fire_first_token(
                    first_token_callback, first_token_tag,
                    write & (nxt == jnp.maximum(jnp.int32(initial_pos), 1)),
                    new_row)
            if token_callback is not None:
                from .sampler import _fire_token_row
                _fire_token_row(
                    token_callback, first_token_tag,
                    write & (jnp.asarray(stream, jnp.int32) != 0),
                    nxt, new_row)
            return nxt, toks, caches, key

        def cond(carry):
            pos = carry[0]
            return pos < end - 1

        _, out, _, _ = jax.lax.while_loop(
            cond, body, (start, toks, caches, rng))
        return out

    from .sampler import jit_bound
    return jit_bound(fn, params)
