"""Experts applied to the (token, expert) pairs a router selected, group by
group, with nothing dropped.

The pairs that fell on an expert held here are sorted by expert, so each
expert's pairs are one run of rows, and the runs are multiplied with their
experts' matrices by ``ops/pallas_gmm.py::grouped_dot`` (one grouped product
over all the rows: its cost follows the rows, not rows times experts).  At
lane-tile widths and enough rows that is a pair of Mosaic kernels, which ask
that every run start on a multiple of their row tile: the rows are laid out
so (``tile``, from ``pallas_gmm.row_tile``; a run's last tile is filled with
zero rows of weight zero, at most one tile an expert).  Every other shape
keeps ``jax.lax.ragged_dot`` and ``tile`` 1, runs end to end.  The pairs are
taken ``chunk`` at a time, in a loop as long as the held pairs need, read on
the device.  ``chunk`` follows the share of the experts held here
(``models/hybrid.py::expert_chunk``: four times the load a balanced router
gives this share, all pairs at most) and every chunk is multiplied whole
(the rows past the last run are zero rows too), so one chunk is the rule,
its time does not follow the routing, and a second one runs only when the
imbalance asks for it: every pair is computed whatever the load, and no
``[tokens, experts, capacity]`` tensor stands for it.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from .pallas_gmm import aligned_rows


class Routing(typing.NamedTuple):
    """The selected pairs, those on held experts first and sorted by
    expert."""
    pair: jnp.ndarray        # [P] pair ids (token * k + slot), sorted
    counts: jnp.ndarray      # [held] pairs of each held expert


def route(expert: jnp.ndarray, offset: int, held: int) -> Routing:
    """Sort the pairs of ``expert [tokens, k]`` (ids among all experts) that
    fell on experts ``offset .. offset + held``."""
    local = expert.reshape(-1) - offset
    local = jnp.where((local >= 0) & (local < held), local, held)
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    return Routing(jnp.argsort(local, stable=True).astype(jnp.int32), counts)


def _zeros(shape, like: typing.Sequence[jnp.ndarray]):
    """A float32 loop carry of zeros that varies over the manual mesh axes
    `like` varies over (inside `shard_map` a carry's type says so)."""
    varying = frozenset().union(*(getattr(jax.typeof(a), "vma", frozenset())
                                  for a in like))
    zeros = jnp.zeros(shape, jnp.float32)
    return jax.lax.pcast(zeros, tuple(varying), to="varying") if varying \
        else zeros


def _chunk_rows(routing: Routing, combine, first, chunk: int, tile: int):
    """(token row, combine weight, expert run lengths, pair id, is a pair)
    of the rows that hold the chunk of sorted pairs that starts at ``first``.
    Every expert's run starts on a multiple of ``tile`` rows and is a whole
    number of tiles long; a row that holds no pair (between a run's last
    pair and the next run, or past the last run) points at the spare row
    ``tokens`` with weight zero."""
    tokens, k = combine.shape
    held = routing.counts.shape[0]
    ends = jnp.cumsum(routing.counts)
    sizes = (jnp.clip(ends - first, 0, chunk)
             - jnp.clip(ends - routing.counts - first, 0, chunk))
    runs = -(-sizes // tile) * tile
    starts = jnp.cumsum(runs) - runs
    at = jnp.arange(aligned_rows(chunk, held, tile))
    reached = at[:, None] >= starts[None, 1:]

    def of_run(value):
        """``value [held]`` of each row's run, with no gather: a sum of the
        steps at the runs' starts."""
        return value[0] + jnp.sum(jnp.where(reached, jnp.diff(value), 0), -1)

    real = at < of_run(starts + sizes)
    source = at + of_run(first + jnp.cumsum(sizes) - sizes - starts)
    pair = routing.pair[jnp.where(real, source, 0)]
    # the rows past the last run are zero rows: counted into the last run,
    # the product takes a whole chunk whatever the routing, and its time
    # stays the same from one batch to the next
    runs = runs.at[-1].add(at.shape[0] - jnp.sum(runs))
    return (jnp.where(real, pair // k, tokens),
            jnp.where(real, combine.reshape(-1)[pair], 0), runs, pair, real)


def _chunks(routing: Routing, chunk: int):
    """How many chunks hold a pair on a held expert."""
    return -(-jnp.sum(routing.counts) // chunk)


def rows_multiplied(routing: Routing, chunk: int, tile: int):
    """Rows the loop multiplies this step: its trips times a chunk's rows,
    those that hold no pair included."""
    return _chunks(routing, chunk) * aligned_rows(
        chunk, routing.counts.shape[0], tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def grouped_ffn(ffn, chunk, tile, x, weights, combine, routing: Routing):
    """``y[t] = sum over the selected pairs (t, e) of combine[t, slot] *
    ffn_e(x[t])`` for the held experts ``e``.

    ``ffn(rows, sizes, *matrices) -> rows`` applies every expert to its run
    of ``sizes[e]`` rows, a multiple of ``tile``
    (``ops/pallas_gmm.py::grouped_dot``); ``x [tokens, D]``; ``weights`` a
    tuple of stacks ``[held, ...]``; ``combine [tokens, k]``.  The loop is
    as long as the routing made it, which no automatic transpose takes: the
    backward is a second loop over the same chunks around ``jax.vjp``."""
    return _forward(ffn, chunk, tile, x, weights, combine, routing)


def _forward(ffn, chunk, tile, x, weights, combine, routing):
    tokens = combine.shape[0]
    padded = jnp.concatenate([x, jnp.zeros_like(x[:1])])

    def step(i, y):
        token, w, sizes, _, _ = _chunk_rows(routing, combine, i * chunk, chunk,
                                            tile)
        out = ffn(padded[token], sizes, *weights)
        return y.at[token].add(out.astype(jnp.float32) * w[:, None])

    y = jax.lax.fori_loop(0, _chunks(routing, chunk), step,
                          _zeros(padded.shape, (x, combine) + tuple(weights)))
    return y[:tokens].astype(x.dtype)


def _fwd(ffn, chunk, tile, x, weights, combine, routing):
    return (_forward(ffn, chunk, tile, x, weights, combine, routing),
            (x, weights, combine, routing))


def _bwd(ffn, chunk, tile, saved, dy):
    x, weights, combine, routing = saved
    tokens, k = combine.shape
    padded = jnp.concatenate([x, jnp.zeros_like(x[:1])])
    dy = jnp.concatenate([dy, jnp.zeros_like(dy[:1])])
    like = (x, combine, dy) + tuple(weights)

    def step(i, carry):
        dx, dweights, dcombine = carry
        token, w, sizes, pair, real = _chunk_rows(routing, combine, i * chunk,
                                                  chunk, tile)
        out, back = jax.vjp(lambda rows, *mats: ffn(rows, sizes, *mats),
                            padded[token], *weights)
        dout = dy[token]
        drows, *dmats = back(dout * w[:, None].astype(dout.dtype))
        dw = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32), -1)
        # rows past the last pair hold a stale id: a spare slot takes them
        dcombine = dcombine.at[jnp.where(real, pair, tokens * k)].add(
            jnp.where(real, dw, 0))
        return (dx.at[token].add(drows.astype(jnp.float32)),
                tuple(d + g.astype(jnp.float32)
                      for d, g in zip(dweights, dmats)), dcombine)

    # every sum over chunks is kept in float32, whatever the operands' type
    dx, dweights, dcombine = jax.lax.fori_loop(
        0, _chunks(routing, chunk), step,
        (_zeros(padded.shape, like),
         tuple(_zeros(m.shape, like) for m in weights),
         _zeros((tokens * k + 1,), like)))
    return (dx[:tokens].astype(x.dtype),
            tuple(d.astype(m.dtype) for d, m in zip(dweights, weights)),
            dcombine[:-1].reshape(tokens, k).astype(combine.dtype), None)


grouped_ffn.defvjp(_fwd, _bwd)
