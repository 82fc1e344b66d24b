"""Experts applied to the (token, expert) pairs a router selected, group by
group, with nothing dropped.

The pairs that fell on an expert held here are sorted by expert, so each
expert's pairs are one run of rows, and the runs are multiplied with their
experts' matrices by ``ops/pallas_gmm.py::grouped_dot`` (one grouped product
over all the rows: its cost follows the rows, not rows times experts).  At
lane-tile widths and half a row tile of pairs an expert or more that is a
pair of Mosaic kernels, which ask
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

Rows go out and come back by gathers alone.  A chunk's rows are the tokens'
rows gathered in sorted order; its results return through the sort's
inverse: every token has ``k`` pairs and the sort is a permutation, so a
token's result is the sum of ``k`` rows whose places are known
(``_rows_of_pairs``), a gather a slot summed in float32 in slot order
(``_sum_of_pairs``).  On the chip a scatter-add of a chunk's rows into the
tokens' costs a sort of its own and a float32 copy of the rows, two and a
half times the gathers' time at 135,168 rows (PERF.md, PR 34), and leaves
the order of a token's addends open; the gathers make the result a function
of the inputs alone.
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from .pallas_gmm import aligned_rows


class Routing(typing.NamedTuple):
    """The selected pairs, those on held experts first and sorted by
    expert, and the way back from the sorted order."""
    pair: jnp.ndarray        # [P] pair ids (token * k + slot), sorted
    counts: jnp.ndarray      # [held] pairs of each held expert
    rank: jnp.ndarray        # [P] each pair's place in ``pair``, its inverse
    local: jnp.ndarray       # [P] each pair's held expert, ``held`` if none


def route(expert: jnp.ndarray, offset: int, held: int) -> Routing:
    """Sort the pairs of ``expert [tokens, k]`` (ids among all experts) that
    fell on experts ``offset .. offset + held``."""
    local = expert.reshape(-1) - offset
    local = jnp.where((local >= 0) & (local < held), local, held)
    counts = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    pair = jnp.argsort(local, stable=True).astype(jnp.int32)
    # the sort is a permutation, so sorting it gives its inverse (a scatter
    # of the places takes three times as long on the chip)
    rank = jnp.argsort(pair).astype(jnp.int32)
    return Routing(pair, counts, rank, local.astype(jnp.int32))


def _zeros(shape, like: typing.Sequence[jnp.ndarray]):
    """A float32 loop carry of zeros that varies over the manual mesh axes
    `like` varies over (inside `shard_map` a carry's type says so)."""
    varying = frozenset().union(*(getattr(jax.typeof(a), "vma", frozenset())
                                  for a in like))
    zeros = jnp.zeros(shape, jnp.float32)
    return jax.lax.pcast(zeros, tuple(varying), to="varying") if varying \
        else zeros


def _runs(routing: Routing, first, chunk: int, tile: int):
    """Of each held expert's run in the chunk of sorted pairs that starts at
    ``first``: (pairs, rows, first row, pairs of the chunk before it).  Every
    run starts on a multiple of ``tile`` rows and is a whole number of tiles
    long."""
    ends = jnp.cumsum(routing.counts)
    before = jnp.clip(ends - routing.counts - first, 0, chunk)
    sizes = jnp.clip(ends - first, 0, chunk) - before
    runs = -(-sizes // tile) * tile
    return sizes, runs, jnp.cumsum(runs) - runs, before


def _of_run(value, reached):
    """``value [held]`` of the run each row (or pair) lies in, with no
    gather: a sum of the steps at the runs it has ``reached [n, held - 1]``."""
    return value[0] + jnp.sum(jnp.where(reached, jnp.diff(value), 0), -1)


def _chunk_rows(routing: Routing, combine, first, chunk: int, tile: int):
    """(token row, combine weight, expert run lengths, pair id, is a pair)
    of the rows that hold the chunk of sorted pairs that starts at ``first``,
    laid out as ``_runs`` says; a row that holds no pair (between a run's
    last pair and the next run, or past the last run) points at the spare
    row ``tokens`` with weight zero."""
    tokens, k = combine.shape
    held = routing.counts.shape[0]
    sizes, runs, starts, before = _runs(routing, first, chunk, tile)
    at = jnp.arange(aligned_rows(chunk, held, tile))
    reached = at[:, None] >= starts[None, 1:]
    real = at < _of_run(starts + sizes, reached)
    source = at + _of_run(first + before - starts, reached)
    pair = routing.pair[jnp.where(real, source, 0)]
    # the rows past the last run are zero rows: counted into the last run,
    # the product takes a whole chunk whatever the routing, and its time
    # stays the same from one batch to the next
    runs = runs.at[-1].add(at.shape[0] - jnp.sum(runs))
    return (jnp.where(real, pair // k, tokens),
            jnp.where(real, combine.reshape(-1)[pair], 0), runs, pair, real)


def _rows_of_pairs(routing: Routing, first, chunk: int, tile: int, k: int):
    """``[tokens, k]``: the row ``_chunk_rows`` gave each pair in the chunk
    that starts at ``first``; a pair on no held expert or in another chunk
    points past the last row (``aligned_rows``), where ``_sum_of_pairs``
    reads zeros."""
    held = routing.counts.shape[0]
    _, _, starts, before = _runs(routing, first, chunk, tile)
    # a pair's expert is known, so its run is too, with no gather
    reached = routing.local[:, None] >= jnp.arange(1, held)[None]
    place = routing.rank - first
    row = place + _of_run(starts - before, reached)
    inside = (routing.local < held) & (place >= 0) & (place < chunk)
    return jnp.where(inside, row, aligned_rows(chunk, held, tile)).reshape(
        -1, k)


def _sum_of_pairs(rows, row_of_pair, weight=None):
    """``[tokens, ...]`` float32: each token's ``k`` rows of ``rows`` summed
    in slot order, ``weight [tokens, k]`` on them if given; a pair that
    points past the last row adds zero."""
    total = None
    for slot in range(row_of_pair.shape[1]):
        term = rows.at[row_of_pair[:, slot]].get(
            mode="fill", fill_value=0).astype(jnp.float32)
        if weight is not None:
            term = term * weight[:, slot, None].astype(jnp.float32)
        total = term if total is None else total + term
    return total


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
    tuple of stacks ``[held, ...]``; ``combine [tokens, k]``.  A chunk's
    results are widened to float32, weighted, summed a token in slot order
    and over the chunks, and rounded once.  The loop is as long as the
    routing made it, which no automatic transpose takes: the backward is a
    second loop over the same chunks around ``jax.vjp``, the rows' gradients
    summed a token the same way."""
    return _forward(ffn, chunk, tile, x, weights, combine, routing)


def _forward(ffn, chunk, tile, x, weights, combine, routing):
    k = combine.shape[1]
    padded = jnp.concatenate([x, jnp.zeros_like(x[:1])])

    def step(i, y):
        token, _, sizes, _, _ = _chunk_rows(routing, combine, i * chunk, chunk,
                                            tile)
        out = ffn(padded[token], sizes, *weights)
        return y + _sum_of_pairs(
            out, _rows_of_pairs(routing, i * chunk, chunk, tile, k), combine)

    y = jax.lax.fori_loop(0, _chunks(routing, chunk), step,
                          _zeros(x.shape, (x, combine) + tuple(weights)))
    return y.astype(x.dtype)


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
        # a scatter of scalars, each pair written once (as a gather through
        # `_rows_of_pairs` it reads every pair, held or not: no faster at
        # any shape, four times as slow where few pairs are held); rows past
        # the last pair hold a stale id: a spare slot takes them
        dcombine = dcombine.at[jnp.where(real, pair, tokens * k)].add(
            jnp.where(real, dw, 0))
        row_of_pair = _rows_of_pairs(routing, i * chunk, chunk, tile, k)
        return (dx + _sum_of_pairs(drows, row_of_pair),
                tuple(d + g.astype(jnp.float32)
                      for d, g in zip(dweights, dmats)), dcombine)

    # every sum over chunks is kept in float32, whatever the operands' type
    dx, dweights, dcombine = jax.lax.fori_loop(
        0, _chunks(routing, chunk), step,
        (_zeros(x.shape, like), tuple(_zeros(m.shape, like) for m in weights),
         _zeros((tokens * k + 1,), like)))
    return (dx.astype(x.dtype),
            tuple(d.astype(m.dtype) for d, m in zip(dweights, weights)),
            dcombine[:-1].reshape(tokens, k).astype(combine.dtype), None)


grouped_ffn.defvjp(_fwd, _bwd)
