"""The delta rule's chunk work as two Mosaic kernels (forward, backward).

What ``ops/delta_rule.py::_within_chunk`` computes for every chunk (running
sums, ``A``, ``P``, ``(I + A)^-1``, ``W``, ``U``, ``Q exp(G)``, ``K exp(G_C -
G)``, ``exp(G_C)``), one grid cell a (batch row, head, block of chunks) with
the head width in the lanes.  The mathematics and its rounding points are
``_within_chunk``'s: the decay enters only as ``exp`` of a difference ``<=
0``, the running sums, ``A``, ``P`` and the inverse are float32 with float32
products, and the results are rounded to the stream's type on the way out.
What never leaves VMEM: the ``[sub, d]`` decay products, ``A``, the inverse.

A step of the kernels' loop takes a span of as many chunks as fill the
matrix unit's 128 rows (four chunks of 32) and treats them as one matrix
with the chunks' blocks on its diagonal: ``A`` and its powers keep those
blocks, so the inverse's products, each a chain on its own, and the products
across sub-blocks are whole ``[128, 128]`` tiles shared by the span's chunks,
and what falls between two chunks is masked out (a chunk at a time the same
chain read 23.5 ms a layer-forward on the v5e, the scan 28.1, this 14.8:
PERF.md, PR 28).

Operands cross heads-major, ``[B, H, T, d]``, in float32 as the scan's
``chunks()`` made them (one transposed copy an operand, which XLA fuses into
what makes the operand), and their gradients come back the same way, so what
is rounded to the stream's type around the chunk work is what was rounded
around the scan.  (Operands and gradients in the stream's type save 1% of
the cell's update and put one run in nine over the comparison's narrowest
limit: PERF.md, PR 28.)  The results come out chunk axis first, ``[N, B, H,
C, d]``, which is what ``_walk_state`` scans over, and the backward takes
their cotangents in that order.  ``beta`` rides as a row a span, ``[T /
span, B, H, 1, span]`` (tokens in the lanes; a column ``[C, 1]`` a token
would pad every number to a lane tile in HBM), and is turned into a column
inside by a masked sum.

The backward recomputes the chunk's forward in VMEM from the five inputs,
which are all the ``custom_vjp`` saves, and writes out the derivative
autodiff takes of ``_within_chunk``.  With ``M = (I + A)^-1``, ``solved = M
rhs`` and ``rhs = beta [K exp(G) | V]``: ``drhs = M^T dsolved``, ``dA =
-tril(drhs solved^T, -1)``.  For a pair product ``F_ti = sum_c r_tc k_ic
exp(G_tc - G_ic)`` with cotangent ``D``: ``dr_t = sum_i D_ti k_i exp(G_t -
G_i)``, ``dk_i = sum_t D_ti r_t exp(G_t - G_i)`` and ``dG = r dr - k dk``;
both sums split their decay as the forward does.  ``dg`` is the reversed
running sum of ``dG``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """``[1, C]`` -> ``[C, 1]`` without a transpose."""
    c = row.shape[1]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), row, 0.0),
                   1, keepdims=True)


def _row(column):
    c = column.shape[0]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), column,
                             0.0), 0, keepdims=True)


def _cut(x, chunk: int):
    """``[span, .]`` -> ``[span / chunk, chunk, .]``: whole sublane tiles."""
    return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])


def _flat(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _decays(run, chunk: int, sub: int):
    """The decay factors of ``_pair_products``, ``run [span, d]`` a whole
    number of chunks.  ``rowscale = exp(run_t - R_s)`` with ``R_s`` the
    running sum at the first token of ``t``'s sub-block; ``keyscale[a] =
    exp(R_a - run_i)`` with ``R_a`` that of sub-block ``a`` of ``i``'s chunk,
    for the tokens of the chunk's earlier sub-blocks and zero from ``a`` on;
    ``inside[i] = exp(run_t - run_i)`` ``[span / sub, sub, d]`` with ``i``
    the ``i``-th token of ``t``'s sub-block, for ``t`` from it on and zero
    before.  Every exponent is ``<= 0``."""
    mine = _cut(run, sub)
    whole = _cut(run, chunk)
    token = _iota(whole.shape, 1)
    at = _iota(mine.shape, 1)
    rowscale = _flat(jnp.exp(mine - mine[:, :1]))
    keyscale = [None] + [
        _flat(jnp.exp(jnp.where(token < a * sub,
                                whole[:, a * sub:a * sub + 1] - whole,
                                -jnp.inf)))
        for a in range(1, chunk // sub)]
    inside = [jnp.exp(jnp.where(at >= i, mine - mine[:, i:i + 1], -jnp.inf))
              for i in range(sub)]
    return rowscale, keyscale, inside


def _sub_block(x, a: int, chunk: int, sub: int):
    """Sub-block ``a`` of every chunk: ``[span, .]`` -> ``[span / chunk *
    sub, .]``."""
    return _flat(_cut(x, chunk)[:, a * sub:(a + 1) * sub])


def _pairs(rows, k, decays, same, chunk: int, sub: int):
    """``sum_c rows_tc k_ic exp(run_tc - run_ic)`` for ``i <= t`` of one
    chunk, zero elsewhere, for every ``[span, d]`` of ``rows`` against the
    same keys: a list of ``[span, span]``.  Across sub-blocks a matrix
    product a sub-block index, all chunks at once (``same`` takes out what
    falls between two chunks); inside, a column a token of the sub-block."""
    span = k.shape[0]
    rowscale, keyscale, inside = decays
    wide = span // chunk
    below = [[jnp.zeros((wide, sub, span), _F32)] for _ in rows]
    for a in range(1, chunk // sub):
        scale = _sub_block(rowscale, a, chunk, sub)
        both = _dot(jnp.concatenate(
            [_sub_block(r, a, chunk, sub) * scale for r in rows], 0),
            k * keyscale[a], _NT)
        for j, o in enumerate(below):
            o.append(_cut(both[j * wide * sub:(j + 1) * wide * sub], sub))
    mine = [_cut(r, sub) for r in rows]
    keys = _cut(k, sub)
    shape = (span // sub, sub, span)
    lane = _iota(shape, 2) - _iota(shape, 0) * sub
    here = [jnp.zeros(shape, _F32) for _ in rows]
    for i in range(sub):
        key = keys[:, i:i + 1] * inside[i]
        here = [jnp.where(lane == i, jnp.sum(r * key, 2, keepdims=True), h)
                for r, h in zip(mine, here)]
    out = []
    for o, h in zip(below, here):
        o = _flat(jnp.concatenate(o, 1))
        out.append((o if same is None else jnp.where(same, o, 0.0))
                   + _flat(h))
    return out


def _pairs_transposed(cots, rows, k, decays, chunk: int, sub: int):
    """The cotangents of :func:`_pairs`: ``cots`` is one ``[span, span]`` a
    set of rows, zero where :func:`_pairs` is.  Returns the list of ``d
    rows`` and ``d k`` as the keys; ``d run`` is ``sum rows * d rows - k * d
    k``."""
    span, d = k.shape
    rowscale, keyscale, inside = decays
    wide = span // chunk
    below = [[jnp.zeros((wide, sub, d), _F32)] for _ in rows]
    d_key = jnp.zeros((span, d), _F32)
    for a in range(1, chunk // sub):
        scale = _sub_block(rowscale, a, chunk, sub)
        both = jnp.concatenate([_sub_block(x, a, chunk, sub) for x in cots],
                               0)
        scaled = jnp.concatenate(
            [_sub_block(r, a, chunk, sub) * scale for r in rows], 0)
        onto = _dot(both, k * keyscale[a])
        for j, o in enumerate(below):
            o.append(_cut(onto[j * wide * sub:(j + 1) * wide * sub] * scale,
                          sub))
        d_key = d_key + _dot(both, scaled, _TN) * keyscale[a]
    mine = [_cut(r, sub) for r in rows]
    cot = [_cut(x, sub) for x in cots]
    keys = _cut(k, sub)
    shape = (span // sub, sub, span)
    lane = _iota(shape, 2) - _iota(shape, 0) * sub
    at = _iota(keys.shape, 1)
    d_rows = [jnp.zeros(keys.shape, _F32) for _ in rows]
    d_here = jnp.zeros(keys.shape, _F32)
    for i in range(sub):
        col = [jnp.sum(jnp.where(lane == i, x, 0.0), 2, keepdims=True)
               for x in cot]
        key = keys[:, i:i + 1] * inside[i]
        d_rows = [o + x * key for o, x in zip(d_rows, col)]
        onto = sum(x * r for x, r in zip(col, mine)) * inside[i]
        d_here = jnp.where(at == i, jnp.sum(onto, 1, keepdims=True), d_here)
    return ([_flat(jnp.concatenate(o, 1)) + _flat(h)
             for o, h in zip(below, d_rows)], d_key + _flat(d_here))


def _inverse(a, chunk: int, inside: int):
    """``(I + a)^-1`` for ``a`` strictly lower-triangular in blocks of one
    chunk on the diagonal, as ``_unit_lower_inverse`` takes it and by the
    same products (the powers of such an ``a`` keep its blocks, so all
    chunks of the span share each product): a level's ``inverse @ power``
    and ``power @ power`` are the two halves of one product of the stacked
    pair.  The squaring stays inside diagonal blocks of ``inside`` rows;
    blocks smaller than the chunk are merged pair by pair."""
    c = a.shape[0]
    levels = max(0, (inside - 1).bit_length() - 1)
    power = -a
    if inside < chunk:      # traced only here: a whole-chunk trace stays
        row, col = _iota((c, c), 0), _iota((c, c), 1)
        together = lambda size: row // size == col // size
        power = jnp.where(together(inside), power, 0.0)
    inverse = power + (_iota((c, c), 0) == _iota((c, c), 1)).astype(_F32)
    if levels:
        power = _dot(power, power)
    for level in range(levels - 1):
        both = _dot(jnp.concatenate([inverse, power], 0), power)
        inverse, power = inverse + both[:c], both[c:]
    inverse = inverse + _dot(inverse, power) if levels else inverse
    size = inside
    while size < chunk:
        between = jnp.where(jnp.logical_and(together(2 * size),
                                            jnp.logical_not(together(size))),
                            a, 0.0)
        inverse = inverse - _dot(_dot(inverse, between), inverse)
        size *= 2
    return inverse


def _running_sum(x, chunk: int, reverse: bool = False):
    """The sum of ``x [span, d]`` over the tokens of its chunk up to each
    token (from it on if ``reverse``), float32, in ``log2(chunk)`` shifted
    adds: the matrix unit has enough to do."""
    from jax.experimental.pallas import tpu as pltpu
    span = x.shape[0]
    at = _iota(x.shape, 0)
    if span != chunk:         # then the chunk divides 128
        at = at & (chunk - 1)
    step = 1
    while step < chunk:
        if reverse:
            x = x + jnp.where(at < chunk - step,
                              pltpu.roll(x, span - step, 0), 0.0)
        else:
            x = x + jnp.where(at >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _span(k, g, beta_row, chunk: int, sub: int):
    """What forward and backward share of one span of chunks: the running
    sum, its decays, ``beta`` as a column, and where ``[span, span]`` holds
    pairs ``i <= t`` and ``i < t`` of one chunk."""
    span = k.shape[0]
    row, col = _iota((span, span), 0), _iota((span, span), 1)
    same = None if span == chunk else row // chunk == col // chunk
    both = lambda x: x if same is None else jnp.logical_and(same, x)
    run = _running_sum(g, chunk)
    return (run, _decays(run, chunk, sub), _column(beta_row), same,
            both(row >= col), both(row > col))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref, p_ref,
                q_in_ref, k_out_ref, last_ref, *, chunk: int, sub: int,
                wide: int, inside: int):
    from jax.experimental import pallas as pl
    kind = w_ref.dtype
    span = wide * chunk

    def one(n):
        at = pl.ds(pl.multiple_of(n * span, span), span)
        q, k, v = (x[0, 0, at, :].astype(_F32) for x in (q_ref, k_ref, v_ref))
        run, decays, beta, same, _, strict = _span(
            k, g_ref[0, 0, at, :], beta_ref[n, 0, 0], chunk, sub)
        p, a = _pairs([q, k], k, decays, same, chunk, sub)
        inverse = _inverse(jnp.where(strict, a * beta, 0.0), chunk, inside)
        into = jnp.exp(run)
        whole = _cut(run, chunk)
        results = ((w_ref, _dot(inverse, k * into * beta)),
                   (u_ref, _dot(inverse, v * beta)), (q_in_ref, q * into),
                   (k_out_ref, k * _flat(jnp.exp(whole[:, -1:] - whole))))
        for m in range(wide):
            rows = slice(m * chunk, (m + 1) * chunk)
            for ref, x in results:
                ref[n * wide + m, 0, 0] = x[rows].astype(kind)
            p_ref[n * wide + m, 0, 0] = p[rows, rows].astype(kind)
            last_ref[n * wide + m, 0, 0] = into[(m + 1) * chunk - 1:
                                                (m + 1) * chunk]

    jax.lax.fori_loop(0, w_ref.shape[0] // wide, lambda n, _: one(n), None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, dw_ref, du_ref, dp_ref,
                dq_in_ref, dk_out_ref, dlast_ref, dq_ref, dk_ref, dv_ref,
                dg_ref, dbeta_ref, *, chunk: int, sub: int, wide: int,
                inside: int):
    from jax.experimental import pallas as pl
    span = wide * chunk

    def one(n):
        at = pl.ds(pl.multiple_of(n * span, span), span)
        of = pl.ds(n * wide, wide)
        q, k, v = (x[0, 0, at, :].astype(_F32) for x in (q_ref, k_ref, v_ref))
        d_w, d_u, d_q_in, d_k_out = (
            _flat(x[of, 0, 0].astype(_F32))
            for x in (dw_ref, du_ref, dq_in_ref, dk_out_ref))
        d_p = jnp.concatenate([
            jnp.pad(dp_ref[n * wide + m, 0, 0].astype(_F32),
                    ((0, 0), (m * chunk, span - (m + 1) * chunk)))
            for m in range(wide)], 0)
        run, decays, beta, same, lower, strict = _span(
            k, g_ref[0, 0, at, :], beta_ref[n, 0, 0], chunk, sub)
        a, = _pairs([k], k, decays, same, chunk, sub)
        inverse = _inverse(jnp.where(strict, a * beta, 0.0), chunk, inside)
        into = jnp.exp(run)
        whole = _cut(run, chunk)
        out_of = _flat(jnp.exp(whole[:, -1:] - whole))
        key_in = k * into
        w = _dot(inverse, key_in * beta)
        u = _dot(inverse, v * beta)
        d_rhs_k = _dot(inverse, d_w, _TN)
        d_rhs_v = _dot(inverse, d_u, _TN)
        d_a = jnp.where(strict, -(_dot(d_rhs_k, w, _NT)
                                  + _dot(d_rhs_v, u, _NT)), 0.0)
        d_beta = (jnp.sum(d_rhs_k * key_in, 1, keepdims=True)
                  + jnp.sum(d_rhs_v * v, 1, keepdims=True)
                  + jnp.sum(d_a * a, 1, keepdims=True))
        (d_q, d_k), d_key = _pairs_transposed(
            [jnp.where(lower, d_p, 0.0), d_a * beta], [q, k], k, decays,
            chunk, sub)
        d_key_in = d_rhs_k * beta
        d_out_of = d_k_out * k * out_of
        d_run = (q * d_q + k * (d_k - d_key) + (d_key_in * k + d_q_in * q)
                 * into - d_out_of)
        ends = (jnp.sum(_cut(d_out_of, chunk), 1, keepdims=True)
                + dlast_ref[of, 0, 0] * _cut(into, chunk)[:, -1:])
        d_run = d_run + _flat(jnp.where(
            _iota(whole.shape, 1) == chunk - 1, ends, 0.0))
        dq_ref[0, 0, at, :] = (d_q + d_q_in * into).astype(dq_ref.dtype)
        dk_ref[0, 0, at, :] = (d_k + d_key + d_key_in * into
                               + d_k_out * out_of).astype(dk_ref.dtype)
        dv_ref[0, 0, at, :] = (d_rhs_v * beta).astype(dv_ref.dtype)
        dg_ref[0, 0, at, :] = _running_sum(d_run, chunk, reverse=True)
        dbeta_ref[n, 0, 0] = _row(d_beta)

    jax.lax.fori_loop(0, dw_ref.shape[0] // wide, lambda n, _: one(n), None)


def _wide(chunk: int, group: int) -> int:
    """Chunks a step of the kernels' loop: as many as fill the matrix unit's
    128 rows, if the block is a whole number of such spans."""
    wide = max(1, 128 // chunk)
    return wide if 128 % chunk == 0 and group % wide == 0 else 1


def _specs(q, v, kind, chunk: int, group: int):
    """Grid, block specs and result shapes: heads-major ``[B, H, T, d]``
    blocks of ``group`` chunks and chunk-major ``[N, B, H, ., .]`` blocks of
    the same in the type ``kind``; ``beta`` is a row a span."""
    from jax.experimental import pallas as pl
    b, h, t, d_k = q.shape
    d_v = v.shape[-1]
    n = t // chunk
    wide = _wide(chunk, group)

    def tokens(d):
        return pl.BlockSpec((1, 1, group * chunk, d),
                            lambda i, j, m: (i, j, m, 0))

    def chunks(rows, d, many=group):
        return pl.BlockSpec((many, 1, 1, rows, d),
                            lambda i, j, m: (m, i, j, 0, 0))

    def result(rows, d, of=kind):
        return jax.ShapeDtypeStruct((n, b, h, rows, d), of)

    grid = (b, h, n // group)
    inputs = [tokens(d_k), tokens(d_k), tokens(d_v), tokens(d_k),
              chunks(1, wide * chunk, group // wide)]
    parts = [chunks(chunk, d_k), chunks(chunk, d_v), chunks(chunk, chunk),
             chunks(chunk, d_k), chunks(chunk, d_k), chunks(1, d_k)]
    shapes = [result(chunk, d_k), result(chunk, d_v), result(chunk, chunk),
              result(chunk, d_k), result(chunk, d_k), result(1, d_k, _F32)]
    return grid, inputs, parts, shapes


def _call(kernel, grid, chunk, sub, group, inside, **kwargs):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, sub=sub,
                          wide=_wide(chunk, group), inside=inside),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        **kwargs)


_STATIC = ("kind", "chunk", "sub", "group", "inside", "interpret")


def _beta_rows(beta, chunk: int, group: int):
    """``beta [B, H, T]`` -> ``[T / span, B, H, 1, span]``, a row of tokens
    a span of the kernels' loop."""
    b, h, t = beta.shape
    span = _wide(chunk, group) * chunk
    return jnp.moveaxis(beta.reshape(b, h, t // span, 1, span), 2, 0)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kda_chunks_fwd(q, k, v, g, beta, kind, chunk: int, sub: int, group: int,
                    inside: int, interpret: bool = False):
    grid, inputs, parts, shapes = _specs(q, v, kind, chunk, group)
    return _call(_fwd_kernel, grid, chunk, sub, group, inside, in_specs=inputs,
                 out_specs=parts, out_shape=shapes, interpret=interpret)(
                     q, k, v, g, _beta_rows(beta, chunk, group))


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kda_chunks_bwd(q, k, v, g, beta, cotangents, kind, chunk: int, sub: int,
                    group: int, inside: int, interpret: bool = False):
    grid, inputs, parts, _ = _specs(q, v, kind, chunk, group)
    rows = _beta_rows(beta, chunk, group)
    *grads, d_rows = _call(
        _bwd_kernel, grid, chunk, sub, group, inside,
        in_specs=inputs + parts,
        out_specs=inputs, out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                                     for x in (q, k, v, g, rows)],
        interpret=interpret)(q, k, v, g, rows, *cotangents)
    return (*grads, jnp.moveaxis(d_rows, 0, 2).reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def kda_chunks(q, k, v, g, beta, kind, chunk: int, sub: int, group: int,
               inside: int, interpret: bool):
    """``_within_chunk`` for every chunk: ``q, k [B, H, T, d_k]``, ``v [B,
    H, T, d_v]``, ``g`` and ``beta [B, H, T]`` float32; ``T`` a multiple of
    ``group * chunk``; ``inside`` is ``_inverse``'s.  Returns ``w, u, p, q_in,
    k_out [T / chunk, B, H, chunk, .]`` in the type ``kind`` and ``last [T /
    chunk, B, H, 1, d_k]`` float32."""
    return _kda_chunks_fwd(q, k, v, g, beta, kind=kind, chunk=chunk, sub=sub,
                           group=group, inside=inside, interpret=interpret)


def _vjp_fwd(q, k, v, g, beta, *static):
    return kda_chunks(q, k, v, g, beta, *static), (q, k, v, g, beta)


def _vjp_bwd(kind, chunk, sub, group, inside, interpret, saved, cotangents):
    return _kda_chunks_bwd(*saved, tuple(cotangents), kind=kind, chunk=chunk,
                           sub=sub, group=group, inside=inside,
                           interpret=interpret)


kda_chunks.defvjp(_vjp_fwd, _vjp_bwd)
