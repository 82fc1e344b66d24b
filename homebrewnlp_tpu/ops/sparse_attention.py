"""Attention over the keys a learned indexer selects (DeepSeek Sparse
Attention, as ``models/hybrid.py::gqa`` spells it: ``-sparse``).

An indexer of ``NI`` heads of width ``DI`` and one key head scores every
earlier position of a row:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          (s <= t)

(``w`` carries the indexer's scales).  Row ``t`` keeps the ``topk`` keys of
the largest scores, ties to the lower position as ``jax.lax.top_k`` breaks
them, every earlier key where there are no more; the kept set is one a
token, shared by every query head.  Attention then runs over that set alone,
and the indexer learns from its own loss: the KL divergence from the heads'
mean attention over the set (which takes no gradient) to the softmax of its
scores over the same set.

Five Mosaic kernels on tiles of ``block`` rows and keys (``sa_config``'s
``q_chunk_size``, 512), each walking the key tiles up to its
row block's diagonal (the triangle), interpreted on the CPU:

- ``_select_kernel``: one block of rows of ``I`` against every key up to
  its diagonal, held in VMEM (the triangle's scores are never whole in
  HBM), each row's ``topk``-th largest score found by bisection over its
  bits, not by a sort, and the kept set written as a mask of bytes ``[B, T,
  T]`` with the log of the softmax's denominator over the kept scores;
- ``_fwd_kernel``: the attention's forward over the mask's tiles, one cell a
  K/V head and a block of rows, the group's query heads in the cell, so a
  K/V tile and a mask tile are read once for the group; an online softmax
  whose masked scores are ``-inf``;
- ``_dq_kernel``, ``_dkv_kernel``: its backward, the one walking key tiles
  for a block of rows, the other blocks of rows for a key tile;
- ``_kl_kernel``: the indexer's loss and its gradient in one walk: a tile's
  mean attention over all query heads (from the forward's row statistics),
  the tile's indexer scores again, and their difference multiplied back
  into ``qI``, ``kI`` and ``w``.  The gradient is the loss's whole gradient
  (nothing else reads those scores), so the loss's backward only scales it.

Masked tiles rather than gathered rows: at 16,384 tokens a row keeps 2,048
keys, and a gather of a token's K/V rows costs bytes the matrix unit could
multiply a whole tile with.  With random weights no tile of the triangle is
empty, so none is skipped.  Kernels of their own rather than a mask operand
in ``ops/pallas_mla.py``'s: those hold a query head's whole K/V in VMEM, one
cell a query head, and their backward sits at the 64 MiB limit at 16,384
tokens; a block of rows' mask against every key is 8 MiB more, twice
buffered, fetched again by each of a group's query heads.  Here a cell holds
a K/V head's group and the key tiles are a grid axis (ROADMAP R9's), so a
mask tile is read once for the group and no VMEM grows with the sequence.
Rounding points, the helpers and the VMEM limit are ``ops/pallas_mla.py``'s:
operands in their own type into float32 sums, the weights rounded to ``v``'s
type, ``dS`` to ``q``'s.

Under a block part's ``jax.checkpoint`` (``models/__init__.py::_body``) the
three forward kernels' outputs are named (:data:`SAVED`) and kept, so the
backward reads them instead of running the selection, the attention's
forward and the indexer's loss again: the mask, ``lse_i``, ``o``, ``lse`` and
the indexer's three scaled gradients.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .pallas_mla import _F32, _NT, _TN, VMEM_BYTES, _dot

#: the names of the forward kernels' outputs a part's checkpoint keeps
SAVED = ("sparse_select", "sparse_attention", "sparse_indexer_grad")


def kept_bytes(q, v, qi, ki, w) -> int:
    """Bytes of the :data:`SAVED` values of one sparse layer: the mask and
    ``lse_i``, ``o`` and ``lse``, the gradients by ``qi``, ``ki`` and ``w``
    (``q [B, H, T, D]``, ``v [B, H / group, T, DV]``, ``qi``, ``ki``, ``w``
    as :func:`select` takes them)."""
    b, h, t, _ = q.shape
    return (b * t * t + 4 * b * t
            + b * h * t * (v.shape[-1] * v.dtype.itemsize + 4)
            + sum(x.size * x.dtype.itemsize for x in (qi, ki, w)))


@functools.lru_cache(maxsize=None)
def say_kept(part: int, parts: int) -> None:
    """Logs once a size what a part's checkpoint keeps of its sparse
    layer."""
    logging.getLogger(__name__).info(
        "a sparse part's checkpoint keeps %d bytes of its forward kernels' "
        "outputs (%s), %d over %d parts", part, ", ".join(SAVED),
        part * parts, parts)


def block_of(length: int, chunk: int) -> int:
    """The tile of a sequence: ``chunk`` (``sa_config``'s ``q_chunk_size``),
    or the whole of a shorter sequence."""
    block = min(chunk, length)
    if length % block:
        raise ValueError(f"a sequence of {length} is no whole number of "
                         f"blocks of {block}")
    return block


def _column(x, j: int):
    """Column ``j`` of ``x [R, n]`` as ``[R, 1]``: a select and a sum over
    lanes, which Mosaic lowers at any ``n``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == j, x, 0.0), 1, keepdims=True)


def _columns(cols):
    """``[R, 1]`` columns side by side as ``[R, len(cols)]``."""
    shape = (cols[0].shape[0], len(cols))
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.zeros(shape, _F32)
    for j, c in enumerate(cols):
        out = jnp.where(lane == j, c, out)
    return out


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


# -- the indexer's scores and the selection -----------------------------------

#: rows of a block of the selection, and keys of a chunk of its walk
SELECT_ROWS, SELECT_CHUNK = 256, 1024
_INT_MIN = -2 ** 31


def _ordered(x):
    """float32 -> int32 of the same order (``-0.0`` below ``0.0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ 0x7FFFFFFF)


def _unordered(key):
    return jax.lax.bitcast_convert_type(
        jnp.where(key >= 0, key, key ^ 0x7FFFFFFF), _F32)


def _select_kernel(qi_ref, ki_ref, w_ref, mask_ref, lse_ref, kept_ref,
                   key_sc, *, topk: int, chunk: int):
    """One block of rows: their scores against every key up to the block's
    diagonal (as ordered int32 keys in VMEM), each row's ``topk``-th largest
    by bisection over the keys' 32 bits, the position cut among the scores
    equal to it by bisection over positions, then the mask, the log of the
    kept scores' sum of exponentials and the kept count."""
    from jax.experimental import pallas as pl
    rows, length = key_sc.shape
    first = pl.program_id(1) * rows
    walk = (first + rows + chunk - 1) // chunk      # chunks the rows reach
    w = w_ref[0].astype(_F32)
    shape = (rows, chunk)
    row = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def at(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def score(c, top):
        ki = ki_ref[0, at(c), :]
        acc = jnp.zeros(shape, _F32)
        for j in range(qi_ref.shape[1]):
            acc += _column(w, j) * jnp.maximum(
                _dot(qi_ref[0, j], ki, _NT), 0.0)
        acc = jnp.where(c * chunk + lane <= row, acc, -jnp.inf)
        key_sc[:, at(c)] = _ordered(acc)
        return jnp.maximum(top, jnp.max(acc, 1, keepdims=True))

    top = jax.lax.fori_loop(0, walk, score,
                            jnp.full((rows, 1), -jnp.inf, _F32))

    def count(test):
        """Per row, the keys ``test(key, position)`` holds for."""
        def add(c, n):
            hit = test(key_sc[:, at(c)], c * chunk + lane)
            return n + jnp.sum(jnp.where(hit, 1.0, 0.0), 1, keepdims=True)
        return jax.lax.fori_loop(0, walk, add, jnp.zeros((rows, 1), _F32))

    def bit(b, least):
        """Bit ``31 - b`` of the ``topk``-th largest key (offset binary:
        adding ``2 ** 31`` to ``INT_MIN`` wraps to 0)."""
        cand = least + jnp.left_shift(jnp.int32(1), 31 - b)
        return jnp.where(count(lambda k, _: k >= cand) >= topk, cand, least)

    least = jax.lax.fori_loop(0, 32, bit,
                              jnp.full((rows, 1), _INT_MIN, jnp.int32))
    need = topk - count(lambda k, _: k > least)
    span = max(length - 1, 1).bit_length()

    def place(b, cut):
        """Bit ``span - 1 - b`` of the position of the ``need``-th key equal
        to the least kept: ``top_k`` keeps the lower positions of a tie."""
        cand = cut + jnp.left_shift(jnp.int32(1), span - 1 - b)
        fewer = count(lambda k, s: (k == least) & (s < cand)) < need
        return jnp.where(fewer, cand, cut)

    cut = jax.lax.fori_loop(0, span, place, jnp.zeros((rows, 1), jnp.int32))
    # a row with no more than topk earlier keys keeps them all
    every = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < topk

    def write(c, acc):
        total, kept = acc
        key = key_sc[:, at(c)]
        s = c * chunk + lane
        keep = (s <= row) & (every | (key > least)
                             | ((key == least) & (s <= cut)))
        mask_ref[0, :, at(c)] = keep.astype(mask_ref.dtype)
        weights = jnp.where(keep, jnp.exp(_unordered(key) - top), 0.0)
        return (total + jnp.sum(weights, 1, keepdims=True),
                kept + jnp.sum(jnp.where(keep, 1.0, 0.0), 1, keepdims=True))

    zero = jnp.zeros((rows, 1), _F32)
    total, kept = jax.lax.fori_loop(0, walk, write, (zero, zero))

    def clear(c, _):
        mask_ref[0, :, at(c)] = jnp.zeros(shape, mask_ref.dtype)

    jax.lax.fori_loop(walk, length // chunk, clear, None)
    lse_ref[0] = top + jnp.log(total)
    kept_ref[0] = kept


@functools.partial(jax.jit,
                   static_argnames=("topk", "rows", "chunk", "interpret"))
def _select(qi, ki, w, topk: int, rows: int, chunk: int,
            interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, ni, t, di = qi.shape
    stat = pl.BlockSpec((1, rows, 1), lambda x, y: (x, y, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=chunk),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, ni, rows, di), lambda x, y: (x, 0, y, 0)),
                  pl.BlockSpec((1, t, di), lambda x, y: (x, 0, 0)),
                  pl.BlockSpec((1, rows, ni), lambda x, y: (x, y, 0))],
        out_specs=[pl.BlockSpec((1, rows, t), lambda x, y: (x, y, 0)),
                   stat, stat],
        out_shape=[jax.ShapeDtypeStruct((b, t, t), jnp.int8),
                   jax.ShapeDtypeStruct((b, t, 1), _F32),
                   jax.ShapeDtypeStruct((b, t, 1), _F32)],
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret)(qi, ki, w)


def select(qi, ki, w, topk: int, block: int, interpret: bool):
    """The kept set of every row, one block of rows at a time, in one
    kernel (``_select_kernel``).

    ``qi [B, NI, T, DI]``, ``ki [B, T, DI]``, ``w [B, T, NI]``.  Returns the
    mask ``[B, T, T]`` int8 (1 where row ``t`` keeps key ``s``), the log of
    ``sum_{s kept} exp(I[t, s])`` ``[B, T, 1]`` float32, and the kept pairs.
    A row keeps the keys whose score exceeds its ``topk``-th largest, and of
    those that equal it the lowest positions up to ``topk`` in all: exactly
    ``jax.lax.top_k``'s set, found without a sort.  Takes no gradient."""
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    t = qi.shape[2]
    rows, chunk = min(SELECT_ROWS, block), min(SELECT_CHUNK, t)
    if t % chunk:
        chunk = block
    mask, lse, kept = _select(qi, ki, w, topk=min(topk, t), rows=rows,
                              chunk=chunk, interpret=interpret)
    mask, lse = checkpoint_name((mask, lse), SAVED[0])
    return mask, lse, jnp.sum(kept).astype(jnp.int32)


# -- attention over the mask ----------------------------------------------------

def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, top_sc,
                total_sc, out_sc):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(2), pl.program_id(3)
    group = q_ref.shape[2]

    @pl.when(j == 0)
    def _():
        top_sc[...] = jnp.full(top_sc.shape, -jnp.inf, _F32)
        total_sc[...] = jnp.zeros(total_sc.shape, _F32)
        out_sc[...] = jnp.zeros(out_sc.shape, _F32)

    @pl.when(j <= i)
    def _():
        seen = mask_ref[0] != 0
        k, v = k_ref[0, 0], v_ref[0, 0]
        for m in range(group):
            scores = jnp.where(seen, _dot(q_ref[0, 0, m], k, _NT), -jnp.inf)
            top = top_sc[m]
            new_top = jnp.maximum(top, jnp.max(scores, 1, keepdims=True))
            # a row that has seen no kept key yet: no -inf - -inf
            safe = jnp.where(new_top > -jnp.inf, new_top, 0.0)
            weights = jnp.exp(scores - safe)
            keep = jnp.exp(top - safe)
            total_sc[m] = total_sc[m] * keep + jnp.sum(weights, 1,
                                                       keepdims=True)
            out_sc[m] = out_sc[m] * keep + _dot(weights.astype(v.dtype), v)
            top_sc[m] = new_top

    @pl.when(j == i)
    def _():
        cols = []
        for m in range(group):
            o_ref[0, 0, m] = (out_sc[m] / total_sc[m]).astype(o_ref.dtype)
            cols.append(top_sc[m] + jnp.log(total_sc[m]))
        lse_ref[0, 0] = _columns(cols)


def _specs(group: int, block: int, rows_major: bool):
    """Block specs over a grid (batch row, K/V head, outer block, inner
    block): ``rows_major`` walks key tiles inside a block of rows, else
    blocks of rows inside a key tile; tiles past the diagonal repeat the
    last block's index, so nothing is fetched for them."""
    from jax.experimental import pallas as pl
    if rows_major:
        rk = lambda o, n: (o, jnp.minimum(n, o))
    else:
        rk = lambda o, n: (jnp.maximum(n, o), o)
    heads = lambda w: pl.BlockSpec(
        (1, 1, group, block, w), lambda x, y, o, n: (x, y, 0, rk(o, n)[0], 0))
    stat = pl.BlockSpec((1, 1, block, group),
                        lambda x, y, o, n: (x, y, rk(o, n)[0], 0))
    keys = lambda w: pl.BlockSpec(
        (1, 1, block, w), lambda x, y, o, n: (x, y, rk(o, n)[1], 0))
    mask = pl.BlockSpec((1, block, block),
                        lambda x, y, o, n: (x,) + rk(o, n))
    return heads, stat, keys, mask


def _grouped(q, g: int):
    b, h, t, d = q.shape
    return q.reshape(b, g, h // g, t, d)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _attention_fwd(q, k, v, mask, block: int, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, t, d = q.shape
    g, d_v = k.shape[1], v.shape[-1]
    group, n = h // g, t // block
    heads, stat, keys, masks = _specs(group, block, True)
    o, lse = pl.pallas_call(
        _fwd_kernel, grid=(b, g, n, n),
        in_specs=[masks, heads(d), keys(d), keys(d_v)],
        out_specs=[heads(d_v), stat],
        out_shape=[jax.ShapeDtypeStruct((b, g, group, t, d_v), v.dtype),
                   jax.ShapeDtypeStruct((b, g, t, group), _F32)],
        scratch_shapes=[pltpu.VMEM((group, block, 1), _F32),
                        pltpu.VMEM((group, block, 1), _F32),
                        pltpu.VMEM((group, block, d_v), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret)(mask, _grouped(q, g), k, v)
    return o.reshape(b, h, t, d_v), lse


def _probabilities(q, k, seen, lse):
    """A tile's attention weights: ``exp(q k^T - lse)``, 0 off the mask."""
    return jnp.exp(jnp.where(seen, _dot(q, k, _NT), -jnp.inf) - lse)


def _dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_sc):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(2), pl.program_id(3)
    group = q_ref.shape[2]

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, _F32)

    @pl.when(j <= i)
    def _():
        seen = mask_ref[0] != 0
        k, v = k_ref[0, 0], v_ref[0, 0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        for m in range(group):
            q, do = q_ref[0, 0, m], do_ref[0, 0, m]
            p = _probabilities(q, k, seen, _column(lse, m))
            ds = p * (_dot(do, v, _NT) - _column(delta, m))
            dq_sc[m] += _dot(ds.astype(q.dtype), k)

    @pl.when(j == i)
    def _():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc):
    from jax.experimental import pallas as pl
    j, i = pl.program_id(2), pl.program_id(3)
    group = q_ref.shape[2]

    @pl.when(i == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, _F32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, _F32)

    @pl.when(i >= j)
    def _():
        seen = mask_ref[0] != 0
        k, v = k_ref[0, 0], v_ref[0, 0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        for m in range(group):
            q, do = q_ref[0, 0, m], do_ref[0, 0, m]
            p = _probabilities(q, k, seen, _column(lse, m))
            dv_sc[...] += _dot(p.astype(do.dtype), do, _TN)
            ds = p * (_dot(do, v, _NT) - _column(delta, m))
            dk_sc[...] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _attention_bwd(q, k, v, mask, o, lse, do, block: int,
                   interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, t, d = q.shape
    g, d_v = k.shape[1], v.shape[-1]
    group, n = h // g, t // block
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), -1)
    delta = jnp.swapaxes(delta.reshape(b, g, group, t), 2, 3)
    operands = (mask, _grouped(q, g), k, v, _grouped(do, g), lse, delta)
    heads, stat, keys, masks = _specs(group, block, True)
    in_specs = [masks, heads(d), keys(d), keys(d_v), heads(d_v), stat, stat]
    dq = pl.pallas_call(
        _dq_kernel, grid=(b, g, n, n), in_specs=in_specs,
        out_specs=heads(d),
        out_shape=jax.ShapeDtypeStruct((b, g, group, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((group, block, d), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret)(*operands)
    heads, stat, keys, masks = _specs(group, block, False)
    in_specs = [masks, heads(d), keys(d), keys(d_v), heads(d_v), stat, stat]
    dk, dv = pl.pallas_call(
        _dkv_kernel, grid=(b, g, n, n), in_specs=in_specs,
        out_specs=[keys(d), keys(d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), _F32),
                        pltpu.VMEM((block, d_v), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret)(*operands)
    return dq.reshape(q.shape), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def attention(q, k, v, mask, block: int, interpret: bool):
    """``softmax(q k^T + mask) v`` over the kept keys and the row statistic
    ``lse [B, H / group, T, group]`` (the log of a row's sum of
    exponentials): ``q [B, H, T, D]`` (already scaled), ``k``, ``v [B, H /
    group, T, .]``, query head ``h`` reading K/V head ``h // group``, ``mask
    [B, T, T]`` int8 as :func:`select` makes it.  ``lse`` takes no
    gradient."""
    return _attention_fwd(q, k, v, mask, block=block, interpret=interpret)


def _fwd(q, k, v, mask, block, interpret):
    # one named value is both the output and the residual: a remat that
    # keeps it has nothing left to run the kernel for
    o, lse = checkpoint_name(
        _attention_fwd(q, k, v, mask, block=block, interpret=interpret),
        SAVED[1])
    return (o, lse), (q, k, v, mask, o, lse)


def _bwd(block, interpret, saved, cotangents):
    q, k, v, mask, o, lse = saved
    dq, dk, dv = _attention_bwd(q, k, v, mask, o, lse, cotangents[0],
                                block=block, interpret=interpret)
    return dq, dk, dv, None


attention.defvjp(_fwd, _bwd)


# -- the indexer's loss -----------------------------------------------------------

def _kl_kernel(mask_ref, q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref,
               lse_i_ref, kl_ref, dqi_ref, dw_ref, dkt_ref):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(1), pl.program_id(2)
    heads, group = q_ref.shape[1], lse_ref.shape[-1]
    block = mask_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        kl_ref[...] = jnp.zeros(kl_ref.shape, _F32)
        dqi_ref[...] = jnp.zeros(dqi_ref.shape, _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    @pl.when((i == 0) & (j == 0))
    def _():
        dkt_ref[...] = jnp.zeros(dkt_ref.shape, _F32)

    @pl.when(j <= i)
    def _():
        seen = mask_ref[0] != 0
        p = jnp.zeros(seen.shape, _F32)
        for h in range(heads):
            p += _probabilities(q_ref[0, h], k_ref[0, h // group], seen,
                                _column(lse_ref[0, h // group], h % group))
        p = p / heads
        ki, w = ki_ref[0], w_ref[0].astype(_F32)
        products = [_dot(qi_ref[0, n], ki, _NT)
                    for n in range(qi_ref.shape[1])]
        scores = sum(_column(w, n) * jnp.maximum(s, 0.0)
                     for n, s in enumerate(products))
        log_q = jnp.where(seen, scores - lse_i_ref[0], -jnp.inf)
        kl_ref[0] += jnp.sum(jnp.where(p > 0, p * (jnp.log(
            jnp.where(p > 0, p, 1.0)) - log_q), 0.0), 1, keepdims=True)
        # d KL_t / d I[t, s] over the kept keys: softmax less the target
        d_scores = jnp.exp(log_q) - p
        dw = []
        at = pl.ds(pl.multiple_of(j * block, block), block)
        for n, s in enumerate(products):
            dw.append(jnp.sum(d_scores * jnp.maximum(s, 0.0), 1,
                              keepdims=True))
            ds = jnp.where(s > 0, d_scores * _column(w, n), 0.0).astype(
                ki.dtype)
            dqi_ref[0, n] += _dot(ds, ki)
            dkt_ref[0, :, at] += _dot(qi_ref[0, n], ds, _TN)
        dw_ref[0] += _columns(dw)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _indexer_kl(q, k, lse, qi, ki, w, mask, lse_i, block: int,
                interpret: bool = False):
    """``(sum_t KL_t, its gradients by qi, ki, w)`` in one walk."""
    from jax.experimental import pallas as pl
    b, h, t, d = q.shape
    g, group = k.shape[1], h // k.shape[1]
    ni, di = qi.shape[1], qi.shape[-1]
    n = t // block
    keys = lambda x, y: jnp.minimum(y, x)
    kl, dqi, dw, dkt = pl.pallas_call(
        _kl_kernel, grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, block, block),
                         lambda z, x, y: (z, x, keys(x, y))),
            pl.BlockSpec((1, h, block, d), lambda z, x, y: (z, 0, x, 0)),
            pl.BlockSpec((1, g, block, d),
                         lambda z, x, y: (z, 0, keys(x, y), 0)),
            pl.BlockSpec((1, g, block, group), lambda z, x, y: (z, 0, x, 0)),
            pl.BlockSpec((1, ni, block, di), lambda z, x, y: (z, 0, x, 0)),
            pl.BlockSpec((1, block, di), lambda z, x, y: (z, keys(x, y), 0)),
            pl.BlockSpec((1, block, ni), lambda z, x, y: (z, x, 0)),
            pl.BlockSpec((1, block, 1), lambda z, x, y: (z, x, 0))],
        out_specs=[
            pl.BlockSpec((1, block, 1), lambda z, x, y: (z, x, 0)),
            pl.BlockSpec((1, ni, block, di), lambda z, x, y: (z, 0, x, 0)),
            pl.BlockSpec((1, block, ni), lambda z, x, y: (z, x, 0)),
            pl.BlockSpec((1, di, t), lambda z, x, y: (z, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, 1), _F32),
                   jax.ShapeDtypeStruct(qi.shape, _F32),
                   jax.ShapeDtypeStruct((b, t, ni), _F32),
                   jax.ShapeDtypeStruct((b, di, t), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret)(mask, q, k, lse, qi, ki, w, lse_i)
    return jnp.sum(kl), dqi, jnp.swapaxes(dkt, 1, 2), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def indexer_kl(q, k, lse, qi, ki, w, mask, lse_i, block: int,
               interpret: bool):
    """The indexer's loss, ``mean_t KL(p_t || softmax_{S_t} I[t, .])``, with
    ``p_t`` the query heads' mean attention over the kept set ``S_t`` (from
    :func:`attention`'s ``q``, ``k`` and ``lse``; no gradient) and ``lse_i``
    :func:`select`'s.  Only ``qi``, ``ki`` and ``w`` take a gradient."""
    return _indexer_kl(q, k, lse, qi, ki, w, mask, lse_i, block=block,
                       interpret=interpret)[0] / (q.shape[0] * q.shape[2])


def _kl_fwd(q, k, lse, qi, ki, w, mask, lse_i, block, interpret):
    kl, dqi, dki, dw = _indexer_kl(q, k, lse, qi, ki, w, mask, lse_i,
                                   block=block, interpret=interpret)
    rows = q.shape[0] * q.shape[2]
    return kl / rows, checkpoint_name(
        tuple((grad / rows).astype(x.dtype) for grad, x in
              zip((dqi, dki, dw), (qi, ki, w))), SAVED[2])


def _kl_bwd(block, interpret, saved, g):
    return (None, None, None) + tuple(
        (g * x).astype(x.dtype) for x in saved) + (None, None)


indexer_kl.defvjp(_kl_fwd, _kl_bwd)
