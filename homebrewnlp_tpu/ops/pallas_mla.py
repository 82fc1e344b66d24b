"""Causal softmax attention as two Mosaic kernels (forward, backward).

What ``ops/block_attention.py``'s unrolled tiles compute, with a tile's
scores, running maximum and sum, exponentials and weights in VMEM: only
``q``, ``k``, ``v``, the output and one float32 row statistic a query (the
log of the row's sum of exponentials) cross HBM.  One grid cell a (batch
row, query head, block of ``block`` query rows); the head's keys and values
stay in VMEM over its blocks of rows, and a cell walks the key tiles up to
its diagonal (the triangle the mask leaves), masking only the diagonal tile.
Key and value widths may differ (latent attention: 192 and 128).

Two things a layer may ask besides (``models/hybrid.py::gqa``), both read
from the operands and ``window``, neither a switch.  *Grouped K/V heads*:
``k`` and ``v`` have ``H / group`` heads, query head ``h`` reads K/V head
``h // group`` (the block index of ``k`` and ``v``, so a K/V head is fetched
once for its group's consecutive cells and never copied ``group`` times in
HBM), and in the backward a K/V head's ``dK``, ``dV`` are summed in float32
over its group's query heads as over their blocks of rows, and written once.
*A window*: a row sees its last ``window`` positions, itself among them, so
a cell walks only the key tiles that meet that band (``_band``: with blocks
of 512 and a window of 1,024 three tiles, the farthest masked at the band's
far edge, the diagonal by the causal mask), forward and backward alike; the
tiles before the band are neither computed nor stepped over.

The rounding points are ``_tile``'s: scores from operands in their own type
added up in float32; mask, maximum, exponentials, sum and the output
accumulator float32; the weights rounded to ``v``'s type before the second
product; the output rounded once at the end.

The backward recomputes each tile's weights from ``q``, ``k`` and the saved
row statistic and saves nothing tile-sized.  With ``P = exp(S - lse)``, ``dP
= dO V^T`` and ``delta = rowsum(dO * O)``: ``dS = P * (dP - delta)``, ``dV =
P^T dO``, ``dK = dS^T Q``, ``dQ = dS K``.  ``P``, ``dS`` and ``dO`` enter
their products in the operands' type, as the matrix unit takes the
cotangents in autodiff of ``_tile``; ``dP`` stays float32 (autodiff rounds
it); ``dQ`` of a block of rows and ``dK``, ``dV`` of a head are summed in
float32 (VMEM) over all their tiles and rounded once, where autodiff adds a
tile's rounded shares.  A cell is a block of rows again, so the row
statistics turn from a row of lanes into a column once a cell, not a tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# rows and keys of a tile
BLOCK = 512
# what a kernel may ask of the v5e's 128 MiB of VMEM
VMEM_BYTES = 64 * 2 ** 20


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=_F32)


def _row_and_key(block: int):
    """The row's and the key's place in a ``[block, block]`` tile on the
    diagonal."""
    shape = (block, block)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _band(mine, block: int, window):
    """``(first, clear)``: of the key tiles before the diagonal tile ``mine``,
    ``[first, clear)`` cross the window's far edge and ``[clear, mine)`` lie
    whole inside the band; the tiles before ``first`` meet no row of the
    block.  Without a window every tile before the diagonal is clear."""
    if window is None:
        return 0, 0
    # the nearest pair of tile j lies (mine - j - 1) * block + 1 behind its
    # row, the farthest (mine - j + 1) * block - 1
    reach, whole = (window - 2) // block + 1, max(window // block - 1, 0)
    return jnp.maximum(mine - reach, 0), jnp.maximum(mine - whole, 0)


def _masked(scores, row, key, mine, j, block: int, window, diagonal: bool):
    """A tile's scores with what its rows do not see at ``-inf``: on the
    diagonal the keys after a row (and, under a window shorter than a block,
    those ``window`` or more behind it); on a tile that crosses the band's
    far edge, ``(mine - j) * block`` further behind, the latter alone."""
    if diagonal:
        seen = row >= key
        if window is not None and window < block:
            seen &= row - key < window
    else:
        seen = row - key < window - (mine - j) * block
    return jnp.where(seen, scores, -jnp.inf)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block: int, window):
    from jax.experimental import pallas as pl
    mine = pl.program_id(2)
    q = q_ref[0, 0]
    row, key = _row_and_key(block)

    def tile(j, carry, edge):
        """``edge``: None inside the band, else which mask the tile takes."""
        top, total, out = carry
        at = pl.ds(pl.multiple_of(j * block, block), block)
        v = v_ref[0, 0, at, :]
        scores = _dot(q, k_ref[0, 0, at, :], _NT)
        if edge is not None:
            scores = _masked(scores, row, key, mine, j, block, window,
                             edge == "diagonal")
        new_top = jnp.maximum(top, jnp.max(scores, 1, keepdims=True))
        if edge == "far":
            # a row that has seen no key yet (the band's far edge hides this
            # whole tile from it, and it is the row's first): no -inf - -inf
            new_top = jnp.where(new_top > -jnp.inf, new_top, 0.0)
        weights = jnp.exp(scores - new_top)
        keep = jnp.exp(top - new_top)
        return (new_top, total * keep + jnp.sum(weights, 1, keepdims=True),
                out * keep + _dot(weights.astype(v.dtype), v))

    carry = (jnp.full((block, 1), -jnp.inf, _F32), jnp.zeros((block, 1), _F32),
             jnp.zeros((block, v_ref.shape[-1]), _F32))
    first, clear = _band(mine, block, window)
    if window is not None:
        carry = jax.lax.fori_loop(first, clear,
                                  lambda j, c: tile(j, c, "far"), carry)
    carry = jax.lax.fori_loop(clear, mine, lambda j, c: tile(j, c, None),
                              carry)
    top, total, out = tile(mine, carry, "diagonal")
    o_ref[0, 0] = (out / total).astype(o_ref.dtype)
    # a column a row of the block -> a row of lanes, without a transpose
    lse_ref[0, 0] = jnp.sum(jnp.where(row == key, top + jnp.log(total), 0.0),
                            0, keepdims=True)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref, dk_ref,
                dv_ref, dk_sum, dv_sum, *, block: int, group: int, window):
    from jax.experimental import pallas as pl
    mine = pl.program_id(2)

    def of_member(cell, member: int):
        """``cell``, in the cells of query head ``member`` of its group: a
        K/V head's sums run over all its group's heads."""
        if group == 1:
            return cell
        return cell & (pl.program_id(1) % group == member)

    @pl.when(of_member(mine == 0, 0))
    def _():
        dk_sum[...] = jnp.zeros_like(dk_sum)
        dv_sum[...] = jnp.zeros_like(dv_sum)

    q, do = q_ref[0, 0], do_ref[0, 0]
    row, key = _row_and_key(block)
    lse = jnp.sum(jnp.where(row == key, lse_ref[0, 0], 0.0), 1, keepdims=True)
    delta = jnp.sum(do.astype(_F32) * o_ref[0, 0].astype(_F32), 1,
                    keepdims=True)

    def tile(j, dq, edge):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        k, v = k_ref[0, 0, at, :], v_ref[0, 0, at, :]
        scores = _dot(q, k, _NT)
        if edge is not None:
            scores = _masked(scores, row, key, mine, j, block, window,
                             edge == "diagonal")
        weights = jnp.exp(scores - lse)
        dv_sum[at, :] += _dot(weights.astype(do.dtype), do, _TN)
        ds = (weights * (_dot(do, v, _NT) - delta)).astype(q.dtype)
        dk_sum[at, :] += _dot(ds, q, _TN)
        return dq + _dot(ds, k)

    dq = jnp.zeros(q.shape, _F32)
    first, clear = _band(mine, block, window)
    if window is not None:
        dq = jax.lax.fori_loop(first, clear, lambda j, x: tile(j, x, "far"),
                               dq)
    dq = jax.lax.fori_loop(clear, mine, lambda j, x: tile(j, x, None), dq)
    dq_ref[0, 0] = tile(mine, dq, "diagonal").astype(dq_ref.dtype)

    @pl.when(of_member(mine == pl.num_programs(2) - 1, group - 1))
    def _():
        def out(j, _):
            at = pl.ds(pl.multiple_of(j * block, block), block)
            dk_ref[0, 0, at, :] = dk_sum[at, :].astype(dk_ref.dtype)
            dv_ref[0, 0, at, :] = dv_sum[at, :].astype(dv_ref.dtype)

        jax.lax.fori_loop(0, pl.num_programs(2), out, None)


def _specs(s: int, block: int, group: int):
    """Block specs of a grid (batch row, query head, block of rows): ``[B,
    H, S, .]`` by block of rows (``q``, the output, their cotangents), whole
    a K/V head (``k``, ``v``, theirs: head ``j // group`` of ``[B, H /
    group, S, .]``), and the row statistic ``[B, H, 1, S]``, queries in the
    lanes."""
    from jax.experimental import pallas as pl

    def rows(width):
        return pl.BlockSpec((1, 1, block, width), lambda i, j, m: (i, j, m, 0))

    def head(width):
        return pl.BlockSpec(
            (1, 1, s, width),
            lambda i, j, m: (i, j if group == 1 else j // group, 0, 0))

    stat = pl.BlockSpec((1, 1, 1, block), lambda i, j, m: (i, j, 0, m))
    return rows, head, stat


def vmem_bytes(s: int, d: int, d_v: int, itemsize: int) -> int:
    """The backward's VMEM need: what grows with the sequence (a head's
    ``k``, ``v`` and their gradients, two buffers each, and the two float32
    sums; widths padded to whole lane tiles) and 16 MiB for the blocks of
    rows and a tile's temporaries."""
    lanes = -(-d // 128) * 128 + -(-d_v // 128) * 128
    return s * lanes * (4 * itemsize + 4) + 16 * 2 ** 20


def _call(kernel, q, v, block: int, order, **kwargs):
    """``order``: the grid's semantics over (query head, block of rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    return pl.pallas_call(
        kernel, grid=(b, h, s // block),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + order,
            vmem_limit_bytes=vmem_bytes(s, d, v.shape[-1], q.dtype.itemsize)),
        **kwargs)


@functools.partial(jax.jit, static_argnames=("block", "window", "interpret"))
def _mla_attention_fwd(q, k, v, block: int, window=None,
                       interpret: bool = False):
    b, h, s, d = q.shape
    d_v = v.shape[-1]
    rows, head, stat = _specs(s, block, h // k.shape[1])
    return _call(
        functools.partial(_fwd_kernel, block=block, window=window), q, v,
        block, ("parallel", "parallel"),
        in_specs=[rows(d), head(d), head(d_v)], out_specs=[rows(d_v), stat],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d_v), v.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), _F32)],
        interpret=interpret)(q, k, v)


@functools.partial(jax.jit, static_argnames=("block", "window", "interpret"))
def _mla_attention_bwd(q, k, v, o, lse, do, block: int, window=None,
                       interpret: bool = False):
    from jax.experimental.pallas import tpu as pltpu
    s, d = q.shape[2:]
    d_v = v.shape[-1]
    group = q.shape[1] // k.shape[1]
    rows, head, stat = _specs(s, block, group)
    return _call(
        functools.partial(_bwd_kernel, block=block, group=group,
                          window=window), q, v, block,
        # dK, dV are summed over the blocks of rows and, grouped, the heads
        ("parallel" if group == 1 else "arbitrary", "arbitrary"),
        in_specs=[rows(d), head(d), head(d_v), rows(d_v), stat, rows(d_v)],
        out_specs=[rows(d), head(d), head(d_v)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((s, d), _F32), pltpu.VMEM((s, d_v), _F32)],
        interpret=interpret)(q, k, v, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, block: int, window, interpret: bool):
    """``softmax(q k^T + mask) v`` for ``q [B, H, S, D]`` (already scaled),
    ``k [B, H / group, S, D]`` and ``v [B, H / group, S, Dv]``, ``S`` a
    multiple of ``block``; the mask is causal and, with ``window``, hides
    the keys ``window`` positions or more behind a row."""
    return _mla_attention_fwd(q, k, v, block=block, window=window,
                              interpret=interpret)[0]


def _fwd(q, k, v, block, window, interpret):
    o, lse = _mla_attention_fwd(q, k, v, block=block, window=window,
                                interpret=interpret)
    return o, (q, k, v, o, lse)


def _bwd(block, window, interpret, saved, do):
    return tuple(_mla_attention_bwd(*saved, do, block=block, window=window,
                                    interpret=interpret))


flash_attention.defvjp(_fwd, _bwd)
