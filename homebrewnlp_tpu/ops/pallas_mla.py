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

Where a head's ``k``, ``v`` and their gradients do not fit VMEM whole
(``vmem_bytes`` over ``VMEM_BYTES``: past 10,752 tokens at 192 / 128), the
key-block kernels walk them through the grid instead, ``KEYS`` keys a cell
(``key_chunk``): the forward and ``dQ`` over (block of rows, cell of keys),
the running triple or ``dQ`` kept in float32 scratch from one cell to the
next and written at the diagonal's cell; ``dK``, ``dV`` over (cell of keys,
group member, block of rows), summed in float32 scratch over the blocks of
rows and the group's query heads and written once.  Inside a cell the tiles
are ``_fwd_kernel``'s and ``_bwd_kernel``'s, with the same masks, band and
rounding points, and only the tiles at or below the diagonal and inside the
band are computed; a cell that meets none computes nothing, and its index
map repeats the nearest cell that does, so nothing is fetched for it.  The
backward's ``delta = rowsum(dO * O)`` is taken once in XLA and read as a row
statistic.

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
from jax.ad_checkpoint import checkpoint_name

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


def _online_tile(q, k, v, carry, row, key, mine, j, block: int, window,
                 edge):
    """Key tile ``j`` into a block of rows' running (maximum, sum, output);
    ``edge``: None inside the band, else which mask the tile takes."""
    top, total, out = carry
    scores = _dot(q, k, _NT)
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block: int, window):
    from jax.experimental import pallas as pl
    mine = pl.program_id(2)
    q = q_ref[0, 0]
    row, key = _row_and_key(block)

    def tile(j, carry, edge):
        at = pl.ds(pl.multiple_of(j * block, block), block)
        v = v_ref[0, 0, at, :]
        return _online_tile(q, k_ref[0, 0, at, :], v, carry, row, key, mine,
                            j, block, window, edge)

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


# -- key blocks: a head's keys walked through the grid ----------------------

#: keys of a grid cell of the key-block kernels, at most
KEYS = 4096
#: the name of the key-block forward's outputs, which a part's checkpoint
#: keeps
KEPT = "key_block_attention"


def key_chunk(s: int, block: int = BLOCK) -> int:
    """Keys of a cell of the key-block kernels for a sequence of ``s``: the
    largest whole number of ``block`` tiles that divides ``s`` and is at
    most ``KEYS``."""
    tiles = s // block
    return block * max(t for t in range(1, max(KEYS // block, 1) + 1)
                       if tiles % t == 0)


def _walk(tile, carry, mine, lo, tiles: int, block: int, window):
    """``carry`` through ``tile(j, carry, edge)`` over the key tiles of
    ``[lo, lo + tiles)`` that block of rows ``mine`` meets before its
    diagonal (``_band``'s ranges cut to the cell).  Returns ``(carry,
    whether the cell holds the diagonal)``; the diagonal tile is the
    caller's."""
    first, clear = _band(mine, block, window)
    hi = lo + tiles
    if window is not None:
        carry = jax.lax.fori_loop(jnp.maximum(first, lo),
                                  jnp.minimum(clear, hi),
                                  lambda j, c: tile(j, c, "far"), carry)
    carry = jax.lax.fori_loop(jnp.maximum(clear, lo), jnp.minimum(mine, hi),
                              lambda j, c: tile(j, c, None), carry)
    return carry, (lo <= mine) & (mine < hi)


def _meets(mine, lo, tiles: int, block: int, window):
    """Whether block of rows ``mine`` meets a key tile of ``[lo, lo +
    tiles)``."""
    return (lo <= mine) & (_band(mine, block, window)[0] < lo + tiles)


def _column(stat, row, key):
    """A ``[1, block]`` row statistic (queries in the lanes) as a column."""
    return jnp.sum(jnp.where(row == key, stat, 0.0), 1, keepdims=True)


def _fwd_blocks_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, top_sc, total_sc,
                       out_sc, *, block: int, window):
    """A block of rows against one cell of keys: ``_fwd_kernel``'s tiles,
    the running maximum, sum and output kept in scratch from one cell of
    the row's keys to the next."""
    from jax.experimental import pallas as pl
    mine, cell = pl.program_id(2), pl.program_id(3)
    tiles = k_ref.shape[2] // block
    lo = cell * tiles
    q = q_ref[0, 0]
    row, key = _row_and_key(block)

    @pl.when(cell == 0)
    def _():
        top_sc[...] = jnp.full(top_sc.shape, -jnp.inf, _F32)
        total_sc[...] = jnp.zeros(total_sc.shape, _F32)
        out_sc[...] = jnp.zeros(out_sc.shape, _F32)

    def tile(j, carry, edge):
        at = pl.ds(pl.multiple_of((j - lo) * block, block), block)
        v = v_ref[0, 0, at, :]
        return _online_tile(q, k_ref[0, 0, at, :], v, carry, row, key, mine,
                            j, block, window, edge)

    @pl.when(_meets(mine, lo, tiles, block, window))
    def _():
        carry, diagonal = _walk(tile, (top_sc[...], total_sc[...],
                                       out_sc[...]),
                                mine, lo, tiles, block, window)

        @pl.when(diagonal)
        def _():
            top, total, out = tile(mine, carry, "diagonal")
            o_ref[0, 0] = (out / total).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.sum(
                jnp.where(row == key, top + jnp.log(total), 0.0), 0,
                keepdims=True)

        @pl.when(jnp.logical_not(diagonal))
        def _():
            top_sc[...] = carry[0]
            total_sc[...] = carry[1]
            out_sc[...] = carry[2]


def _grads_of_tile(q, do, k, v, lse, delta, row, key, mine, j, block: int,
                   window, edge):
    """A tile's weights ``P`` (float32) and ``dS`` (``q``'s type), from the
    saved row statistics, as ``_bwd_kernel`` makes them."""
    scores = _dot(q, k, _NT)
    if edge is not None:
        scores = _masked(scores, row, key, mine, j, block, window,
                         edge == "diagonal")
    weights = jnp.exp(scores - lse)
    return weights, (weights * (_dot(do, v, _NT) - delta)).astype(q.dtype)


def _dq_blocks_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                      dq_sc, *, block: int, window):
    """``dQ`` of a block of rows, summed in float32 scratch over the cells of
    its keys and rounded once at its diagonal."""
    from jax.experimental import pallas as pl
    mine, cell = pl.program_id(2), pl.program_id(3)
    tiles = k_ref.shape[2] // block
    lo = cell * tiles

    @pl.when(cell == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, _F32)

    @pl.when(_meets(mine, lo, tiles, block, window))
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        row, key = _row_and_key(block)
        lse = _column(lse_ref[0, 0], row, key)
        delta = _column(delta_ref[0, 0], row, key)

        def tile(j, dq, edge):
            at = pl.ds(pl.multiple_of((j - lo) * block, block), block)
            k = k_ref[0, 0, at, :]
            _, ds = _grads_of_tile(q, do, k, v_ref[0, 0, at, :], lse, delta,
                                   row, key, mine, j, block, window, edge)
            return dq + _dot(ds, k)

        dq, diagonal = _walk(tile, dq_sc[...], mine, lo, tiles, block, window)

        @pl.when(diagonal)
        def _():
            dq_ref[0, 0] = tile(mine, dq, "diagonal").astype(dq_ref.dtype)

        @pl.when(jnp.logical_not(diagonal))
        def _():
            dq_sc[...] = dq


def _dkv_blocks_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_sum, dv_sum, *, block: int,
                       window):
    """``dK``, ``dV`` of one cell of keys, summed in float32 scratch over
    the blocks of rows (and the group's query heads) that meet them and
    rounded once."""
    from jax.experimental import pallas as pl
    cell, member, mine = (pl.program_id(2), pl.program_id(3),
                          pl.program_id(4))
    tiles = k_ref.shape[2] // block
    lo = cell * tiles
    first_cell = (member == 0) & (mine == 0)
    last_cell = ((member == pl.num_programs(3) - 1)
                 & (mine == pl.num_programs(4) - 1))

    @pl.when(first_cell)
    def _():
        dk_sum[...] = jnp.zeros_like(dk_sum)
        dv_sum[...] = jnp.zeros_like(dv_sum)

    @pl.when(_meets(mine, lo, tiles, block, window))
    def _():
        q, do = q_ref[0, 0], do_ref[0, 0]
        row, key = _row_and_key(block)
        lse = _column(lse_ref[0, 0], row, key)
        delta = _column(delta_ref[0, 0], row, key)

        def tile(j, carry, edge):
            at = pl.ds(pl.multiple_of((j - lo) * block, block), block)
            weights, ds = _grads_of_tile(
                q, do, k_ref[0, 0, at, :], v_ref[0, 0, at, :], lse, delta,
                row, key, mine, j, block, window, edge)
            dv_sum[at, :] += _dot(weights.astype(do.dtype), do, _TN)
            dk_sum[at, :] += _dot(ds, q, _TN)
            return carry

        _, diagonal = _walk(tile, 0, mine, lo, tiles, block, window)

        @pl.when(diagonal)
        def _():
            tile(mine, 0, "diagonal")

    @pl.when(last_cell)
    def _():
        def out(j, _):
            at = pl.ds(pl.multiple_of(j * block, block), block)
            dk_ref[0, 0, at, :] = dk_sum[at, :].astype(dk_ref.dtype)
            dv_ref[0, 0, at, :] = dv_sum[at, :].astype(dv_ref.dtype)

        jax.lax.fori_loop(0, tiles, out, None)


def _cell_of_keys(block: int, keys: int, window):
    """Index map of a row-major grid's cell of keys: the cells a block of
    rows does not meet repeat the nearest one it meets, so nothing is
    fetched for them."""
    tiles = keys // block

    def at(mine, cell):
        first = _band(mine, block, window)[0]
        return jnp.minimum(jnp.maximum(cell, first // tiles), mine // tiles)

    return at


def _block_of_rows(block: int, keys: int, window, blocks: int):
    """Index map of a key-major grid's block of rows: the blocks that meet
    none of a cell's keys repeat the nearest one that does."""
    tiles = keys // block

    def at(cell, mine):
        last = blocks - 1
        if window is not None:
            last = jnp.minimum(last, (cell + 1) * tiles - 1
                               + (window - 2) // block + 1)
        return jnp.minimum(jnp.maximum(mine, cell * tiles), last)

    return at


def _blocks_params(keys: int, d: int, d_v: int, itemsize: int, *order):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=order,
        vmem_limit_bytes=vmem_bytes(keys, d, d_v, itemsize))


@functools.partial(jax.jit,
                   static_argnames=("block", "keys", "window", "interpret"))
def _key_blocks_fwd(q, k, v, block: int, keys: int, window=None,
                    interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    d_v = v.shape[-1]
    group = h // k.shape[1]
    cell = _cell_of_keys(block, keys, window)
    rows = lambda w: pl.BlockSpec((1, 1, block, w),
                                  lambda i, j, m, c: (i, j, m, 0))
    chunk = lambda w: pl.BlockSpec(
        (1, 1, keys, w), lambda i, j, m, c: (i, j // group, cell(m, c), 0))
    stat = pl.BlockSpec((1, 1, 1, block), lambda i, j, m, c: (i, j, 0, m))
    return pl.pallas_call(
        functools.partial(_fwd_blocks_kernel, block=block, window=window),
        grid=(b, h, s // block, s // keys),
        in_specs=[rows(d), chunk(d), chunk(d_v)],
        out_specs=[rows(d_v), stat],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d_v), v.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), _F32)],
        scratch_shapes=[pltpu.VMEM((block, 1), _F32),
                        pltpu.VMEM((block, 1), _F32),
                        pltpu.VMEM((block, d_v), _F32)],
        compiler_params=_blocks_params(keys, d, d_v, q.dtype.itemsize,
                                       "parallel", "parallel", "parallel",
                                       "arbitrary"),
        interpret=interpret)(q, k, v)


@functools.partial(jax.jit,
                   static_argnames=("block", "keys", "window", "interpret"))
def _key_blocks_bwd(q, k, v, o, lse, do, block: int, keys: int, window=None,
                    interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    g, d_v = k.shape[1], v.shape[-1]
    group, blocks = h // g, s // block
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), -1)[:, :, None]
    operands = (q, k, v, do, lse, delta)
    params = functools.partial(_blocks_params, keys, d, d_v,
                               q.dtype.itemsize)

    cell = _cell_of_keys(block, keys, window)
    rows = lambda w: pl.BlockSpec((1, 1, block, w),
                                  lambda i, j, m, c: (i, j, m, 0))
    chunk = lambda w: pl.BlockSpec(
        (1, 1, keys, w), lambda i, j, m, c: (i, j // group, cell(m, c), 0))
    stat = pl.BlockSpec((1, 1, 1, block), lambda i, j, m, c: (i, j, 0, m))
    dq = pl.pallas_call(
        functools.partial(_dq_blocks_kernel, block=block, window=window),
        grid=(b, h, blocks, s // keys),
        in_specs=[rows(d), chunk(d), chunk(d_v), rows(d_v), stat, stat],
        out_specs=rows(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), _F32)],
        compiler_params=params("parallel", "parallel", "parallel",
                               "arbitrary"),
        interpret=interpret)(*operands)

    mine = _block_of_rows(block, keys, window, blocks)
    rows = lambda w: pl.BlockSpec(
        (1, 1, block, w),
        lambda i, j, c, n, m: (i, j * group + n, mine(c, m), 0))
    chunk = lambda w: pl.BlockSpec((1, 1, keys, w),
                                   lambda i, j, c, n, m: (i, j, c, 0))
    stat = pl.BlockSpec((1, 1, 1, block),
                        lambda i, j, c, n, m: (i, j * group + n, 0,
                                               mine(c, m)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_blocks_kernel, block=block, window=window),
        grid=(b, g, s // keys, group, blocks),
        in_specs=[rows(d), chunk(d), chunk(d_v), rows(d_v), stat, stat],
        out_specs=[chunk(d), chunk(d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((keys, d), _F32),
                        pltpu.VMEM((keys, d_v), _F32)],
        compiler_params=params("parallel", "parallel", "parallel",
                               "arbitrary", "arbitrary"),
        interpret=interpret)(*operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def key_block_attention(q, k, v, block: int, keys: int, window,
                        interpret: bool):
    """:func:`flash_attention` for a sequence whose head does not fit VMEM
    whole: the grid walks a head's keys ``keys`` at a time (a multiple of
    ``block`` dividing ``S``), forward and backward alike."""
    return _key_blocks_fwd(q, k, v, block=block, keys=keys, window=window,
                           interpret=interpret)[0]


def _blocks_fwd(q, k, v, block, keys, window, interpret):
    # one named value is both the output and the residual: a part's remat
    # that keeps it (models/__init__.py) has nothing left to run the kernel
    # for
    o, lse = checkpoint_name(
        _key_blocks_fwd(q, k, v, block=block, keys=keys, window=window,
                        interpret=interpret), KEPT)
    return o, (q, k, v, o, lse)


def _blocks_bwd(block, keys, window, interpret, saved, do):
    return _key_blocks_bwd(*saved, do, block=block, keys=keys, window=window,
                           interpret=interpret)


key_block_attention.defvjp(_blocks_fwd, _blocks_bwd)
