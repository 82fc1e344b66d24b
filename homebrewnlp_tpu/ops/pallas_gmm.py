"""Grouped matrix products as two Mosaic kernels.

What ``jax.lax.ragged_dot`` computes for the experts' loop
(``ops/grouped_ffn.py``): the rows ``[M, K]`` are runs, one a group, and run
``g`` is multiplied with matrix ``g`` of a stack ``[G, K, N]``.  The runs
here start on multiples of ``ROW_TILE`` rows (``grouped_ffn`` lays its
sorted pairs out so), so a tile of rows belongs to one group, the grid is one
cell a tile whatever the groups' sizes, no tile is masked or visited twice,
and the kernels' time does not follow the routing.  The tile's group comes
to the index maps by scalar prefetch: a group's matrix is fetched when the
group changes and stays in VMEM over the group's tiles.

``_gmm_rows`` multiplies the rows with their matrices (the forward) or, with
``transposed``, with their matrices' transposes (the gradient for the rows:
the same ``[K, N]`` block, contracted over its last axis inside the kernel;
no transposed stack is written to HBM).  ``_gmm_weights`` is the gradient for
the stack, ``rows^T dout`` a group: summed over the group's tiles in a
float32 VMEM accumulator and written once a group.  A group without a row is
visited by no tile, and :func:`grouped_dot` writes its zeros.

The rounding points are ``ragged_dot``'s: operands in their own type,
products summed in float32, every result rounded once to the operands' type.
"""
from __future__ import annotations

import functools
import logging
import typing

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# rows of a tile: a run of rows starts on a multiple of it.  Timed alone on
# the v5e (PERF.md, PR 32), tiles of 256, 512 and 1,024 rows multiply at the
# same rate a row; the smallest leaves the fewest rows without a pair
ROW_TILE = 256
# the kernels take a product whose runs' alignment adds at most one row in
# this many (a few rows, as a decoding step's, stay with ``ragged_dot``)
ROWS_A_TILE_ROW = 8
# what a kernel may ask of the v5e's 128 MiB of VMEM
VMEM_BYTES = 64 * 2 ** 20


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=_F32)


def vmem_bytes(k: int, n: int, itemsize: int) -> int:
    """The larger VMEM need of the two kernels: a tile of rows ``[ROW_TILE,
    K]`` and of results ``[ROW_TILE, N]`` and a group's matrix ``[K, N]``,
    two buffers each, with the float32 product before it is rounded
    (``_gmm_rows``) or the float32 sum and one tile's share of it
    (``_gmm_weights``)."""
    blocks = 2 * itemsize * (ROW_TILE * (k + n) + k * n)
    return blocks + 4 * max(ROW_TILE * max(k, n), 2 * k * n) + 4 * 2 ** 20


def refusals(m: int, groups: int, k: int, n: int, itemsize: int
             ) -> typing.List[str]:
    """Every clause by which the kernels turn a product away; none where
    they take it."""
    out = []
    if m % ROW_TILE:
        out.append(f"{m} rows are no whole number of tiles of {ROW_TILE}")
    if k % 128 or n % 128:
        out.append(f"K {k} or N {n} is no multiple of 128")
    least = (ROWS_A_TILE_ROW + 1) * groups * ROW_TILE
    if m < least:
        out.append(f"{m} rows are fewer than {ROWS_A_TILE_ROW + 1} tiles a "
                   f"group ({least})")
    need = vmem_bytes(k, n, itemsize)
    if need > VMEM_BYTES:
        out.append(f"the blocks of [{k} x {n}] need {need} bytes of VMEM, "
                   f"over {VMEM_BYTES}")
    return out


def _fits(m: int, groups: int, k: int, n: int, itemsize: int) -> bool:
    return not refusals(m, groups, k, n, itemsize)


def takes_kernels(rows, stack) -> bool:
    """Whether the kernels run this product: ``K`` and ``N`` whole lane
    tiles, the rows a whole number of row tiles and enough of them that a
    tile a group is at most one row in ``ROWS_A_TILE_ROW + 1``, and the
    blocks within VMEM."""
    return _fits(rows.shape[0], *stack.shape, rows.dtype.itemsize)


def aligned_rows(pairs: int, groups: int, tile: int) -> int:
    """Rows that hold ``pairs`` rows in ``groups`` runs, each a whole number
    of ``tile`` rows: a run may end up to ``tile - 1`` rows short."""
    return -(-(pairs + groups * (tile - 1)) // tile) * tile


def row_tile(pairs: int, groups: int, k: int, n: int, itemsize: int) -> int:
    """What the runs of ``pairs`` rows in all must start on multiples of
    before :func:`grouped_dot` multiplies them with stacks ``[groups, k, n]``
    and ``[groups, n, k]``: ``ROW_TILE`` where the kernels take the rows so
    laid out, else 1 (``ragged_dot`` takes any run).  The log says once a
    shape which of the two it is, and by which clauses."""
    why = refusals(aligned_rows(pairs, groups, ROW_TILE), groups, k, n,
                   itemsize)
    _say_once(pairs, groups, k, n, "; ".join(why))
    return 1 if why else ROW_TILE


@functools.lru_cache(maxsize=None)
def _say_once(pairs: int, groups: int, k: int, n: int, why: str) -> None:
    log = logging.getLogger(__name__)
    what = f"grouped products of {pairs} pairs with {groups} x [{k} x {n}]"
    if why:
        log.warning("%s run as jax.lax.ragged_dot: %s", what, why)
    else:
        log.info("%s run as the Mosaic kernels _gmm_rows / _gmm_weights",
                 what)


def tile_groups(sizes, tiles: int):
    """The group of each of ``tiles`` tiles of rows, for runs of ``sizes``
    rows (multiples of ``ROW_TILE``) laid end to end."""
    first = jnp.arange(tiles, dtype=jnp.int32) * ROW_TILE
    group = jnp.searchsorted(jnp.cumsum(sizes), first, side="right")
    return jnp.minimum(group, sizes.shape[0] - 1).astype(jnp.int32)


def _rows_kernel(group_ref, x_ref, w_ref, o_ref, *, transposed: bool):
    del group_ref
    o_ref[...] = _dot(x_ref[...], w_ref[0], _NT if transposed else _NN
                      ).astype(o_ref.dtype)


def _weights_kernel(group_ref, x_ref, d_ref, o_ref, sum_ref):
    from jax.experimental import pallas as pl
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    mine = group_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != mine))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    sum_ref[...] += _dot(x_ref[...], d_ref[...], _TN)

    @pl.when((i == last) | (group_ref[jnp.minimum(i + 1, last)] != mine))
    def _():
        o_ref[0] = sum_ref[...].astype(o_ref.dtype)


def _call(kernel, x, k: int, n: int, order: str, out_shape,
          interpret: bool, scratch_shapes=(), **specs):
    """One grid cell a tile of ``x``'s rows, the tiles' groups prefetched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(x.shape[0] // ROW_TILE,),
            scratch_shapes=scratch_shapes, **specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(order,),
            vmem_limit_bytes=vmem_bytes(k, n, x.dtype.itemsize)),
        out_shape=out_shape, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _gmm_rows(x, stack, group, transposed: bool = False,
              interpret: bool = False):
    """``x [M, K] -> [M, N]`` (``transposed``: ``x [M, N] -> [M, K]``): tile
    ``i`` of ``x`` times matrix ``group[i]`` of ``stack [G, K, N]``."""
    from jax.experimental import pallas as pl
    m, (_, k, n) = x.shape[0], stack.shape
    out = k if transposed else n
    return _call(
        functools.partial(_rows_kernel, transposed=transposed), x, k, n,
        "parallel", jax.ShapeDtypeStruct((m, out), x.dtype), interpret,
        in_specs=[pl.BlockSpec((ROW_TILE, x.shape[1]), lambda i, g: (i, 0)),
                  pl.BlockSpec((1, k, n), lambda i, g: (g[i], 0, 0))],
        out_specs=pl.BlockSpec((ROW_TILE, out), lambda i, g: (i, 0)),
    )(group, x, stack)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _gmm_weights(x, dout, group, groups: int, interpret: bool = False):
    """``[groups, K, N]``: ``x[run g]^T dout[run g]`` for every group with a
    tile among ``group``; what a group without one holds is not defined."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k, n = x.shape[1], dout.shape[1]
    return _call(
        _weights_kernel, x, k, n, "arbitrary",
        jax.ShapeDtypeStruct((groups, k, n), x.dtype), interpret,
        scratch_shapes=[pltpu.VMEM((k, n), _F32)],
        in_specs=[pl.BlockSpec((ROW_TILE, k), lambda i, g: (i, 0)),
                  pl.BlockSpec((ROW_TILE, n), lambda i, g: (i, 0))],
        out_specs=pl.BlockSpec((1, k, n), lambda i, g: (g[i], 0, 0)),
    )(group, x, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_dot(rows, stack, sizes, interpret: bool):
    group = tile_groups(sizes, rows.shape[0] // ROW_TILE)
    return _gmm_rows(rows, stack, group, interpret=interpret)


def _fwd(rows, stack, sizes, interpret):
    return _kernel_dot(rows, stack, sizes, interpret), (rows, stack, sizes)


def _bwd(interpret, saved, dout):
    rows, stack, sizes = saved
    group = tile_groups(sizes, rows.shape[0] // ROW_TILE)
    dstack = _gmm_weights(rows, dout, group, groups=stack.shape[0],
                          interpret=interpret)
    return (_gmm_rows(dout, stack, group, transposed=True,
                      interpret=interpret),
            jnp.where((sizes > 0)[:, None, None], dstack, 0), None)


_kernel_dot.defvjp(_fwd, _bwd)


def grouped_dot(rows, stack, sizes):
    """``jax.lax.ragged_dot(rows, stack, sizes)`` for ``rows [M, K]``,
    ``stack [G, K, N]`` and runs of ``sizes [G]`` rows that add up to ``M``.
    The shape alone chooses what multiplies (:func:`takes_kernels`); the
    kernels ask that every run be a whole number of ``ROW_TILE`` rows."""
    if not takes_kernels(rows, stack):
        return jax.lax.ragged_dot(rows, stack, sizes)
    from . import pallas_interpret
    return _kernel_dot(rows, stack, sizes, pallas_interpret())
