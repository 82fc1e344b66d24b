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
float32 VMEM accumulator and written once a group.  Where the float32 ``[K,
N]`` sum does not fit VMEM it is taken a block of ``N`` at a time
(:func:`weight_blocks`: the least count that fits, 1 at 2,304 x 896 and
2,304 x 1,024, 2 at 4,096 x 1,280): an outer grid axis over the blocks with
the row tiles inside it, the rows read once a block.  A group without a row
is visited by no tile, and :func:`grouped_dot` writes its zeros.

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
# the kernels take a product whose groups hold this many pairs on average or
# more.  Timed alone on the v5e against ``ragged_dot`` at 1/4 to 8 tiles of
# pairs a group (PERF.md, PR 36), the rows their alignment adds included:
# from half a tile on they are the quicker at 4,096 x 1,280 (1.9 times) and
# no slower at 2,304 x 1,024, at a quarter 3% the slower there; a decoding
# step's few rows stay with ``ragged_dot``
PAIRS_A_GROUP = ROW_TILE // 2
# what a kernel may ask of the v5e's 128 MiB of VMEM
VMEM_BYTES = 64 * 2 ** 20
# of it, what the compiler may need beside the blocks
_SPARE_BYTES = 4 * 2 ** 20


def _dot(a, b, contract=_NN):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=_F32)


def _blocks_bytes(k: int, n: int, itemsize: int) -> int:
    """A tile of rows ``[ROW_TILE, K]``, one ``[ROW_TILE, N]`` and a matrix
    ``[K, N]``, two buffers each."""
    return 2 * itemsize * (ROW_TILE * (k + n) + k * n)


def rows_vmem_bytes(k: int, n: int, itemsize: int) -> int:
    """What ``_gmm_rows`` needs of VMEM: its blocks and the float32 product
    before it is rounded."""
    return (_blocks_bytes(k, n, itemsize) + 4 * ROW_TILE * max(k, n)
            + _SPARE_BYTES)


def weights_vmem_bytes(k: int, n: int, itemsize: int, blocks: int) -> int:
    """What ``_gmm_weights`` needs of VMEM with ``N`` in ``blocks`` blocks:
    its blocks, the float32 sum ``[K, N / blocks]`` and one tile's share of
    it."""
    n //= blocks
    return _blocks_bytes(k, n, itemsize) + 4 * 2 * k * n + _SPARE_BYTES


def weight_blocks(k: int, n: int, itemsize: int) -> int:
    """In how many blocks of ``N``, whole lane tiles each, ``_gmm_weights``
    sums ``[K, N]``: the least count that fits ``VMEM_BYTES``.  At one lane
    tile a block it needs no more than ``_gmm_rows`` does, so a count is
    found wherever that kernel fits; 0 if none is."""
    return next((blocks for blocks in range(1, n // 128 + 1)
                 if n % (128 * blocks) == 0 and weights_vmem_bytes(
                     k, n, itemsize, blocks) <= VMEM_BYTES), 0)


def refusals(m: int, groups: int, k: int, n: int, itemsize: int
             ) -> typing.List[str]:
    """Every clause by which the kernels turn a product away; none where
    they take it."""
    out = []
    if m % ROW_TILE:
        out.append(f"{m} rows are no whole number of tiles of {ROW_TILE}")
    # laid out on tiles, ``pairs`` rows in ``groups`` runs are the tiles that
    # hold ``pairs - groups`` rows and one more a group (``aligned_rows``)
    least = groups * (ROW_TILE + PAIRS_A_GROUP)
    if m < least:
        out.append(f"{m} rows on tiles hold fewer than {PAIRS_A_GROUP} pairs "
                   f"a group ({least} rows)")
    if k % 128 or n % 128:
        out.append(f"K {k} or N {n} is no multiple of 128")
    need = rows_vmem_bytes(k, n, itemsize)
    if need > VMEM_BYTES:
        out.append(f"_gmm_rows' blocks of [{k} x {n}] need {need} bytes of "
                   f"VMEM, over {VMEM_BYTES}")
    return out


def takes_kernels(rows, stack) -> bool:
    """Whether the kernels run this product: ``K`` and ``N`` whole lane
    tiles, the rows a whole number of row tiles that hold
    ``PAIRS_A_GROUP`` pairs a group or more, and both kernels' blocks
    within VMEM."""
    return not refusals(rows.shape[0], *stack.shape, rows.dtype.itemsize)


def aligned_rows(pairs: int, groups: int, tile: int) -> int:
    """Rows that hold ``pairs`` rows in ``groups`` runs, each a whole number
    of ``tile`` rows: a run may end up to ``tile - 1`` rows short."""
    return -(-(pairs + groups * (tile - 1)) // tile) * tile


def row_tile(pairs: int, groups: int, k: int, n: int, itemsize: int) -> int:
    """What the runs of ``pairs`` rows in all must start on multiples of
    before :func:`grouped_dot` multiplies them with stacks ``[groups, k, n]``
    and ``[groups, n, k]``: ``ROW_TILE`` where the kernels take the rows so
    laid out (no clause tells ``k`` from ``n``), else 1 (``ragged_dot``
    takes any run).  The log says once a shape which of the two it is, and
    by which clauses."""
    why = refusals(aligned_rows(pairs, groups, ROW_TILE), groups, k, n,
                   itemsize)
    _say_once(pairs, groups, k, n, itemsize, "; ".join(why))
    return 1 if why else ROW_TILE


@functools.lru_cache(maxsize=None)
def _say_once(pairs: int, groups: int, k: int, n: int, itemsize: int,
              why: str) -> None:
    log = logging.getLogger(__name__)
    what = f"grouped products of {pairs} pairs with {groups} x [{k} x {n}]"
    if why:
        log.warning("%s run as jax.lax.ragged_dot: %s", what, why)
    else:
        log.info("%s run as the Mosaic kernels _gmm_rows / _gmm_weights on "
                 "%d rows, the stacks' gradients summed in %d and %d blocks",
                 what, aligned_rows(pairs, groups, ROW_TILE),
                 weight_blocks(k, n, itemsize), weight_blocks(n, k, itemsize))


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
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    mine = group_ref[i]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != mine))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    sum_ref[...] += _dot(x_ref[...], d_ref[...], _TN)

    @pl.when((i == last) | (group_ref[jnp.minimum(i + 1, last)] != mine))
    def _():
        o_ref[0] = sum_ref[...].astype(o_ref.dtype)


def _call(kernel, grid, order, vmem: int, out_shape, interpret: bool,
          scratch_shapes=(), **specs):
    """``kernel`` over ``grid``, whose last axis is the tiles of rows; the
    tiles' groups are prefetched for the index maps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, scratch_shapes=scratch_shapes,
            **specs),
        compiler_params=pltpu.CompilerParams(dimension_semantics=order,
                                             vmem_limit_bytes=vmem),
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
        functools.partial(_rows_kernel, transposed=transposed),
        (m // ROW_TILE,), ("parallel",),
        rows_vmem_bytes(k, n, x.dtype.itemsize),
        jax.ShapeDtypeStruct((m, out), x.dtype), interpret,
        in_specs=[pl.BlockSpec((ROW_TILE, x.shape[1]), lambda i, g: (i, 0)),
                  pl.BlockSpec((1, k, n), lambda i, g: (g[i], 0, 0))],
        out_specs=pl.BlockSpec((ROW_TILE, out), lambda i, g: (i, 0)),
    )(group, x, stack)


@functools.partial(jax.jit, static_argnames=("groups", "blocks", "interpret"))
def _gmm_weights(x, dout, group, groups: int, blocks: int,
                 interpret: bool = False):
    """``[groups, K, N]``: ``x[run g]^T dout[run g]`` for every group with a
    tile among ``group``, a block ``[K, N / blocks]`` at a time over all the
    tiles; what a group without a tile holds is not defined."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k, n = x.shape[1], dout.shape[1]
    block = n // blocks
    return _call(
        _weights_kernel, (blocks, x.shape[0] // ROW_TILE),
        ("parallel", "arbitrary"),
        weights_vmem_bytes(k, n, x.dtype.itemsize, blocks),
        jax.ShapeDtypeStruct((groups, k, n), x.dtype), interpret,
        scratch_shapes=[pltpu.VMEM((k, block), _F32)],
        in_specs=[pl.BlockSpec((ROW_TILE, k), lambda j, i, g: (i, 0)),
                  pl.BlockSpec((ROW_TILE, block), lambda j, i, g: (i, j))],
        out_specs=pl.BlockSpec((1, k, block), lambda j, i, g: (g[i], 0, j)),
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
    dstack = _gmm_weights(
        rows, dout, group, groups=stack.shape[0],
        blocks=weight_blocks(*stack.shape[1:], rows.dtype.itemsize),
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
