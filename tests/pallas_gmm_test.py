"""The experts' grouped products as Mosaic kernels (ops/pallas_gmm.py),
interpreted on the CPU at lane-tile widths against `jax.lax.ragged_dot`, alone
and through `ops/grouped_ffn.py`'s loop; what chooses them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.ops import grouped_ffn as gf
from homebrewnlp_tpu.ops import pallas_gmm as gmm

TILE = gmm.ROW_TILE
K, N = 128, 256
#: float32 sums may differ by their order; a bfloat16 result by one rounding
CLOSE = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
         jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _normal(seed, shape, dtype):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32
                             ).astype(dtype)


def _close(got, want, dtype, of_largest=False):
    """``of_largest``: a sum over many rows is as exact as its largest
    element's rounding, so the tolerance is taken of that."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = dict(CLOSE[dtype])
    if of_largest:
        tol["atol"] *= max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, **tol)


# -- the product alone --------------------------------------------------------

RUNS = {"balanced": [10, 10, 10, 10], "one_takes_all": [0, 40, 0, 0],
        "empty_first_and_last": [0, 17, 23, 0], "uneven": [1, 30, 2, 7]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("runs", sorted(RUNS))
def test_kernels_match_ragged_dot(runs, dtype):
    """The result and both gradients for runs of whole tiles: every group a
    tile or more, one group all of them, groups without a row (their
    gradient is zero, written by no tile of the kernel)."""
    sizes = jnp.array(RUNS[runs], jnp.int32) * TILE
    rows = _normal(0, (40 * TILE, K), dtype)
    stack = _normal(1, (4, K, N), dtype) * 0.1
    assert gmm.takes_kernels(rows, stack)

    def loss(dot):
        return lambda r, s: jnp.sum(jnp.sin(dot(r, s, sizes).astype(
            jnp.float32)))

    assert "pallas_call" in str(jax.make_jaxpr(gmm.grouped_dot)(rows, stack,
                                                                sizes))
    _close(gmm.grouped_dot(rows, stack, sizes),
           jax.lax.ragged_dot(rows, stack, sizes), dtype)
    got = jax.grad(loss(gmm.grouped_dot), (0, 1))(rows, stack)
    want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(rows, stack)
    _close(got[0], want[0], dtype)
    _close(got[1], want[1], dtype, of_largest=True)
    empty = np.asarray(sizes) == 0
    assert not np.any(np.asarray(got[1], np.float32)[empty])


def test_transposed_product_reads_the_stack_as_it_is_stored():
    """The rows' gradient contracts the stack's last axis inside the kernel:
    its jaxpr holds no transpose of the stack."""
    sizes = jnp.array(RUNS["uneven"], jnp.int32) * TILE
    rows = _normal(0, (40 * TILE, K), jnp.bfloat16)
    stack = _normal(1, (4, K, N), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda r: jnp.sum(gmm.grouped_dot(
        r, stack, sizes).astype(jnp.float32))))(rows)
    outside = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "transpose" not in outside, outside


#: the stack's gradient in 2 and 3 blocks of ``N``: ``K`` 256, so that a
#: ``VMEM_BYTES`` lowered to what `_gmm_rows` needs leaves the whole ``[K, N]``
#: sum outside (at ``K`` 128 the two kernels' needs are equal to the byte)
K_WIDE = 256


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("blocks", [2, 3])
@pytest.mark.parametrize("runs", sorted(RUNS))
def test_blocked_weights_kernel_matches_ragged_dot(runs, blocks, dtype,
                                                   monkeypatch):
    """`_gmm_weights` summing a block ``[K, N / blocks]`` at a time, reached
    as a cell reaches it: the module's VMEM is too small for the whole sum
    and `weight_blocks` answers the least count that fits.  The result and
    both gradients against `ragged_dot`, and the call's grid and blocks."""
    n, itemsize = 128 * blocks, jnp.dtype(dtype).itemsize
    monkeypatch.setattr(gmm, "VMEM_BYTES",
                        gmm.rows_vmem_bytes(K_WIDE, n, itemsize))
    assert gmm.weight_blocks(K_WIDE, n, itemsize) == blocks
    assert gmm.weights_vmem_bytes(K_WIDE, n, itemsize, 1) > gmm.VMEM_BYTES
    sizes = jnp.array(RUNS[runs], jnp.int32) * TILE
    rows = _normal(0, (40 * TILE, K_WIDE), dtype)
    stack = _normal(1, (4, K_WIDE, n), dtype) * 0.1
    assert gmm.takes_kernels(rows, stack)

    def loss(dot):
        return lambda r, s: jnp.sum(jnp.sin(dot(r, s, sizes).astype(
            jnp.float32)))

    grad = jax.grad(loss(gmm.grouped_dot), (0, 1))
    calls = _pallas_calls(jax.make_jaxpr(grad)(rows, stack).jaxpr)
    assert [c[0] for c in calls] == ["_gmm_rows", "_gmm_weights",
                                     "_gmm_rows"], calls
    assert calls[1][1:] == ((blocks, 40), [(TILE, K_WIDE), (TILE, 128),
                                           (1, K_WIDE, 128)])
    got = grad(rows, stack)
    want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(rows, stack)
    _close(gmm.grouped_dot(rows, stack, sizes),
           jax.lax.ragged_dot(rows, stack, sizes), dtype)
    _close(got[0], want[0], dtype)
    _close(got[1], want[1], dtype, of_largest=True)
    empty = np.asarray(sizes) == 0
    assert not np.any(np.asarray(got[1], np.float32)[empty])


def _pallas_calls(jaxpr, inside=None):
    """(jitted function, grid, block shapes) of every `pallas_call` under
    ``jaxpr``, in program order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            out.append((inside, tuple(mapping.grid),
                        [tuple(getattr(d, "block_size", d)
                               for d in b.block_shape)
                         for b in mapping.block_mappings]))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", None)
            if inner is not None:
                out += _pallas_calls(getattr(inner, "jaxpr", inner),
                                     eqn.params.get("name", inside))
    return out


# -- what chooses -------------------------------------------------------------

def test_the_shape_alone_chooses_the_kernels():
    bf16 = 2
    # the three cells: 16 of 64 experts 896 wide on all 131,072 pairs; 8 of
    # 256 experts 1,024 wide on 16,384 rows; 8 of 320 experts 1,280 wide on
    # a stream of 4,096, 6,556 pairs (820 a group, 8,704 rows on tiles)
    assert gmm.row_tile(131072, 16, 2304, 896, bf16) == TILE
    assert gmm.row_tile(16384, 8, 2304, 1024, bf16) == TILE
    assert gmm.row_tile(6556, 8, 4096, 1280, bf16) == TILE
    assert gmm.aligned_rows(131072, 16, TILE) == 131072 + 16 * TILE
    assert gmm.aligned_rows(6556, 8, TILE) == 8704
    # toy widths, a decoding step's few rows, matrices beyond VMEM
    assert gmm.row_tile(768, 8, 72, 16, 4) == 1
    assert gmm.row_tile(131072, 16, 2304, 900, bf16) == 1
    assert gmm.row_tile(64, 16, 2304, 896, bf16) == 1
    # the rule counts a group's pairs: on tiles, `pairs` rows are one tile a
    # group and the tiles that hold `pairs - groups` rows
    least = 16 * gmm.PAIRS_A_GROUP - TILE + 16
    assert gmm.row_tile(least, 16, 2304, 896, bf16) == 1
    assert gmm.row_tile(least + 1, 16, 2304, 896, bf16) == TILE
    assert gmm.row_tile(131072, 16, 8192, 8192, bf16) == 1
    why = gmm.refusals(gmm.aligned_rows(131072, 16, TILE), 16, 8192, 8192,
                       bf16)
    assert len(why) == 1 and "_gmm_rows' blocks" in why[0]
    # one lane tile a block, `_gmm_weights` needs no more than `_gmm_rows`:
    # no width that kernel takes is without a block count
    for k, n in ((128, 128), (4096, 1280), (1280, 4096), (8192, 128),
                 (128, 8192), (8192, 8192)):
        assert gmm.weights_vmem_bytes(k, n, bf16, n // 128) <= \
            gmm.rows_vmem_bytes(k, n, bf16)

    def path(m, groups, k, n):
        sizes = jnp.zeros((groups,), jnp.int32).at[0].set(m)
        return str(jax.make_jaxpr(gmm.grouped_dot)(
            jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16), sizes))

    for m, groups, k, n in ((131072 + 16 * TILE, 16, 2304, 896),
                            (131072 + 16 * TILE, 16, 896, 2304),
                            (16384 + 8 * TILE, 8, 2304, 1024),
                            (8704, 8, 4096, 1280), (8704, 8, 1280, 4096)):
        assert "pallas_call" in path(m, groups, k, n)
        assert "ragged_dot" not in path(m, groups, k, n)
    for m, groups, k, n in ((768, 8, 72, 16), (16, 16, 2304, 896),
                            (gmm.aligned_rows(64, 16, TILE), 16, 2304, 896),
                            (16384 + 8 * TILE, 8, 8192, 8192)):
        assert "pallas_call" not in path(m, groups, k, n)
        assert "ragged_dot" in path(m, groups, k, n)


#: cell -> (pairs of a chunk, held experts, stream, an expert's width, blocks
#: of the stacks' gradients `[stream, width]` and `[width, stream]`)
CELLS = {"mellum2_12b": (131072, 16, 2304, 896, 1, 1),
         "kimi_linear_48b": (16384, 8, 2304, 1024, 1, 1),
         "solar_open2_250b": (6556, 8, 4096, 1280, 2, 2)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_grids_and_blocks(cell):
    """What the shape chose for each cell's products: the Mellum and Kimi
    shapes sum a stack's gradient whole (one block: PR 32's row tiles and
    blocks, under a leading grid axis of one), the Solar shape in two
    halves of ``N``; `_gmm_rows` is one cell a row tile everywhere."""
    pairs, groups, k, n, blocks, blocks_back = CELLS[cell]
    assert gmm.weight_blocks(k, n, 2) == blocks
    assert gmm.weight_blocks(n, k, 2) == blocks_back
    assert gmm.rows_vmem_bytes(k, n, 2) <= gmm.VMEM_BYTES
    assert gmm.weights_vmem_bytes(k, n, 2, blocks) <= gmm.VMEM_BYTES
    if blocks > 1:
        assert gmm.weights_vmem_bytes(k, n, 2, blocks - 1) > gmm.VMEM_BYTES
    m = gmm.aligned_rows(pairs, groups, TILE)
    tiles = m // TILE
    sizes = jnp.zeros((groups,), jnp.int32).at[0].set(m)
    for k, n, blocks in ((k, n, blocks), (n, k, blocks_back)):
        grad = jax.grad(lambda r, s: jnp.sum(gmm.grouped_dot(
            r, s, sizes).astype(jnp.float32)), (0, 1))
        calls = _pallas_calls(jax.make_jaxpr(grad)(
            jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
            jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16)).jaxpr)
        assert calls == [
            ("_gmm_rows", (tiles,), [(TILE, k), (1, k, n), (TILE, n)]),
            ("_gmm_weights", (blocks, tiles),
             [(TILE, k), (TILE, n // blocks), (1, k, n // blocks)]),
            ("_gmm_rows", (tiles,), [(TILE, n), (1, k, n), (TILE, k)])], calls


def test_vmem_is_counted_a_kernel():
    """The Solar shape: `_gmm_rows` fits as it is, the whole float32
    ``[K, N]`` sum of `_gmm_weights` does not, its halves do."""
    assert gmm.rows_vmem_bytes(4096, 1280, 2) == 34865152
    assert gmm.weights_vmem_bytes(4096, 1280, 2, 1) == 72613888
    assert gmm.weights_vmem_bytes(4096, 1280, 2, 2) == 40501248
    assert gmm.weights_vmem_bytes(2304, 896, 2, 1) == int(30.75 * 2 ** 20)
    assert gmm.weights_vmem_bytes(2304, 1024, 2, 1) == int(34.25 * 2 ** 20)
    assert gmm.weight_blocks(4096, 1280, 2) == 2


@pytest.mark.parametrize("groups", [1, 2, 8, 16, 64])
def test_the_layout_and_the_product_agree_on_who_multiplies(groups):
    """`row_tile` lays the runs out for the kernels exactly where
    `grouped_dot` then takes them: never whole tiles for `ragged_dot`'s sake,
    never runs the kernels cannot take."""
    taken = set()
    for pairs in list(range(1, 70 * TILE, 97)) + [
            p * TILE + d for p in (8 * groups, 9 * groups) for d in (-1, 0, 1)
            if p * TILE + d > 0] + [
            groups * gmm.PAIRS_A_GROUP + d for d in range(-TILE - 1, TILE + 2)
            if groups * gmm.PAIRS_A_GROUP + d > 0]:
        tile = gmm.row_tile(pairs, groups, K, N, 2)
        taken.add(tile)
        rows = jax.ShapeDtypeStruct(
            (gmm.aligned_rows(pairs, groups, tile), K), jnp.bfloat16)
        stack = jax.ShapeDtypeStruct((groups, K, N), jnp.bfloat16)
        assert gmm.takes_kernels(rows, stack) == (tile > 1), (pairs, tile)
        assert gmm.takes_kernels(
            jax.ShapeDtypeStruct(rows.shape[:1] + (N,), jnp.bfloat16),
            jax.ShapeDtypeStruct((groups, N, K), jnp.bfloat16)) == (tile > 1)
    assert taken == {1, TILE}


# -- through the experts' loop ------------------------------------------------

def _expert(dot):
    def ffn(rows, sizes, up, gate, down):
        hidden = jax.nn.silu(dot(rows, up, sizes)) * dot(rows, gate, sizes)
        return dot(hidden, down, sizes)
    return ffn


#: tokens, top k, experts in all, held (from expert 1 on), chunk, bias on the
#: router's scores that sends every token to one expert
LOOPS = {
    "balanced": (1024, 4, 5, 2, 4096, None),
    "one_expert_takes_every_token": (1024, 4, 5, 2, 4096, 2),
    "an_expert_without_a_pair": (1000, 2, 8, 2, 4096, 1),
    "pairs_short_of_the_chunk": (256, 4, 5, 2, 4096, None),
    "two_trips": (4000, 2, 3, 2, 4096, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(LOOPS))
def test_grouped_ffn_with_the_kernels_matches_ragged_dot(case, dtype):
    """`grouped_ffn` over runs laid out on whole tiles and multiplied by the
    kernels against the same over plain runs and `ragged_dot`: the result
    and all three gradients.  The runs end inside a tile in every case (the
    counts are what the scores give)."""
    tokens, topk, experts, held, chunk, favoured = LOOPS[case]
    x = _normal(2, (tokens, K), dtype)
    scores = _normal(3, (tokens, experts), jnp.float32)
    if favoured is not None:
        scores = scores.at[:, favoured].add(10.0)
    if case == "an_expert_without_a_pair":
        scores = scores.at[:, 2].add(-10.0)
    combine, picked = jax.lax.top_k(jax.nn.softmax(scores), topk)
    stacks = tuple(_normal(4 + i, shape, dtype) * 0.1 for i, shape in
                   enumerate(((held, K, N), (held, K, N), (held, N, K))))
    routing = gf.route(picked, 1, held)
    counts = np.asarray(routing.counts)
    assert np.any(counts % TILE), counts
    trips = -(-int(counts.sum()) // chunk)
    assert trips == (2 if case == "two_trips" else 1), counts
    if case == "an_expert_without_a_pair":
        assert counts[1] == 0
    if favoured is not None:
        assert counts[favoured - 1] == tokens
    tile = gmm.row_tile(chunk, held, K, N, jnp.dtype(dtype).itemsize)
    assert tile == TILE

    def run(ffn, tile):
        def loss(x, stacks, combine):
            y = gf.grouped_ffn(ffn, chunk, tile, x, stacks, combine, routing)
            return jnp.sum(jnp.sin(y.astype(jnp.float32))), y
        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)

    kernels, plain = run(_expert(gmm.grouped_dot), tile), run(
        _expert(jax.lax.ragged_dot), 1)
    assert "pallas_call" in str(jax.make_jaxpr(kernels)(x, stacks, combine))
    assert "pallas_call" not in str(jax.make_jaxpr(plain)(x, stacks, combine))
    (_, got_y), got = jax.jit(kernels)(x, stacks, combine)
    (_, want_y), want = jax.jit(plain)(x, stacks, combine)
    _close(got_y, want_y, dtype)
    _close(got[0], want[0], dtype)
    for g, w in zip(got[1], want[1]):
        _close(g, w, dtype, of_largest=True)
    _close(got[2], want[2], dtype, of_largest=True)
    assert int(gf.rows_multiplied(routing, chunk, tile)) == trips * (
        chunk + held * TILE)
    assert int(gf.rows_multiplied(routing, chunk, 1)) == trips * chunk


@pytest.mark.parametrize("tile", [1, 8, TILE])
def test_every_pair_has_one_row_and_every_run_whole_tiles(tile):
    """The layout itself, at any tile: each held pair of the chunk appears
    once, among its expert's run, which starts on a multiple of the tile;
    every other row has weight zero and points at the spare row."""
    tokens, topk, held, chunk = 300, 2, 3, 256
    picked = jax.random.randint(jax.random.key(7), (tokens, topk), 0, 5)
    combine = jnp.ones((tokens, topk), jnp.float32)
    routing = gf.route(picked, 1, held)
    total, seen = int(jnp.sum(routing.counts)), []
    for first in range(0, total, chunk):
        token, w, runs, pair, real = map(np.asarray, gf._chunk_rows(
            routing, combine, first, chunk, tile))
        assert len(token) == gmm.aligned_rows(chunk, held, tile) == runs.sum()
        assert not np.any(runs % tile)
        assert np.all(token[~real] == tokens) and not np.any(w[~real])
        group = np.repeat(np.arange(held), runs)
        local = np.asarray(picked).reshape(-1)[pair[real]] - 1
        np.testing.assert_array_equal(local, group[real])
        np.testing.assert_array_equal(token[real], pair[real] // topk)
        seen += list(pair[real])
    assert sorted(seen) == sorted(np.flatnonzero(
        (np.asarray(picked).reshape(-1) >= 1)
        & (np.asarray(picked).reshape(-1) <= held)))
