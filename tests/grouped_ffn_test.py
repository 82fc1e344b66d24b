"""How a chunk's rows come back to their tokens in `ops/grouped_ffn.py`: by
gathers through the sort's inverse, against the rows' own layout, a dense sum
over the experts, and the traced programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.ops import grouped_ffn as gf
from homebrewnlp_tpu.ops.pallas_gmm import aligned_rows

D, INTER = 32, 48
#: tokens, top k, experts in all, held (from expert 1 on), chunk, bias on the
#: router's scores that sends every token to one expert; the counts are what
#: the scores give, so runs end inside a tile and pairs fall on experts not
#: held in every case
ROUTINGS = {
    "balanced": (512, 4, 5, 2, 2048, None),
    "one_expert_takes_every_token": (512, 4, 5, 2, 2048, 2),
    "an_expert_without_a_pair": (500, 2, 8, 3, 2048, 1),
    "pairs_short_of_the_chunk": (96, 4, 5, 2, 2048, None),
    "two_trips": (1500, 2, 3, 2, 1536, None),
}


def _routing(case):
    tokens, topk, experts, held, chunk, favoured = ROUTINGS[case]
    scores = jax.random.normal(jax.random.key(3), (tokens, experts))
    if favoured is not None:
        scores = scores.at[:, favoured].add(10.0)
    if case == "an_expert_without_a_pair":
        scores = scores.at[:, 2].add(-10.0)
    combine, picked = jax.lax.top_k(jax.nn.softmax(scores), topk)
    routing = gf.route(picked, 1, held)
    counts = np.asarray(routing.counts)
    trips = -(-int(counts.sum()) // chunk)
    assert trips == (2 if case == "two_trips" else 1), counts
    assert 0 < counts.sum() < tokens * topk, counts
    if case == "an_expert_without_a_pair":
        assert counts[1] == 0
    if favoured is not None:
        assert counts[favoured - 1] == tokens
    return picked, combine, routing, held, chunk, trips


@pytest.mark.parametrize("tile", [1, 8, 256])
@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_the_inverse_map_points_each_pair_at_its_row(case, tile):
    """In every chunk the row that holds pair `p` is `row_of_pair[p]`, every
    other pair points past the last row, and over the trips every pair on a
    held expert is reached exactly once."""
    picked, combine, routing, held, chunk, trips = _routing(case)
    tokens, topk = picked.shape
    np.testing.assert_array_equal(
        np.asarray(routing.pair)[np.asarray(routing.rank)],
        np.arange(tokens * topk))
    spare = aligned_rows(chunk, held, tile)
    reached = np.zeros(tokens * topk, int)
    for trip in range(trips):
        _, _, _, pair, real = map(np.asarray, gf._chunk_rows(
            routing, combine, trip * chunk, chunk, tile))
        row_of_pair = np.asarray(gf._rows_of_pairs(
            routing, trip * chunk, chunk, tile, topk))
        assert row_of_pair.shape == (tokens, topk)
        want = np.full(tokens * topk, spare)
        want[pair[real]] = np.flatnonzero(real)
        np.testing.assert_array_equal(row_of_pair.reshape(-1), want)
        reached += want < spare
    local = np.asarray(picked).reshape(-1) - 1
    np.testing.assert_array_equal(reached, (local >= 0) & (local < held))


def _ffn(rows, sizes, up, gate, down):
    dot = jax.lax.ragged_dot
    return dot(jax.nn.silu(dot(rows, up, sizes)) * dot(rows, gate, sizes),
               down, sizes)


def _operands(case):
    picked, combine, routing, held, chunk, _ = _routing(case)
    x = jax.random.normal(jax.random.key(2), (picked.shape[0], D))
    stacks = tuple(jax.random.normal(jax.random.key(4 + i), shape) * 0.2
                   for i, shape in enumerate(((held, D, INTER),
                                              (held, D, INTER),
                                              (held, INTER, D))))
    return x, stacks, combine, picked, routing, held, chunk


def _value_and_grad(routing, chunk, tile):
    def loss(x, stacks, combine):
        y = gf.grouped_ffn(_ffn, chunk, tile, x, stacks, combine, routing)
        return jnp.sum(jnp.sin(y)), y
    return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)


@pytest.mark.parametrize("tile", [1, 8])
@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_grouped_ffn_matches_a_dense_sum_over_the_experts(case, tile):
    """The result and the three gradients against `sum_e mask_e * ffn_e(x)`
    in float32, every expert applied to every token."""
    x, stacks, combine, picked, routing, held, chunk = _operands(case)

    def dense(x, stacks, combine):
        up, gate, down = stacks
        y = 0.0
        for e in range(held):
            mask = jnp.sum(jnp.where(picked == e + 1, combine, 0), -1)
            y += mask[:, None] * ((jax.nn.silu(x @ up[e]) * (x @ gate[e]))
                                  @ down[e])
        return jnp.sum(jnp.sin(y)), y

    (_, got_y), got = jax.jit(_value_and_grad(routing, chunk, tile))(
        x, stacks, combine)
    (_, want_y), want = jax.jit(jax.value_and_grad(
        dense, (0, 1, 2), has_aux=True))(x, stacks, combine)
    for g, w in zip(jax.tree.leaves((got_y, got)),
                    jax.tree.leaves((want_y, want))):
        # a sum over many rows is as exact as its largest element's rounding
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * scale)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_no_scatter_add_of_rows_is_left(pass_):
    """Neither loop adds rows of `D` into an array of a row a token (and a
    spare one): the scatter-adds left are of scalars (the last run's
    zero rows counted in, a pair's weight gradient written once)."""
    x, stacks, combine, _, routing, held, chunk = _operands("two_trips")
    run = _value_and_grad(routing, chunk, 8)
    if pass_ == "forward":
        run = lambda *a: gf.grouped_ffn(_ffn, chunk, 8, *a, routing)  # noqa
    eqns = list(_equations(jax.make_jaxpr(run)(x, stacks, combine).jaxpr))
    assert any(e.primitive.name == "while" for e in eqns)
    assert sum(e.primitive.name == "gather" for e in eqns) >= 8
    into = [e.invars[0].aval.shape for e in eqns
            if e.primitive.name == "scatter-add"]
    assert into and not [s for s in into if len(s) > 1], into


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_two_calls_on_the_same_inputs_are_bitwise_equal(dtype):
    """A token's addends are summed in slot order, so the result and the
    gradients are functions of the inputs alone."""
    x, stacks, combine, _, routing, held, chunk = _operands("two_trips")
    x, stacks = jax.tree.map(lambda a: a.astype(dtype), (x, stacks))
    run = jax.jit(_value_and_grad(routing, chunk, 8))
    first, second = run(x, stacks, combine), run(x, stacks, combine)
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(second)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
