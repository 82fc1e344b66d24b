"""Solar-Open2's block parts (models/hybrid.py: `kda` with `beta` up to 2,
the gated `gqa` without positions, the routed experts at a share of a
fortieth, the heads at a share of a quarter) against the plain reference
(benchmark/reference/solar_open2.py), at toy sizes on the CPU in float32.

The tolerances, and why.  Both sides compute in float32 from the same seeded
weights, so what is left is the order of sums: the chunked delta rule solves
a triangular system where the reference walks token by token, attention
takes other blocks, the experts see only the tokens routed to them.  That
leaves 1e-5 of scale on a layer's output (`LAYER`).  Through three updates
the optimizer keeps momentum and SM3 rows in bfloat16 on both sides: a
reading rounds to 2**-9 there, so `sm3_leaf` and `change_leaf` get 4e-3 and
the losses, which see the weights only through the learning rate, 1e-5.

`beta` up to 2 is more than a factor: the chunk's `(I + A)^-1` by repeated
squaring carries powers of `A` far larger than the inverse once keys lie
close together, and float32 loses the result in them (section (c): 1e8 off
at `beta` 1.9 and cosines of 0.9; in this model's own layers 2 and 3, 1e-3
of scale at the cell's widths and all of it at the toy's).  Under
`kda_allow_neg_eigval` the squaring stays inside blocks of 8 tokens and
blocks are merged (ops/delta_rule.py), which is what these tests hold.
"""
import contextlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.config import Config
from homebrewnlp_tpu.models.ctx import Args, Ctx
from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
from homebrewnlp_tpu.nd import NT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
LAYER = dict(rtol=1e-5, atol=1e-5)
GQA = "gqa-nope-gated"
MOE = "routed_moe-sigmoid-bias-topk8-gated-shared1-in:silu"
NAMES = ("batch", "sequence", "heads", "features_per_head")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "solar_open2.py"), "solar_ref")
compare = _load(os.path.join(BENCH, "compare.py"), "compare")
flops = _load(os.path.join(BENCH, "flops_solar_open2.py"), "flops_solar_open2")
SOUND = {k: jnp.float32(v) for k, v in ref.SOUND.items()}


def toy(**over):
    """The benchmark's configuration at a toy width: one whole period (the
    gated attention, then three delta-rule layers), the same block parts and
    schedule, 8 query heads over 2 K/V heads, 8 of 32 experts held."""
    raw = dict(
        model_mode="gpt", sequence_length=48, heads=4, features_per_head=6,
        vocab_size=128, depth=4, train_batch_size=2, calc_accuracy=False,
        memory_reduction_strategy="checkpoint", weight_decay=0.0001,
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
        learning_rate=0.01, z_loss=1e-4, embedding_stddev=0.02,
        intermediate_feed_forward_multiplier=2.5, factorized_embedding=False,
        scale_by_depth=False, weight_centralisation=False,
        weight_standardisation=False, experts=32, experts_held=8,
        expert_offset=0, moe_intermediate_size=16, moe_balance_weight=1.0,
        routed_scaling_factor=1, rms_norm_eps=1e-5,
        linear_attn_config={"num_heads": 4, "head_dim": 8,
                            "short_conv_kernel_size": 4, "num_kv_heads": None},
        num_attention_heads=8, num_key_value_heads=2, head_dim=8,
        rope_theta=100.0, use_rope=False, use_gqa_gate=True,
        kda_allow_neg_eigval=True, kda_use_full_proj=False,
        tpu_size=1, calculation_dtype="float32", slice_dtype="float32",
        storage_dtype="float32", optimizer_slice_dtype="bfloat16",
        block_config=[
            {"layer": ["rms_norm-scale", GQA], "skip": True},
            {"layer": ["rms_norm-scale", "kda"], "skip": True},
            {"layer": ["rms_norm-scale", MOE], "skip": True}],
        block_schedule=[[0, 2], [1, 2], [1, 2], [1, 2]],
        output_block_config=[{"layer": ["rms_norm-scale"]}],
        learning_rate_config={"linear_warmup": {"final_step": 64}})
    raw.update(over)
    return raw


def run_layer(cfg, spec, params, x):
    """One layer of the DSL on `x`, its parameters keyed as under its own
    scope (`gqa_/proj/q_proj` ...).  Returns (output array, ctx)."""
    name, *extras = spec.split("-")
    ctx = Ctx(cfg, params=params, train=True)
    out = ctx.scoped(name + "_", LAYER_FUNCTIONS[name],
                     Args(ctx, NT(x, NAMES), extras))
    return out.transpose_to(NAMES).x, ctx


def part_params(sz, kind, seed, prefix):
    """Seeded weights of one block part, by the reference's rules, keyed as
    `run_layer` wants them."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(mean, std, shape), jnp.float32)
            for k, (shape, (mean, std)) in ref._part_leaves(sz, kind).items()
            if k.startswith(prefix)}


def stream(seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(2, 48, 4, 6)),
                       jnp.float32)


def assert_gradients_match(mine, theirs, params, x):
    got, want = (jax.grad(lambda p, x, f=f: jnp.sum(jnp.sin(f(p, x))),
                          (0, 1))(params, x) for f in (mine, theirs))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for k in want[0]:
        np.testing.assert_allclose(got[0][k], want[0][k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


# -- (a) the whole update -----------------------------------------------------

@pytest.fixture(scope="module")
def followed():
    """Three updates of the toy model through `Trainer.step`, driven and read
    as the benchmark's runner does, and the reference's three."""
    sys.path[:0] = [BENCH]
    runner = _load(os.path.join(BENCH, "runners", "train_step.py"),
                   "train_step")
    with open(os.path.join(BENCH, "traffic", "train.json")) as f:
        traffic = json.load(f)

    class Spans:
        @contextlib.contextmanager
        def span(self, name):
            yield

    # the balance term is taken over a whole batch: both rows in one block
    config = dict(toy(), benchmark={"followed_steps": 3, "reference_rows": 2})
    program = runner.Program(config, traffic, 2 ** 31 + 131, ref, Spans(),
                             lambda m: None)
    got = program.follow_and_warm_up()
    want = ref.follow(program.sizes, program.seed, program.host_batches, 3, 2)
    return got, want, program


def test_three_updates_match_the_reference(followed):
    got, want, _ = followed
    read = compare.readings(got, want)
    assert max(read["loss1"], read["loss2"], read["loss3"]) < 1e-5, read
    assert read["grad_norm1"] < 4e-3 and read["sm3_leaf"] < 4e-3, read
    assert read["change_leaf"] < 4e-3 and read["change_median"] < 1e-3, read
    # every leaf moved, the selection bias alone took no gradient
    still = [n for n, g in zip(want["names"], want["grad_leaf"]) if g == 0]
    assert still and all(n.endswith("router_bias") for n in still)


def test_the_runner_sees_the_parameters_the_reference_names(followed):
    _, want, program = followed
    assert sorted(ref.shapes(program.sizes)) == want["names"]
    # table, head, final norm; one gqa part of 6, three kda parts of 16,
    # four expert parts of 9
    assert len(want["names"]) == 3 + 6 + 3 * 16 + 4 * 9
    assert sum("gqa_/gate/gate_proj" in n for n in want["names"]) == 1


def test_step_reports_the_load_and_the_rows_of_the_held_experts(followed):
    """`expert_pairs_layer_max` over the chunk is the trips of the fullest
    layer's loop; `expert_rows_filled` its pairs over the rows multiplied.
    At the toy width no run is laid out on tiles, so those are the trips
    times the chunk (all 768 pairs here: one trip)."""
    program = followed[2]
    program.state, metrics = program.trainer.step(
        program.state, program.ring[0], jax.random.key(0))
    pairs = float(metrics["expert_pairs_held"])
    # 96 tokens x top-8 over 32 experts, 8 held, four expert layers
    assert 0.5 * 768 < pairs < 1.5 * 768
    assert float(metrics["expert_load_mean"]) == pytest.approx(pairs / 32)
    fullest = float(metrics["expert_pairs_layer_max"])
    assert 0 < fullest <= 768
    assert float(metrics["expert_rows_filled"]) == pytest.approx(
        fullest / 768)


@pytest.mark.parametrize("case", sorted(ref.LOWER))
def test_every_planted_fault_moves_the_reference(followed, case):
    """Each case of `LOWER` is a different model at the toy size too: it
    reads apart from the sound reference by more than the program does."""
    _, want, program = followed
    got = ref.follow(program.sizes, program.seed, program.host_batches, 3, 2,
                     lower=case)
    read = compare.readings(got, want)
    assert max(read["loss3"], read["sm3_leaf"], read["change_leaf"]) > 4e-3, (
        case, read)


# -- (b) the gated attention without positions, alone -------------------------

def test_gated_nope_layer_matches_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "gqa", 1, "gqa_/")
    u = stream(2)
    got, _ = run_layer(cfg, GQA, params, u)
    want = ref._gqa(params, u, sz, SOUND, rows=16)
    np.testing.assert_allclose(got, want, **LAYER)
    # every variant the faults plant is another layer
    for fault in ("gate", "gate_first", "rotate", "grouped"):
        other = ref._gqa(params, u, sz, dict(SOUND, **{
            fault: jnp.float32(1.0 - ref.SOUND[fault])}), rows=16)
        assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


def test_gated_nope_layer_gradients_match_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    assert_gradients_match(
        lambda p, x: run_layer(cfg, GQA, p, x)[0],
        lambda p, x: ref._gqa(p, x, sz, SOUND, rows=16),
        part_params(sz, "gqa", 3, "gqa_/"), stream(4))


def test_no_position_means_no_order_among_the_keys_seen():
    """Without positions a row's result depends on which keys it sees, not
    on where they stand: the earlier tokens reordered leave the last row as
    it was.  (A rotated layer fails this.)"""
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "gqa", 5, "gqa_/")
    u = stream(6)
    order = np.r_[np.random.default_rng(7).permutation(47), 47]
    got = run_layer(cfg, GQA, params, u)[0][:, -1]
    np.testing.assert_allclose(run_layer(cfg, GQA, params, u[:, order])[0][
        :, -1], got, rtol=1e-4, atol=1e-5)
    rotated = lambda x: ref._gqa(params, x, sz, dict(
        SOUND, rotate=jnp.float32(1.0)), rows=16)[:, -1]
    assert np.abs(np.asarray(rotated(u[:, order]) - rotated(u))).max() > 1e-3


@pytest.mark.parametrize("spec,keys,why", [
    ("gqa-full_attention", {}, "without positions is spelt gqa-nope"),
    ("gqa-nope", {}, "gated"),
    (GQA, {"use_rope": True}, "gqa-nope"),
    (GQA, {"use_gqa_gate": False}, "gated"),
    ("gqa-full_attention-gated", {"use_rope": True}, "rope_parameters"),
])
def test_the_part_and_the_keys_must_say_the_same_layer(spec, keys, why):
    """The spelling a program before this layer would refuse (one extra that
    names no rotary table, or two extras) is the only one taken, and only
    beside the keys that say the same."""
    cfg = Config(toy(**keys))
    sz = ref.Sizes.from_config(toy())
    with pytest.raises(ValueError, match=why):
        run_layer(cfg, spec, part_params(sz, "gqa", 1, "gqa_/"), stream(2))


# -- (c) the delta rule with beta up to 2 -------------------------------------

def _kda_inputs(t, decay, seed=0, b=2, n=3, d=8):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, t, n, d))) * d ** -0.5
    k = unit(rng.normal(size=(b, t, n, d)))
    v = rng.normal(size=(b, t, n, d))
    g = -decay * rng.uniform(0.2, 1.0, size=(b, t, n, d))
    beta = rng.uniform(0.1, 1.95, size=(b, t, n))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _assert_delta_rule_matches(args):
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    f = lambda *a: chunked_kda(*a, wide_beta=True)
    recurrence = lambda *a: ref._delta_rule(*a, inner=1)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = f(*args)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, recurrence(*args), **LAYER)
    for mine, theirs in zip(jax.grad(loss(f), range(5))(*args),
                            jax.grad(loss(recurrence), range(5))(*args)):
        assert np.all(np.isfinite(mine))
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t,decay", [(64, 0.1), (128, 5.0), (50, 1.0)],
                         ids=["mild", "decay5", "ragged"])
def test_chunked_delta_rule_with_beta_to_2_matches_the_recurrence(t, decay):
    """The scan every toy shape takes, `beta` drawn up to 1.95: `I - beta k
    k^T` then turns `k`'s direction over, and the chunk's triangular system
    holds entries up to twice as large."""
    args = _kda_inputs(t, decay)
    assert float(args[4].max()) > 1.8
    _assert_delta_rule_matches(args)


@pytest.mark.parametrize("t,decay", [(64, 0.1), (128, 5.0)],
                         ids=["mild", "decay5_whole_span"])
def test_delta_rule_kernels_with_beta_to_2_match_the_recurrence(t, decay):
    """ops/pallas_kda.py's kernels (interpreted here) at a head width of one
    lane tile, the cell's: outputs and all five gradients, `beta`'s among
    them, against the token-by-token recurrence."""
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    args = _kda_inputs(t, decay, b=1, n=2, d=128)
    text = str(jax.make_jaxpr(lambda *a: chunked_kda(*a, wide_beta=True))(
        *args))
    assert text.count("pallas_call") == 1
    _assert_delta_rule_matches(args)


@pytest.mark.parametrize("d", [8, 128], ids=["scan", "kernels"])
def test_keys_close_together_under_beta_near_2_keep_the_inverse(d):
    """What leaned on `beta <= 1`: with keys at cosines of 0.9, hardly any
    decay and `beta` in 1.6 .. 1.95, `A` is near 1.7 below its diagonal and
    `A^16` near 1e12, where `(I + A)^-1` has entries near 2.  Squared inside
    blocks of 8 tokens and merged, the chunks stand with the recurrence;
    squared over the whole chunk (`wide_beta` false, the form `beta <= 1`
    keeps) they do not."""
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, 64, 2, d)
    k = unit(rng.normal(size=(1, 1, 2, d)) + 0.3 * rng.normal(size=shape))
    q = unit(rng.normal(size=shape)) * d ** -0.5
    v = rng.normal(size=shape)
    g = -0.02 * rng.uniform(0.2, 1.0, size=shape)
    beta = rng.uniform(1.6, 1.95, size=shape[:3])
    args = tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))
    want = ref._delta_rule(*args, inner=1)
    got = chunked_kda(*args, wide_beta=True)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    lost = chunked_kda(*args)
    assert not np.abs(np.asarray(lost - want)).max() < 1.0
    dk = lambda f: jax.grad(lambda k: jnp.sum(jnp.sin(f(
        args[0], k, *args[2:]))))(args[1])
    np.testing.assert_allclose(
        dk(lambda *a: chunked_kda(*a, wide_beta=True)),
        dk(lambda *a: ref._delta_rule(*a, inner=1)), rtol=1e-2, atol=1e-3)


def test_beta_up_to_1_keeps_the_squaring_over_the_whole_chunk():
    """`wide_beta` false traces what it did before the flag: no mask, no
    merge (the Kimi cell's jaxpr keeps its sha256; CHANGES.md, PR 33)."""
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    args = _kda_inputs(64, 1.0)
    narrow, wide = (str(jax.make_jaxpr(
        lambda *a: chunked_kda(*a, wide_beta=w))(*args)) for w in (False,
                                                                   True))
    count = lambda text: text.count("dot_general")
    assert narrow == str(jax.make_jaxpr(chunked_kda)(*args))
    assert count(wide) == count(narrow)             # as many products
    assert wide.count("select_n") > narrow.count("select_n")    # the masks


def test_kda_layer_doubles_beta_under_the_key_alone():
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "kda", 1, "kda_/")
    u = stream(2)
    got, _ = run_layer(Config(toy()), "kda", params, u)
    np.testing.assert_allclose(got, ref._kda(params, u, sz, SOUND), rtol=1e-4,
                               atol=1e-5)
    plain, _ = run_layer(Config(toy(kda_allow_neg_eigval=False)), "kda",
                         params, u)
    np.testing.assert_allclose(plain, ref._kda(params, u, sz, dict(
        SOUND, beta_scale=jnp.float32(1.0))), rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-3


# -- (d) the shares of heads and of experts -----------------------------------

def _head_share(params, rank, of, per_head):
    """The weights a chip holding share `rank` of `of` of a layer's heads
    has: every leaf in `per_head` cut along the axis it names."""
    out = {}
    for k, v in params.items():
        axis = per_head.get(k.rsplit("/", 1)[1])
        if axis is None:
            out[k] = v
        else:
            n = v.shape[axis] // of
            out[k] = jax.lax.slice_in_dim(v, rank * n, (rank + 1) * n,
                                          axis=axis)
    return out


KDA_HEAD_AXIS = {"q_proj": 2, "k_proj": 2, "v_proj": 2, "q_conv": 1,
                 "k_conv": 1, "v_conv": 1, "decay_up": 1, "dt_bias": 0,
                 "a_log": 0, "beta": 2, "out_up": 1, "proj": 0}
GQA_HEAD_AXIS = {"q_proj": 2, "k_proj": 2, "v_proj": 2, "gate_proj": 2,
                 "out_proj": 0}


@pytest.mark.parametrize("kind,spec,axes", [
    ("kda", "kda", KDA_HEAD_AXIS), ("gqa", GQA, GQA_HEAD_AXIS)])
def test_four_shares_of_the_heads_add_up_to_the_uncut_layer(kind, spec, axes):
    """The share test for heads, at the cell's ratio: four chips of a host
    hold 16 of 64 heads each (a query share with its own two K/V heads, of
    8), and heads meet only in the sum the output matrix makes, so the four
    shares' results add up to the uncut layer's.  What every chip holds
    whole (the gate pairs' first halves, the head norm's weight) is in every
    share and is not counted twice, since it enters no sum over heads."""
    raw = toy(linear_attn_config=dict(toy()["linear_attn_config"],
                                      num_heads=64),
              num_attention_heads=64, num_key_value_heads=8)
    whole = ref.Sizes.from_config(raw)
    params = part_params(whole, kind, 9, kind + "_/")
    u = stream(10)
    layer = {"kda": ref._kda, "gqa": ref._gqa}[kind]
    uncut = layer(params, u, whole, SOUND)
    cut = dict(raw, num_attention_heads=16, num_key_value_heads=2,
               linear_attn_config=dict(raw["linear_attn_config"],
                                       num_heads=16))
    total = 0.0
    for rank in range(4):
        share = _head_share(params, rank, 4, axes)
        got, _ = run_layer(Config(cut), spec, share, u)
        np.testing.assert_allclose(
            got, layer(share, u, ref.Sizes.from_config(cut), SOUND),
            rtol=1e-4, atol=1e-5)
        total = total + got
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=3e-5)


def _expert_case(seed=8):
    raw = toy()
    whole = ref.Sizes.from_config(dict(raw, experts_held=32))
    params = part_params(whole, "routed_moe", seed, "routed_moe_/")
    return raw, whole, params, stream(seed + 1)


def _expert_share(params, first, held):
    """The weights a chip holding experts `first .. first + held` has."""
    return {k: v[first:first + held] if k.startswith(
        "routed_moe_/orthogonal_var") else v for k, v in params.items()}


def test_all_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all four shares (8 of 32
    experts each, as 8 of 320 in the cell) and the shared expert, which
    every chip computes alike, counted once, are the reference's uncut
    expert layer."""
    raw, whole, params, u = _expert_case()
    uncut = ref._experts(params, u, whole, SOUND)[0]
    names = [f"routed_moe_/shared/orthogonal_var{i}/orthogonal_var"
             for i in ("", 1, 2)]
    shared = ref._swiglu(u, *(params[n] for n in names))
    total, pairs = shared, 0
    for rank in range(4):
        cfg = Config(dict(raw, expert_offset=8 * rank))
        share = _expert_share(params, 8 * rank, 8)
        got, ctx = run_layer(cfg, MOE, share, u)
        total = total + (got - shared)
        pairs += int(jnp.sum(ctx.expert_load[0]))
        np.testing.assert_allclose(got, ref._experts(
            share, u, whole._replace(held=8, offset=8 * rank), SOUND)[0],
            **LAYER)
    assert pairs == 96 * 8          # every selected pair fell on one share
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=3e-5)


def test_expert_layer_gradients_match_the_reference():
    raw, whole, params, u = _expert_case(12)
    cfg = Config(raw)
    assert_gradients_match(
        lambda p, x: run_layer(cfg, MOE, p, x)[0],
        lambda p, x: ref._experts(p, x, whole._replace(held=8), SOUND)[0],
        {k: v for k, v in _expert_share(params, 0, 8).items()
         if not k.endswith("router_bias")}
        | {"routed_moe_/router_bias": params["routed_moe_/router_bias"]}, u)


def test_the_balance_term_matches_the_reference_and_is_1_at_a_uniform_load():
    raw, whole, params, u = _expert_case(14)
    cfg = Config(dict(raw, moe_balance_weight=2.0))
    _, ctx = run_layer(cfg, MOE, _expert_share(params, 0, 8), u)
    want = ref._experts(_expert_share(params, 0, 8), u,
                        whole._replace(held=8, balance=2.0), SOUND)[1]
    np.testing.assert_allclose(ctx.aux_losses[0], want, rtol=1e-5)
    assert 2.0 * 0.9 < float(want) < 2.0 * 1.5
    # gradients reach the router through the scores alone
    grad = jax.grad(lambda p: ref._experts(
        p, u, whole._replace(held=8, balance=2.0), SOUND)[1])(
            _expert_share(params, 0, 8))
    assert float(jnp.abs(grad["routed_moe_/router"]).max()) > 0
    assert not np.any(grad["routed_moe_/orthogonal_var/orthogonal_var"])


def test_the_chunk_and_the_products_path_at_8_of_320(caplog):
    """8 of 320 under top-8: a balanced router sends 0.2 pairs a token, the
    chunk is four such loads (6,556 pairs of the cell's 8,192 tokens; 13,108
    of 16,384); laid out on row tiles it is 8,704 rows, 820 pairs a group,
    which ops/pallas_gmm.py takes (PR 36): `_gmm_rows` as it is, the float32
    ``[4096, 1280]`` sum of `_gmm_weights` in two halves, and says so once a
    shape.  The Mellum and Kimi shapes keep one block and the same tile."""
    from homebrewnlp_tpu.models.hybrid import expert_chunk
    from homebrewnlp_tpu.ops import pallas_gmm as gmm
    assert expert_chunk(16384, 8, 8, 320) == 13108
    chunk = expert_chunk(8192, 8, 8, 320)
    assert chunk == 6556 == 4 * -(-8192 * 8 * 8 // 320)
    assert gmm.aligned_rows(chunk, 8, gmm.ROW_TILE) == 8704
    assert not gmm.refusals(8704, 8, 4096, 1280, 2)
    assert not gmm.refusals(8704, 8, 1280, 4096, 2)
    assert gmm.rows_vmem_bytes(4096, 1280, 2) == 34865152 < gmm.VMEM_BYTES
    assert gmm.weights_vmem_bytes(4096, 1280, 2, 1) == 72613888 \
        > gmm.VMEM_BYTES
    assert gmm.weight_blocks(4096, 1280, 2) == 2
    assert gmm.weight_blocks(1280, 4096, 2) == 2
    gmm._say_once.cache_clear()
    with caplog.at_level("INFO"):
        assert gmm.row_tile(chunk, 8, 4096, 1280, 2) == gmm.ROW_TILE == 256
        assert gmm.row_tile(chunk, 8, 4096, 1280, 2) == gmm.ROW_TILE
        # a decoding step's few pairs stay with `ragged_dot`
        assert gmm.row_tile(64, 8, 4096, 1280, 2) == 1
    assert caplog.text.count("run as the Mosaic kernels") == 1
    assert "on 8704 rows" in caplog.text and "in 2 and 2 blocks" in caplog.text
    assert caplog.text.count("run as jax.lax.ragged_dot") == 1
    # both cells that had the kernels keep them, one block and the same tile
    for pairs, held, k, n in ((131072, 16, 2304, 896), (16384, 8, 2304, 1024)):
        assert gmm.row_tile(pairs, held, k, n, 2) == 256
        assert not gmm.refusals(gmm.aligned_rows(pairs, held, 256), held, k,
                                n, 2)
        assert gmm.weight_blocks(k, n, 2) == gmm.weight_blocks(n, k, 2) == 1


# -- (e) scopes ---------------------------------------------------------------

def test_step_scope_gives_the_gate_its_layer_in_every_pass():
    from homebrewnlp_tpu.obs import profile as P
    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import random_text_batch
    cfg = Config(toy())
    tr = Trainer(cfg, make_mesh(cfg, jax.devices()[:1]))
    batch = random_text_batch(cfg)
    tr.step_cost_analysis(tr.init(batch), batch)
    names = P.op_map_from_hlo_text(tr._compiled.as_text()).values()
    seen, gate = {}, set()
    for name in names:
        pass_, block, layer = P.step_scope(name)
        if block is not None:
            seen.setdefault(layer, set()).add(pass_)
            assert layer in ("gqa", "kda", "routed_moe", "norm", "skip"), name
        if "/gqa_/gate/" in name:
            assert (block, layer) == ("d0_0", "gqa"), name
            gate.add(pass_)
    for layer in ("gqa", "kda", "routed_moe", "norm"):
        assert {"forward", "remat", "backward"} <= seen[layer], (layer, seen)
    assert gate == {"forward", "remat", "backward"}
    under_gqa = {part for n in names if "/gqa_/" in n
                 for part in n.split("/gqa_/")[1].split("/")[:1]}
    assert {"proj", "attention", "gate", "out"} <= under_gqa
    assert "rotary" not in under_gqa            # no table is built


# -- (f) the two configuration files ------------------------------------------

def _files():
    with open(os.path.join(REPO, "configs", "solar_open2_250b.json")) as f:
        published = json.load(f)
    with open(os.path.join(BENCH, "configs", "solar_open2_250b.json")) as f:
        cut = json.load(f)
    return published, cut, cut.pop("benchmark")


def test_the_cut_differs_from_the_published_file_in_the_reduced_keys_only():
    published, cut, meta = _files()
    changed = sorted(k for k in set(cut) | set(published)
                     if cut.get(k) != published.get(k))
    assert changed == sorted(meta["reduced"]) == sorted((
        "num_hidden_layers", "depth", "block_schedule", "gqa_layers",
        "linear_attn_config", "num_attention_heads", "num_key_value_heads",
        "experts_held", "vocab_size", "tpu_size"))
    assert meta["published"] == {k: published[k] for k in meta["reduced"]}
    # inside the nested group only the number of heads moved
    assert {k for k, v in cut["linear_attn_config"].items()
            if v != published["linear_attn_config"][k]} == {"num_heads"}
    widths = ("_dim", "_rank", "_size", "features", "per_tok", "multiplier")
    assert not [k for k in changed if k not in ("vocab_size", "tpu_size")
                and any(w in k for w in widths)]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "solar_open2_250b")
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == meta["source"]
    assert {"gqa_gate_shape", "qk_norm", "router_scores", "kda_gate_rank",
            "optimizer", "initialisers", "z_loss", "sequence_length",
            "train_batch_size", "l2_norm_eps"} <= set(meta["assumed"])
    assert "experts over all 40" in meta["deployment"]
    assert "heads over a host's four chips" in meta["deployment"]


def test_the_cut_keeps_the_guides_floors_and_every_published_width():
    published, cut, meta = _files()
    assert cut["experts_held"] == 8 and cut["experts"] == 320
    assert cut["vocab_size"] * 8 == published["vocab_size"] == 196608
    # one whole period: the gated attention, then three delta-rule layers
    kinds = [[cut["block_config"][c]["layer"][-1] for c in row]
             for row in cut["block_schedule"]]
    assert [k[0] for k in kinds] == [GQA, "kda", "kda", "kda"]
    assert all(k[1] == MOE for k in kinds) and cut["gqa_layers"] == [0]
    cfg = Config(dict(cut))
    la = cfg.linear_attn_config
    assert (cfg.heads * cfg.features_per_head, cfg.head_dim,
            cfg.moe_intermediate_size, cfg.experts) == (4096, 128, 1280, 320)
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
            ) == (16, 128, 4)
    # a quarter of the heads, each query share with its own K/V heads
    assert (cfg.num_attention_heads * 4, cfg.num_key_value_heads * 4) == (
        published["num_attention_heads"], published["num_key_value_heads"]
        ) == (64, 8)
    assert la["num_heads"] * 4 == published["linear_attn_config"]["num_heads"]
    assert (cfg.use_rope, cfg.use_gqa_gate, cfg.kda_allow_neg_eigval,
            cfg.kda_use_full_proj) == (False, True, True, False)
    assert cfg.rms_norm_eps == 1e-5 and cfg.routed_scaling_factor == 1
    assert (cut["n_routed_experts"], cut["n_shared_experts"],
            cut["num_experts_per_tok"], cut["hidden_size"]) == (320, 1, 8,
                                                                4096)
    sz = ref.Sizes.from_config(cut)
    count = sum(int(np.prod(s)) for s in ref.shapes(sz).values())
    assert 905e6 < count < 907e6, count
    # the published file is the same model, whole: 48 layers, 1 : 3
    whole = Config(dict(published))
    assert whole.depth == 48 and whole.experts_held == 320
    assert [r[0] for r in whole.block_schedule] == [
        0 if d in published["gqa_layers"] else 1 for d in range(48)]
    assert published["gqa_layers"] == list(range(0, 48, 4))
    assert published["gqa_interval"] == 3


# -- (g) the yardstick --------------------------------------------------------

def test_the_yardstick_counts_what_the_plain_forward_multiplies():
    """benchmark/flops_solar_open2.py against a jaxpr count of the
    reference's layers at the toy size (forward, multiply-adds a token).  The
    delta-rule layer agrees to the unit.  The plain attention multiplies the
    whole square where the yardstick counts the triangle the mask leaves,
    and the plain expert layer applies every held expert to every token
    where the yardstick counts the expected load: both differences are
    written out."""
    from homebrewnlp_tpu.train.flops import jaxpr_flops
    raw = toy()
    sz = ref.Sizes.from_config(raw)
    u = stream(1)
    tokens, s = 2 * 48, 48
    part = flops.part_macs_per_token(raw)

    def counted(kind, f):
        params = part_params(sz, kind, 1, kind + "_/")
        return jaxpr_flops(jax.make_jaxpr(f)(params, u)) / 2 / tokens

    assert counted("kda", lambda p, x: ref._kda(p, x, sz, SOUND)
                   ) == part["kda"]
    h, w = 8, 8
    # ... and the traced flag of `gate_after_out_proj` sends the gate's
    # logits through the output matrix whether the fault is planted or not
    assert counted("gqa", lambda p, x: ref._gqa(p, x, sz, SOUND, rows=48)
                   ) - part["gqa"] == (2 * h * w * (s - (s + 1) / 2)
                                       + h * w * 24)
    expert = 3 * 24 * 16
    assert counted("routed_moe", lambda p, x: ref._experts(p, x, sz, SOUND)[0]
                   ) - part["routed_moe"] == (8 - 8 * 8 / 32) * expert
    assert flops.forward_macs_per_token(raw) == (
        part["gqa"] + 3 * part["kda"] + 4 * part["routed_moe"] + 24 * 128)
    assert flops.train_step_flops(raw) == 6 * tokens * (
        flops.forward_macs_per_token(raw))


def test_the_yardstick_at_the_cells_sizes():
    _, cut, _ = _files()
    part = flops.part_macs_per_token(cut)
    d, s = 4096, 8192
    assert part["kda"] == (3 * d * 2048 + 2 * (d * 128 + 128 * 2048) + d * 16
                           + 2048 * d + 3 * 16 * 128 * 128)
    assert part["gqa"] == (d * (2 * 16 + 2 * 2) * 128 + 2048 * d
                           + 2 * 16 * 128 * (s + 1) / 2)
    assert part["routed_moe"] == d * 320 + (1 + 8 * 8 / 320) * 3 * d * 1280
    step = flops.train_step_flops(cut)
    assert 16e12 < step < 17e12, step
    # the experts' grouped products are a small share of it
    routed = 4 * 8 * 8 / 320 * 3 * d * 1280 * 6 * s
    assert 0.03 < routed / step < 0.05
    work = flops.attention(cut)
    assert work["backward"]["flops"] == 2 * work["forward"]["flops"] == (
        2 * 2 * 2 * 16 * 128 * (s * (s + 1) // 2))
    # q, o of 16 heads, k, v of 2, the row statistic, at one sequence
    assert work["forward"]["bytes"] == s * (
        2 * 16 * 128 * 2 + 2 * 2 * 128 * 2 + 16 * 4)
    assert len(flops.attention_passes(cut)) == 2 * 1


# -- (h) what is turned away --------------------------------------------------

def test_full_rank_gates_are_refused_with_a_reason():
    with pytest.raises(ValueError, match="only the two low-rank pairs"):
        Config(toy(kda_use_full_proj=True))


def test_serving_these_mixers_is_turned_away_with_a_reason(caplog):
    from homebrewnlp_tpu.infer.kv_cache import cache_eligible
    with caplog.at_level("INFO"):
        assert not cache_eligible(Config(toy()))
    assert "gqa-nope, writes k as it is" in caplog.text
    assert "gate from the decoded token's own input" in caplog.text
    with caplog.at_level("INFO"):
        assert not cache_eligible(Config(toy(
            block_config=toy()["block_config"][1:], block_schedule=None,
            depth=1)))
    assert "state cache" in caplog.text


def test_main_trains_the_toy_shape_through_the_normal_path(tmp_path):
    from homebrewnlp_tpu.main import main as cli_main
    from homebrewnlp_tpu.train.metrics import read_metric_rows
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(toy(model_path=str(tmp_path / "run"),
                                   vocab_size=256)))
    cli_main(["--model", str(path), "--run_mode", "train", "--steps", "3"])
    rows = read_metric_rows(str(tmp_path / "run" / "metrics.jsonl"))
    assert rows[-1]["step"] == 2 and np.isfinite(rows[-1]["loss"])
    assert rows[-1]["expert_pairs_held"] > 0
    assert rows[-1]["expert_pairs_layer_max"] > 0
    assert 0 < rows[-1]["expert_rows_filled"] <= 1
