"""Kimi-Linear's block parts (models/hybrid.py) against the plain reference
(benchmark/reference/kimi_linear.py), at toy sizes on the CPU in float32.

The tolerances, and why.  Both sides compute in float32 from the same seeded
weights, so what is left is the order of sums: the chunked delta rule solves
a triangular system where the reference walks token by token, attention
takes other blocks, the experts see only the tokens routed to them.  That
leaves 1e-5 of scale on a layer's output (`LAYER`).  Through three updates
the optimizer keeps momentum and SM3 rows in bfloat16 on both sides: a
reading rounds to 2**-9 there, so `sm3_leaf` and `change_leaf` get 4e-3 and
the losses, which see the weights only through the learning rate, 1e-5.
"""
import contextlib
import functools
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
MOE = "routed_moe-sigmoid-bias-topk8-gated-shared1-in:silu"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "kimi_linear.py"), "kimi_ref")
compare = _load(os.path.join(BENCH, "compare.py"), "compare")


def toy(**over):
    """The benchmark's configuration at a toy width: all five layers, the
    same block parts and schedule."""
    raw = dict(
        model_mode="gpt", sequence_length=48, heads=4, features_per_head=6,
        vocab_size=128, depth=5, train_batch_size=2, calc_accuracy=False,
        memory_reduction_strategy="checkpoint", weight_decay=0.0001,
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
        learning_rate=0.01, z_loss=1e-4, embedding_stddev=0.02,
        intermediate_feed_forward_multiplier=4.0, factorized_embedding=False,
        scale_by_depth=False, weight_centralisation=False,
        weight_standardisation=False, experts=32, experts_held=8,
        expert_offset=0, moe_intermediate_size=16, routed_scaling_factor=2.446,
        moe_balance_weight=0.0, rms_norm_eps=1e-5,
        linear_attn_config={"num_heads": 4, "head_dim": 8,
                            "short_conv_kernel_size": 4},
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        mla_use_nope=True,
        tpu_size=1,
        calculation_dtype="float32", slice_dtype="float32",
        storage_dtype="float32", optimizer_slice_dtype="bfloat16",
        block_config=[
            {"layer": ["rms_norm-scale", "kda"], "skip": True},
            {"layer": ["rms_norm-scale", "mla"], "skip": True},
            {"layer": ["rms_norm-scale", "gated_feed_forward-in:silu"],
             "skip": True},
            {"layer": ["rms_norm-scale", MOE], "skip": True}],
        block_schedule=[[0, 2], [0, 3], [0, 3], [1, 3], [0, 3]],
        output_block_config=[{"layer": ["rms_norm-scale"]}],
        learning_rate_config={"linear_warmup": {"final_step": 64}})
    raw.update(over)
    return raw


def run_layer(cfg, spec, params, x, names=("batch", "sequence", "heads",
                                           "features_per_head")):
    """One layer of the DSL on `x`, its parameters keyed as under its own
    scope (`kda_/conv/q_proj` ...).  Returns (output array, ctx)."""
    name, *extras = spec.split("-")
    ctx = Ctx(cfg, params=params, train=True)
    out = ctx.scoped(name + "_", LAYER_FUNCTIONS[name],
                     Args(ctx, NT(x, names), extras))
    return out.transpose_to(names).x, ctx


def part_params(sz, kind, seed, prefix):
    """Seeded weights of one block part, by the reference's rules, keyed as
    `run_layer` wants them."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(mean, std, shape), jnp.float32)
            for k, (shape, (mean, std)) in ref._part_leaves(sz, kind).items()
            if k.startswith(prefix)}


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

    config = dict(toy(), benchmark={"followed_steps": 3, "reference_rows": 1})
    program = runner.Program(config, traffic, 2 ** 31 + 77, ref, Spans(),
                             lambda m: None)
    got = program.follow_and_warm_up()
    want = ref.follow(program.sizes, program.seed, program.host_batches, 3, 1)
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
    # table, head, final norm; 4 kda parts, 1 mla, 1 dense, 4 expert parts
    assert len(want["names"]) == 3 + 4 * 16 + 6 + 4 + 4 * 9


def test_step_reports_the_experts_load(followed):
    program = followed[2]
    program.state, metrics = program.trainer.step(
        program.state, program.ring[0], jax.random.key(0))
    pairs = float(metrics["expert_pairs_held"])
    # 96 tokens x top-8 over 32 experts, 8 held, four expert layers
    assert 0.5 * 768 < pairs < 1.5 * 768
    assert float(metrics["expert_load_mean"]) == pytest.approx(pairs / 32)
    assert float(metrics["expert_load_max"]) >= pairs / 32


# -- (b) each mixer alone -----------------------------------------------------

def _kda_inputs(t, decay, seed=0, b=2, n=3, d=8):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(b, t, n, d))) * d ** -0.5
    k = unit(rng.normal(size=(b, t, n, d)))
    v = rng.normal(size=(b, t, n, d))
    g = -decay * rng.uniform(0.2, 1.0, size=(b, t, n, d))
    beta = rng.uniform(0.1, 0.9, size=(b, t, n))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("t,decay,chunk,sub", [
    (64, 0.1, 16, 16), (64, 5.0, 16, 16), (128, 5.0, 64, 16),
    (128, 5.0, 64, 8), (50, 1.0, 16, 4), (37, 5.0, 64, 16)],
    ids=["mild", "decay5", "decay5_chunk64", "decay5_sub8", "ragged",
         "shorter_than_chunk"])
def test_chunked_delta_rule_matches_the_recurrence(t, decay, chunk, sub):
    """Chunks against the token-by-token recurrence, with a log-decay down
    to -5 a token (exp(-cumsum) would overflow after 18 tokens) and sequences
    that are no multiple of the chunk."""
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    args = _kda_inputs(t, decay)
    got = chunked_kda(*args, chunk=chunk, group=2, sub=sub)
    want = ref._delta_rule(*args, inner=1)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, **LAYER)
    grads = [jax.grad(lambda *a, f=f: jnp.sum(jnp.sin(f(*a))), range(5))(*args)
             for f in (lambda *a: chunked_kda(*a, chunk=chunk, group=2, sub=sub),
                       lambda *a: ref._delta_rule(*a, inner=1))]
    for mine, theirs in zip(*grads):
        assert np.all(np.isfinite(mine))
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t,decay", [(64, 0.1), (128, 5.0), (70, 5.0),
                                     (20, 1.0)],
                         ids=["mild", "decay5_whole_span", "ragged",
                              "shorter_than_chunk"])
def test_delta_rule_kernels_match_the_scan_and_the_recurrence(t, decay,
                                                              monkeypatch):
    """ops/pallas_kda.py's kernels (interpreted here) at a head width of one
    lane tile, against the scan they replace there and the token-by-token
    recurrence: outputs and all five gradients.  128 tokens fill the matrix
    unit's span of four chunks; 64 and 70 walk chunk by chunk."""
    from homebrewnlp_tpu.ops import delta_rule
    args = _kda_inputs(t, decay, b=1, n=2, d=128)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = delta_rule.chunked_kda(*args)
    mine = jax.grad(loss(delta_rule.chunked_kda), range(5))(*args)
    assert np.all(np.isfinite(got))
    recurrence = lambda *a: ref._delta_rule(*a, inner=1)
    np.testing.assert_allclose(got, recurrence(*args), **LAYER)
    for a, b in zip(mine, jax.grad(loss(recurrence), range(5))(*args)):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    monkeypatch.setattr(delta_rule, "_kernel_parts", delta_rule._scan_parts)
    np.testing.assert_allclose(got, delta_rule.chunked_kda(*args), rtol=1e-6,
                               atol=1e-6)
    for a, b in zip(mine, jax.grad(loss(delta_rule.chunked_kda), range(5))(
            *args)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_head_width_alone_chooses_the_kernels():
    """Whole lane tiles take the kernels, forward and backward; every other
    width keeps the scan."""
    from homebrewnlp_tpu.ops.delta_rule import chunked_kda
    for d, kernels in ((8, 0), (128, 2)):
        args = _kda_inputs(64, 1.0, b=1, n=2, d=d)
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(chunked_kda(*a)), range(5)))(*args))
        assert text.count("pallas_call") == kernels, (d, kernels)


def test_kda_layer_matches_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "kda", 1, "kda_/")
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 48, 4, 6)),
                    jnp.float32)
    got, _ = run_layer(cfg, "kda", params, u)
    want = ref._kda(params, u, sz, ref.SOUND)
    np.testing.assert_allclose(got, want, **LAYER)


@pytest.mark.parametrize("rows", [7, 16, 20, 48])
def test_blocked_attention_matches_the_full_matrix(rows):
    from homebrewnlp_tpu.ops.block_attention import causal_attention
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(2, 48, 3, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 48, 3, 8)), jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    scores = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(causal_attention(q, k, v, rows=rows), want,
                               **LAYER)


def _attention_inputs(s, kind, b=1, h=2, d=192, d_v=128, seed=6):
    """Queries (scaled), keys and values at the latent attention's head
    widths, and the whole-matrix softmax of their float32 values."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, w)) * scale, kind)
               for w, scale in ((d, d ** -0.5), (d, 1.0), (d_v, 1.0)))

    def full(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")

    return (q, k, v), full


@pytest.mark.parametrize("kind", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [1, 3], ids=["one_block", "three_blocks"])
def test_attention_kernels_match_the_tiles_and_the_full_matrix(blocks, kind,
                                                               monkeypatch):
    """ops/pallas_mla.py's kernel pair (interpreted here) at the latent
    attention's widths, 192 and 128, against the unrolled tiles it replaces
    at such shapes and against the softmax over the whole [S, S] matrix:
    outputs and all three gradients.  One block is one diagonal tile; three
    walk tiles below the diagonal too and add up a head's dK, dV over blocks
    of rows.  In float32 all three agree to the order of their sums; in
    bfloat16 the kernels stand no further from the exact result than the
    tiles do (no rounding point lower than theirs)."""
    from homebrewnlp_tpu.ops import block_attention, pallas_mla
    s = blocks * pallas_mla.BLOCK
    args, full = _attention_inputs(s, kind)
    assert block_attention.takes_kernels(args[0], args[2])
    f32 = lambda xs: [np.asarray(x.astype(jnp.float32)) for x in xs]

    def results(f):
        loss = lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32)))
        return f32([f(*args), *jax.grad(loss, range(3))(*args)])

    kernels = results(block_attention.causal_attention)
    exact = results(full)
    monkeypatch.setattr(block_attention, "takes_kernels", lambda q, v: False)
    tiles = results(functools.partial(block_attention.causal_attention,
                                      rows=pallas_mla.BLOCK))
    for mine, theirs, want in zip(kernels, tiles, exact):
        assert np.all(np.isfinite(mine))
        if kind == jnp.float32:
            np.testing.assert_allclose(mine, want, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)
        else:
            off = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
            assert off(mine) <= 1.1 * off(theirs), (off(mine), off(theirs))
            assert off(mine) <= 1e-2 * float(np.sqrt(np.mean(want ** 2)))


def test_the_shape_alone_chooses_the_attention_kernels():
    """A sequence of whole blocks at head widths of whole or half lane tiles
    takes the kernel pair, forward and backward; every other shape, the toy
    configuration's and `test_blocked_attention_matches_the_full_matrix`'s
    among them, keeps the unrolled tiles."""
    from homebrewnlp_tpu.ops.block_attention import causal_attention
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    for s, d, d_v, kernels in ((BLOCK, 192, 128, 2), (2 * BLOCK, 64, 64, 2),
                               (48, 12, 8, 0), (BLOCK + 8, 192, 128, 0),
                               (BLOCK, 12, 8, 0), (BLOCK, 192, 72, 0)):
        (q, k, v), _ = _attention_inputs(s, jnp.float32, d=d, d_v=d_v)
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(causal_attention(*a)), range(3)))(q, k, v))
        assert text.count("pallas_call") == kernels, (s, d, d_v, kernels)


def test_mla_layer_matches_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "mla", 4, "mla_/")
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 48, 4, 6)),
                    jnp.float32)
    got, _ = run_layer(cfg, "mla", params, u)
    np.testing.assert_allclose(got, ref._mla(params, u, sz, rows=48), **LAYER)


def test_gated_feed_forward_and_rms_norm_match_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "gated_feed_forward", 6, "")
    u = jnp.asarray(np.random.default_rng(7).normal(size=(2, 48, 4, 6)),
                    jnp.float32)
    got, _ = run_layer(cfg, "gated_feed_forward-in:silu", params, u)
    np.testing.assert_allclose(got, ref._dense(params, u), **LAYER)
    got, _ = run_layer(cfg, "rms_norm-scale", params, u)
    want = ref._rms(u.reshape(2, 48, 24), params["rms_norm_/scale"].reshape(-1),
                    1e-5).reshape(u.shape)
    np.testing.assert_allclose(got, want, **LAYER)


# -- (c), (d) the experts' share and no drops ---------------------------------

def _expert_case(seed=8):
    raw = toy()
    whole = ref.Sizes.from_config(dict(raw, experts_held=32))
    params = part_params(whole, "routed_moe", seed, "routed_moe_/")
    u = jnp.asarray(np.random.default_rng(seed + 1).normal(size=(2, 48, 4, 6)),
                    jnp.float32)
    return raw, whole, params, u


def _share(params, first, held):
    """The weights a chip holding experts `first .. first + held` has."""
    return {k: v[first:first + held] if k.startswith(
        "routed_moe_/orthogonal_var") else v for k, v in params.items()}


def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all four shares (8 of 32
    experts each, as 8 of 256 in the cell) plus the shared expert counted
    once are the reference's uncut expert layer."""
    raw, whole, params, u = _expert_case()
    uncut = ref._experts(params, u, whole, ref.SOUND)
    shared_only = ref._experts(
        params, u, whole._replace(held=0), ref.SOUND)
    total = shared_only
    pairs = 0
    for rank in range(4):
        cfg = Config(dict(raw, expert_offset=8 * rank))
        got, ctx = run_layer(cfg, MOE, _share(params, 8 * rank, 8), u)
        total = total + (got - shared_only)
        pairs += int(jnp.sum(ctx.expert_load[0]))
        part = ref._experts(_share(params, 8 * rank, 8), u,
                            whole._replace(held=8, offset=8 * rank), ref.SOUND)
        np.testing.assert_allclose(got, part, **LAYER)
    assert pairs == 96 * 8          # every selected pair fell on one share
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=3e-5)


@pytest.mark.parametrize("chunk", [16, 100, 768])
def test_no_token_is_dropped_when_one_expert_takes_them_all(chunk,
                                                            monkeypatch):
    """A selection bias sends every token to held expert 3: its load is the
    whole batch; with chunks of 16 or 100 pairs the held pairs fill several
    chunks (the last in part), with 768 a part of one; the layer still equals
    the reference."""
    raw, whole, params, u = _expert_case(10)
    params["routed_moe_/router_bias"] = jnp.zeros((32,)).at[3].set(10.0)
    from homebrewnlp_tpu.models import hybrid
    monkeypatch.setattr(hybrid, "expert_chunk", lambda *share: chunk)
    got, ctx = run_layer(Config(raw), MOE, _share(params, 0, 8), u)
    assert int(ctx.expert_load[0][3]) == 96
    want = ref._experts(_share(params, 0, 8), u, whole._replace(held=8),
                        ref.SOUND)
    np.testing.assert_allclose(got, want, **LAYER)


def test_expert_layer_gradients_match_the_reference():
    raw, whole, params, u = _expert_case(12)
    held = _share(params, 0, 8)
    cfg = Config(raw)

    def mine(p, x):
        return jnp.sum(jnp.sin(run_layer(cfg, MOE, p, x)[0]))

    def theirs(p, x):
        return jnp.sum(jnp.sin(ref._experts(p, x, whole._replace(held=8),
                                            ref.SOUND)))

    got, want = (jax.grad(f, (0, 1))(held, u) for f in (mine, theirs))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for k in want[0]:
        np.testing.assert_allclose(got[0][k], want[0][k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)
    assert not np.any(got[0]["routed_moe_/router_bias"])


# -- (e) scopes ---------------------------------------------------------------

def test_step_scope_gives_every_new_layer_its_own_layer_and_pass():
    from homebrewnlp_tpu.obs import profile as P
    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import random_text_batch
    cfg = Config(toy())
    tr = Trainer(cfg, make_mesh(cfg, jax.devices()[:1]))
    batch = random_text_batch(cfg)
    tr.step_cost_analysis(tr.init(batch), batch)
    names = P.op_map_from_hlo_text(tr._compiled.as_text()).values()
    seen = {}
    for name in names:
        pass_, block, layer = P.step_scope(name)
        if block is not None:
            seen.setdefault(layer, set()).add(pass_)
            assert layer in ("kda", "mla", "routed_moe", "gated_feed_forward",
                             "norm", "skip"), name
    for layer in ("kda", "mla", "routed_moe", "gated_feed_forward", "norm"):
        assert {"forward", "remat", "backward"} <= seen[layer], (layer, seen)
    inner = {p for n in names for p in n.split("/")}
    assert {"conv", "gates", "chunk_scan", "out", "router", "dispatch",
            "experts", "shared", "combine"} <= inner
    # ops/pallas_kda.py's kernels (the toy width keeps the scan), named as
    # the v5e compile of the cell's gradient names them
    step, under = "jit(step_fn)/", "/block_/kda_/chunk_scan/jit(_kda_chunks_"
    back = step + "transpose(jvp(gpt))/body/jvp(gpt)/body/checkpoint/"
    for name, pass_ in (
            (step + "jvp(gpt)/body/gpt/body/d0_0" + under
             + "fwd)/pallas_call", "forward"),
            (back + "rematted_computation/gpt/body/d4_0" + under
             + "fwd)/pallas_call", "remat"),
            (back + "gpt/body/d4_0" + under + "bwd)/pallas_call",
             "backward")):
        assert P.step_scope(name) == (pass_, name.split("/body/")[-1][:4],
                                      "kda"), name
    # ops/pallas_gmm.py's kernels in the experts' loops (the toy width keeps
    # `ragged_dot`): the forward's products again inside the backward's
    # `jax.vjp`, then the rows' and the stacks' gradients
    under = "/block_/routed_moe_/experts/while/body/"
    for name, pass_ in (
            (step + "jvp(gpt)/body/gpt/body/%s" + under
             + "jit(_gmm_rows)/pallas_call", "forward"),
            (back + "gpt/body/%s" + under
             + "jvp(jit(_gmm_rows))/pallas_call", "backward"),
            (back + "gpt/body/%s" + under
             + "transpose(jvp(jit(_gmm_rows)))/pallas_call", "backward"),
            (back + "gpt/body/%s" + under
             + "transpose(jvp(jit(_gmm_weights)))/pallas_call", "backward")):
        assert P.step_scope(name % "d4_1") == (pass_, "d4_1",
                                               "routed_moe"), name


# -- (f) the two configuration files ------------------------------------------

def _files():
    with open(os.path.join(REPO, "configs", "kimi_linear_48b.json")) as f:
        published = json.load(f)
    with open(os.path.join(BENCH, "configs", "kimi_linear_48b.json")) as f:
        cut = json.load(f)
    return published, cut, cut.pop("benchmark")


def test_the_cut_differs_from_the_published_file_in_the_reduced_keys_only():
    published, cut, meta = _files()
    changed = sorted(k for k in set(cut) | set(published)
                     if cut.get(k) != published.get(k))
    assert changed == sorted(meta["reduced"])
    assert meta["published"] == {k: published[k] for k in meta["reduced"]}
    widths = ("_dim", "_rank", "_size", "heads", "features", "head_dim",
              "per_token", "linear_attn_config", "multiplier")
    assert not [k for k in changed if k not in ("vocab_size", "tpu_size")
                and any(w in k for w in widths)]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "kimi_linear_48b")
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == meta["source"]


def test_the_cut_keeps_the_guides_floors_and_every_published_width():
    published, cut, meta = _files()
    assert cut["experts_held"] >= 8 and cut["experts"] == 256
    assert cut["vocab_size"] * 8 >= published["vocab_size"]
    # a whole period (3 kda : 1 mla) after the leading dense layer
    kinds = [[cut["block_config"][c]["layer"][-1].split("-")[0] for c in row]
             for row in cut["block_schedule"]]
    assert kinds[0] == ["kda", "gated_feed_forward"]
    assert [k[0] for k in kinds[1:5]] == ["kda", "kda", "mla", "kda"]
    assert all(k[1] == "routed_moe" for k in kinds[1:])
    la = cut["linear_attn_config"]
    for depth, (mixer, _) in enumerate(kinds, 1):
        assert depth in (la["kda_layers"] if mixer == "kda"
                         else la["full_attn_layers"])
    cfg = Config({k: v for k, v in cut.items()})
    assert (cfg.heads * cfg.features_per_head, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2304, 9216, 1024)
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
            ) == (32, 128, 4)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.routed_scaling_factor == 2.446
    assert "sigmoid-bias-topk8-gated-shared1" in cut["block_config"][3][
        "layer"][-1]
    sz = ref.Sizes.from_config(cut)
    count = sum(int(np.prod(s)) for s in ref.shapes(sz).values())
    assert 601e6 < count < 603e6, count
    # the published file is the same model, whole: 27 layers in the lists' order
    whole = Config(dict(published))
    assert whole.depth == 27 and len(whole.block_schedule) == 27
    assert [r[0] for r in whole.block_schedule] == [
        0 if d in la["kda_layers"] else 1 for d in range(1, 28)]


def test_serving_these_mixers_is_turned_away_with_a_reason(caplog):
    from homebrewnlp_tpu.infer.kv_cache import cache_eligible
    with caplog.at_level("INFO"):
        assert not cache_eligible(Config(toy()))
    assert "state cache" in caplog.text
    with caplog.at_level("INFO"):
        assert not cache_eligible(Config(toy(
            block_config=toy()["block_config"][1:3], block_schedule=None)))
    assert "latent cache" in caplog.text


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
    assert rows[-1]["expert_load_max"] >= rows[-1]["expert_load_mean"]
