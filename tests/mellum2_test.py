"""Mellum 2's block parts (models/hybrid.py::gqa, the routed experts at a
share of a quarter) against the plain reference
(benchmark/reference/mellum2.py), at toy sizes on the CPU in float32.

The tolerances, and why.  Both sides compute in float32 from the same seeded
weights, so what is left is the order of sums: attention takes other blocks
(an online softmax over tiles where the reference takes whole rows), the
experts see only the tokens routed to them.  That leaves 1e-5 of scale on a
layer's output (`LAYER`).  Through three updates the optimizer keeps
momentum and SM3 rows in bfloat16 on both sides: a reading rounds to 2**-9
there, so `sm3_leaf`, the first gradient and `change_leaf` get 4e-3 and the
losses, which see the weights only through the learning rate, 1e-5.
"""
import contextlib
import functools
import importlib.util
import json
import math
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
MOE = "routed_moe-topk8-gated-in:silu"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
DEFAULT = {"rope_type": "default", "rope_theta": 500000}
NAMES = ("batch", "sequence", "heads", "features_per_head")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "mellum2.py"), "mellum2_ref")
compare = _load(os.path.join(BENCH, "compare.py"), "compare")
flops = _load(os.path.join(BENCH, "flops_mellum2.py"), "flops_mellum2")


def toy(**over):
    """The benchmark's configuration at a toy width: one whole period (three
    sliding layers, one full), the same block parts and schedule, a window a
    third of the sequence, the YaRN table stretched from 16 positions on so
    that both tables differ within 48."""
    raw = dict(
        model_mode="gpt", sequence_length=48, heads=4, features_per_head=6,
        vocab_size=128, depth=4, train_batch_size=2, calc_accuracy=False,
        memory_reduction_strategy="checkpoint", weight_decay=0.0001,
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
        learning_rate=0.01, z_loss=1e-4, embedding_stddev=0.02,
        intermediate_feed_forward_multiplier=4.0, factorized_embedding=False,
        scale_by_depth=False, weight_centralisation=False,
        weight_standardisation=False, experts=32, experts_held=8,
        expert_offset=0, moe_intermediate_size=16, moe_balance_weight=0.0,
        rms_norm_eps=1e-6, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, sliding_window=16,
        rope_parameters={
            "sliding_attention": dict(DEFAULT, rope_theta=100.0),
            "full_attention": dict(YARN, rope_theta=100.0, factor=4.0,
                                   original_max_position_embeddings=16,
                                   attention_factor=1.2)},
        tpu_size=1, calculation_dtype="float32", slice_dtype="float32",
        storage_dtype="float32", optimizer_slice_dtype="bfloat16",
        block_config=[
            {"layer": ["rms_norm-scale", "gqa-sliding_attention"],
             "skip": True},
            {"layer": ["rms_norm-scale", "gqa-full_attention"], "skip": True},
            {"layer": ["rms_norm-scale", MOE], "skip": True}],
        block_schedule=[[0, 2], [0, 2], [0, 2], [1, 2]],
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
    program = runner.Program(config, traffic, 2 ** 31 + 131, ref, Spans(),
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
    assert all(g > 0 for g in want["grad_leaf"])        # every leaf moved


def test_the_runner_sees_the_parameters_the_reference_names(followed):
    _, want, program = followed
    assert sorted(ref.shapes(program.sizes)) == want["names"]
    # table, head, final norm; 4 attention parts of 5, 4 expert parts of 5
    assert len(want["names"]) == 3 + 4 * 5 + 4 * 5


def test_step_reports_the_load_of_the_held_experts(followed):
    program = followed[2]
    program.state, metrics = program.trainer.step(
        program.state, program.ring[0], jax.random.key(0))
    pairs = float(metrics["expert_pairs_held"])
    # 96 tokens x top-8 over 32 experts, 8 held, four expert layers
    assert 0.5 * 768 < pairs < 1.5 * 768
    assert float(metrics["expert_load_mean"]) == pytest.approx(pairs / 32)


def test_step_reports_the_share_of_multiplied_rows_that_hold_a_pair(followed):
    """`expert_rows_filled`: the fullest layer's held pairs over the rows its
    loop multiplied.  At the toy width no run is laid out on tiles, so those
    are the loop's trips times the chunk (all 768 pairs here: one trip)."""
    program = followed[2]
    program.state, metrics = program.trainer.step(
        program.state, program.ring[1], jax.random.key(1))
    fullest = float(metrics["expert_pairs_layer_max"])
    assert 0 < fullest <= 768
    assert float(metrics["expert_rows_filled"]) == pytest.approx(
        fullest / 768)


@pytest.mark.parametrize("case", sorted(ref.LOWER))
def test_every_planted_fault_moves_the_reference(followed, case):
    """Each case of `LOWER` is a different model at the toy size too: it
    reads apart from the sound reference by more than the program does."""
    _, want, program = followed
    got = ref.follow(program.sizes, program.seed, program.host_batches, 3, 1,
                     lower=case)
    read = compare.readings(got, want)
    assert max(read["loss3"], read["sm3_leaf"], read["change_leaf"]) > 4e-3, (
        case, read)


# -- (b) the attention layer alone --------------------------------------------

@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_gqa_layer_matches_the_reference(kind):
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, kind, 1, "gqa_/")
    u = stream(2)
    got, _ = run_layer(cfg, "gqa-" + kind, params, u)
    fault = {k: jnp.float32(v) for k, v in ref.SOUND.items()}
    want = ref._attention(params, u, sz, kind, fault, rows=16)
    np.testing.assert_allclose(got, want, **LAYER)
    # the window and the table are the layer type's: the other type's differ
    other = ({"sliding_attention", "full_attention"} - {kind}).pop()
    assert np.abs(np.asarray(run_layer(cfg, "gqa-" + other, params, u)[0])
                  - np.asarray(want)).max() > 1e-3


def test_gqa_layer_gradients_match_the_reference():
    cfg = Config(toy())
    sz = ref.Sizes.from_config(toy())
    params = part_params(sz, "sliding_attention", 3, "gqa_/")
    fault = {k: jnp.float32(v) for k, v in ref.SOUND.items()}

    def mine(p, x):
        return jnp.sum(jnp.sin(run_layer(cfg, "gqa-sliding_attention", p,
                                         x)[0]))

    def theirs(p, x):
        return jnp.sum(jnp.sin(ref._attention(p, x, sz, "sliding_attention",
                                              fault, rows=16)))

    got, want = (jax.grad(f, (0, 1))(params, stream(4)) for f in (mine,
                                                                  theirs))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for k in want[0]:
        np.testing.assert_allclose(got[0][k], want[0][k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


@pytest.mark.parametrize("entry", [DEFAULT, YARN], ids=["default", "yarn"])
def test_rotary_tables_match_a_direct_formula(entry):
    """The published entries at the published head width, pair by pair."""
    from homebrewnlp_tpu.ops import rotary
    dim, theta = 128, entry["rope_theta"]
    got, factor = rotary.inverse_frequencies(entry, dim)
    want = []
    for i in range(dim // 2):
        plain = theta ** (-2 * i / dim)
        if entry["rope_type"] == "default":
            want.append(plain)
            continue
        turns = lambda n: dim * math.log(8192 / (n * 2 * math.pi)) / (
            2 * math.log(theta))
        low, high = math.floor(turns(32)), math.ceil(turns(1))
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain / 16 * ramp + plain * (1 - ramp))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got.dtype == np.float32
    assert factor == (1.0 if entry is DEFAULT else 1.2772588722239782)
    if entry is YARN:
        assert factor == pytest.approx(0.1 * math.log(16) + 1)
        # short wavelengths are left alone, long ones stretched 16 times
        assert got[0] == 1.0 and got[-1] == pytest.approx(
            theta ** (-126 / 128) / 16, rel=1e-6)
    cos, sin = rotary.table(entry, dim, 32)
    angle = np.arange(32, dtype=np.float32)[:, None] * got[None]
    np.testing.assert_allclose(cos, np.cos(angle) * factor, atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angle) * factor, atol=1e-6)
    assert cos.dtype == jnp.float32
    # the reference's own formula, written apart, agrees
    theirs, their_factor = ref.rotary_frequencies(entry, dim)
    np.testing.assert_allclose(got, theirs, rtol=1e-6)
    assert factor == their_factor


def test_rotation_turns_pairs_and_keeps_their_length():
    from homebrewnlp_tpu.ops import rotary
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 32, 2, 128)),
                    jnp.bfloat16)
    cos, sin = rotary.table(DEFAULT, 128, 32)
    got = rotary.rotate(x, cos, sin)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got[:, 0], x[:, 0].astype(jnp.float32))
    pairs = lambda t: np.hypot(*np.split(np.asarray(t, np.float32), 2, -1))
    np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5)
    # relative: the score of a rotated pair depends on the distance alone
    q = rotary.rotate(jnp.broadcast_to(x[:, :1], x.shape), cos, sin)
    score = jnp.einsum("bshd,bthd->bhst", q, q)
    np.testing.assert_allclose(score[0, 0, 3, 7], score[0, 0, 10, 14],
                               rtol=1e-4)


# -- (c) the kernels ----------------------------------------------------------

def _attention_inputs(s, kind, group, b=1, kv=2, d=128, seed=6):
    """Queries (scaled) over `group` times `kv` heads, keys and values over
    `kv`, and the softmax over the whole masked [S, S] matrix of their
    float32 values, the K/V heads repeated plainly."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)) * scale, kind)
               for h, scale in ((kv * group, d ** -0.5), (kv, 1.0), (kv, 1.0)))

    def full(q, k, v, window=None):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = (jnp.repeat(x, group, 2) for x in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
        behind = jnp.arange(s)[:, None] - jnp.arange(s)[None]
        seen = behind >= 0
        if window is not None:
            seen &= behind < window
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                          precision="highest")

    return (q, k, v), full


# a block of the kernels is 512: windows under, at and over a block, at two
# blocks (the cell's), between blocks, and longer than the sequence; in
# bfloat16 the cell's own group, with no window, the cell's and an odd one
WINDOWS = [None, 200, 512, 700, 1024, 4096]
KERNEL_CASES = [(w, g, jnp.float32) for w in WINDOWS for g in (1, 8)] + [
    (w, 8, jnp.bfloat16) for w in (None, 700, 1024)]


@pytest.mark.parametrize(
    "window,group,kind", KERNEL_CASES,
    ids=[f"{w}-{g}-{k.__name__}" for w, g, k in KERNEL_CASES])
def test_attention_kernels_match_the_tiles_and_the_full_matrix(
        window, group, kind, monkeypatch):
    """ops/pallas_mla.py's kernel pair (interpreted here) with grouped K/V
    heads and a window, over three blocks of rows, against the unrolled
    tiles and against the softmax over the whole masked matrix: outputs and
    all three gradients, dK and dV summed over the group.  In float32 all
    three agree to the order of their sums; in bfloat16 the kernels stand no
    further from the exact result than the tiles do."""
    from homebrewnlp_tpu.ops import block_attention, pallas_mla
    s = 3 * pallas_mla.BLOCK
    args, full = _attention_inputs(s, kind, group, kv=1 if group == 8 else 2)
    assert block_attention.takes_kernels(args[0], args[2])
    f32 = lambda xs: [np.asarray(x.astype(jnp.float32)) for x in xs]

    def results(f):
        loss = lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32)))
        return f32([f(*args), *jax.grad(loss, range(3))(*args)])

    attend = functools.partial(block_attention.causal_attention,
                               window=window, rows=pallas_mla.BLOCK)
    kernels = results(attend)
    exact = results(functools.partial(full, window=window))
    monkeypatch.setattr(block_attention, "takes_kernels", lambda q, v: False)
    tiles = results(attend)
    for mine, theirs, want in zip(kernels, tiles, exact):
        assert np.all(np.isfinite(mine))
        if kind == jnp.float32:
            np.testing.assert_allclose(mine, want, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)
        else:
            off = lambda x: float(np.sqrt(np.mean((x - want) ** 2)))
            assert off(mine) <= 1.1 * off(theirs), (off(mine), off(theirs))
            assert off(mine) <= 1e-2 * float(np.sqrt(np.mean(want ** 2)))


@pytest.mark.parametrize("window", [None, 1, 5, 16, 20, 48, 100])
@pytest.mark.parametrize("group", [1, 4])
def test_unrolled_tiles_match_the_full_matrix(group, window):
    """The oracle itself at a toy shape: tiles of 16 rows, windows under, at
    and over a tile and over the sequence."""
    from homebrewnlp_tpu.ops.block_attention import causal_attention
    (q, k, v), full = _attention_inputs(48, jnp.float32, group, b=2, d=12)
    got = causal_attention(q, k, v, rows=16, window=window)
    np.testing.assert_allclose(got, full(q, k, v, window), **LAYER)


def test_a_cell_walks_the_band_and_nothing_before_it():
    """Which key tiles a block of rows meets: with blocks of 512 and a window
    of 1,024 the two before the diagonal, the farther one masked at the
    band's far edge; the cell's 16 blocks walk 45 of the triangle's 136."""
    from homebrewnlp_tpu.ops.pallas_mla import _band
    band = lambda m, w: tuple(int(x) for x in _band(jnp.int32(m), 512, w))
    assert band(5, None) == (0, 0)
    assert [band(m, 1024) for m in (0, 1, 2, 3, 15)] == [
        (0, 0), (0, 0), (0, 1), (1, 2), (13, 14)]
    assert sum(m - band(m, 1024)[0] + 1 for m in range(16)) == 45
    assert sum(m + 1 for m in range(16)) == 136
    # a window inside a block: the diagonal and, for its first rows, one more
    assert band(5, 200) == (4, 5) and band(5, 1) == (5, 5)
    # between blocks, both tiles before the diagonal cross the far edge
    assert band(5, 700) == (3, 5) and band(5, 512) == (4, 5)
    for m, w in ((5, 1024), (7, 700), (3, 200), (9, 512), (4, 1), (6, 4096)):
        first, clear = band(m, w)
        rows = np.arange(m * 512, (m + 1) * 512)[:, None]
        for j in range(m):
            keys = np.arange(j * 512, (j + 1) * 512)[None]
            seen = rows - keys < w
            assert seen.any() == (j >= first), (m, w, j)
            assert seen.all() == (j >= clear), (m, w, j)


def test_the_shape_alone_chooses_the_attention_kernels():
    """Whole blocks at head widths of whole or half lane tiles take the
    kernel pair, whatever the group and the window; every other shape keeps
    the unrolled tiles, whatever the group and the window."""
    from homebrewnlp_tpu.ops.block_attention import causal_attention
    from homebrewnlp_tpu.ops.pallas_mla import BLOCK
    for s, d, kernels in ((BLOCK, 128, 2), (48, 12, 0), (BLOCK + 8, 128, 0),
                          (BLOCK, 72, 0)):
        for group in (1, 8):
            for window in (None, 100, 1024):
                (q, k, v), _ = _attention_inputs(s, jnp.float32, group, kv=1,
                                                 d=d)
                text = str(jax.make_jaxpr(jax.grad(
                    lambda *a: jnp.sum(causal_attention(*a, window=window)),
                    range(3)))(q, k, v))
                assert text.count("pallas_call") == kernels, (s, d, group,
                                                              window)


# -- (d) the experts' share and the chunk -------------------------------------

def _expert_case(seed=8):
    raw = toy()
    whole = ref.Sizes.from_config(dict(raw, experts_held=32))
    params = part_params(whole, "routed_moe", seed, "routed_moe_/")
    return raw, whole, params, stream(seed + 1)


def _share(params, first, held):
    """The weights a chip holding experts `first .. first + held` has."""
    return {k: v[first:first + held] if k.startswith(
        "routed_moe_/orthogonal_var") else v for k, v in params.items()}


def test_all_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts of all four shares (8 of 32 experts
    each, as 16 of 64 in the cell; no shared expert to count once) are the
    reference's uncut expert layer."""
    raw, whole, params, u = _expert_case()
    fault = {k: jnp.float32(v) for k, v in ref.SOUND.items()}
    uncut = ref._experts(params, u, whole, fault)
    total, pairs = 0.0, 0
    for rank in range(4):
        cfg = Config(dict(raw, expert_offset=8 * rank))
        got, ctx = run_layer(cfg, MOE, _share(params, 8 * rank, 8), u)
        total = total + got
        pairs += int(jnp.sum(ctx.expert_load[0]))
        assert ctx.expert_load[0].shape == (8,)
        part = ref._experts(_share(params, 8 * rank, 8), u,
                            whole._replace(held=8, offset=8 * rank), fault)
        np.testing.assert_allclose(got, part, **LAYER)
    assert pairs == 96 * 8          # every selected pair fell on one share
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=3e-5)


def test_expert_layer_gradients_match_the_reference():
    raw, whole, params, u = _expert_case(12)
    held = _share(params, 0, 8)
    cfg = Config(raw)
    fault = {k: jnp.float32(v) for k, v in ref.SOUND.items()}

    def mine(p, x):
        return jnp.sum(jnp.sin(run_layer(cfg, MOE, p, x)[0]))

    def theirs(p, x):
        return jnp.sum(jnp.sin(ref._experts(p, x, whole._replace(held=8),
                                            fault)))

    got, want = (jax.grad(f, (0, 1))(held, u) for f in (mine, theirs))
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-5)
    for k in want[0]:
        np.testing.assert_allclose(got[0][k], want[0][k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


def test_the_chunk_follows_the_share():
    """One rule for both cells: 8 of 256 experts under top-8 keep the chunk
    of 16,384 rows they had (four times their balanced load); 16 of 64 get
    all 131,072 pairs, which their balanced load of 32,768 fills by a
    quarter, so their loop takes one trip whatever the routing."""
    from homebrewnlp_tpu.models.hybrid import expert_chunk
    assert expert_chunk(16384, 8, 8, 256) == 16384
    assert expert_chunk(16384, 8, 16, 64) == 16384 * 8
    for tokens, topk, held, experts in ((16384, 8, 8, 256),
                                        (16384, 8, 16, 64), (96, 8, 8, 32),
                                        (4096, 2, 1, 8), (512, 8, 64, 64)):
        chunk = expert_chunk(tokens, topk, held, experts)
        balanced = tokens * topk * held / experts
        assert chunk <= tokens * topk
        # a balanced load fills at most half of it, if it is not all pairs
        assert chunk >= min(2 * balanced, tokens * topk)


# -- (e) scopes ---------------------------------------------------------------

def test_step_scope_gives_the_new_scopes_their_layer_and_pass():
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
            assert layer in ("gqa", "routed_moe", "norm", "skip"), name
    for layer in ("gqa", "routed_moe", "norm"):
        assert {"forward", "remat", "backward"} <= seen[layer], (layer, seen)
    under_gqa = {part for n in names if "/gqa_/" in n
                 for part in n.split("/gqa_/")[1].split("/")[:1]}
    assert {"proj", "rotary", "attention", "out"} <= under_gqa, under_gqa
    # the kernels (the toy width keeps the tiles), named as the v5e compile
    # of the cell's gradient names them
    step, under = "jit(step_fn)/", "/block_/gqa_/attention/jit(_mla_attention_"
    back = step + "transpose(jvp(gpt))/body/jvp(gpt)/body/checkpoint/"
    for name, pass_ in (
            (step + "jvp(gpt)/body/gpt/body/d0_0" + under
             + "fwd)/pallas_call", "forward"),
            (back + "rematted_computation/gpt/body/d3_1" + under
             + "fwd)/pallas_call", "remat"),
            (back + "gpt/body/d3_1" + under + "bwd)/pallas_call",
             "backward")):
        assert P.step_scope(name) == (pass_, name.split("/body/")[-1][:4],
                                      "gqa"), name
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
        assert P.step_scope(name % "d3_2") == (pass_, "d3_2",
                                               "routed_moe"), name


# -- (f) the two configuration files ------------------------------------------

def _files():
    with open(os.path.join(REPO, "configs", "mellum2_12b.json")) as f:
        published = json.load(f)
    with open(os.path.join(BENCH, "configs", "mellum2_12b.json")) as f:
        cut = json.load(f)
    return published, cut, cut.pop("benchmark")


def test_the_cut_differs_from_the_published_file_in_the_reduced_keys_only():
    published, cut, meta = _files()
    changed = sorted(k for k in set(cut) | set(published)
                     if cut.get(k) != published.get(k))
    assert changed == sorted(meta["reduced"])
    assert meta["published"] == {k: published[k] for k in meta["reduced"]}
    widths = ("_dim", "_rank", "_size", "heads", "features", "per_tok",
              "rope_parameters", "sliding_window", "multiplier")
    assert not [k for k in changed if k not in ("vocab_size", "tpu_size")
                and any(w in k for w in widths)]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "mellum2_12b")
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == meta["source"]
    assert {"qk_norm", "mtp_head", "intermediate_size", "sequence_length",
            "train_batch_size", "optimizer"} <= set(meta["assumed"])


def test_the_cut_keeps_the_guides_floors_and_every_published_width():
    published, cut, meta = _files()
    assert cut["experts_held"] == 16 >= 8 and cut["experts"] == 64
    assert cut["vocab_size"] * 8 >= published["vocab_size"] == 98304
    # one whole period: three sliding layers, then a full one
    kinds = [[cut["block_config"][c]["layer"][-1].split("-")[:2] for c in row]
             for row in cut["block_schedule"]]
    assert [k[0] for k in kinds] == [["gqa", t] for t in cut["layer_types"]]
    assert cut["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"] == published["layer_types"][:4]
    assert all(k[1][0] == "routed_moe" for k in kinds)
    assert cut["mlp_layer_types"] == ["sparse"] * 4
    cfg = Config(dict(cut))
    assert (cfg.heads * cfg.features_per_head, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window,
            cfg.moe_intermediate_size, cfg.experts) == (
                2304, 32, 4, 128, 1024, 896, 64)
    assert cfg.hidden_size == 2304 and cfg.rms_norm_eps == 1e-6
    assert cfg.rope_parameters == {"full_attention": YARN,
                                   "sliding_attention": DEFAULT}
    assert "topk8-gated" in cut["block_config"][2]["layer"][-1]
    assert cfg.num_experts == 64 and cfg.num_experts_per_tok == 8
    sz = ref.Sizes.from_config(cut)
    count = sum(int(np.prod(s)) for s in ref.shapes(sz).values())
    assert 594e6 < count < 597e6, count
    # the published file is the same model, whole: 28 layers, 3 : 1
    whole = Config(dict(published))
    assert whole.depth == 28 and whole.experts_held == 64
    assert [r[0] for r in whole.block_schedule] == [
        0 if t == "sliding_attention" else 1 for t in published["layer_types"]]
    assert published["layer_types"] == (["sliding_attention"] * 3
                                        + ["full_attention"]) * 7


def test_the_yardstick_counts_the_band_and_the_triangle():
    """benchmark/flops_mellum2.py against a count by hand at the cell's
    sizes, and a sliding layer's attention against a full one's."""
    _, cut, _ = _files()
    s, d, h = 8192, 128, 32
    band = sum(min(i + 1, 1024) for i in range(s))
    assert flops.visible_pairs(s, 1024) == band
    assert flops.visible_pairs(s, None) == s * (s + 1) // 2
    assert flops.visible_pairs(48, 100) == 48 * 49 // 2
    part = flops.part_macs_per_token(cut)
    assert part["sliding_attention"] - part["full_attention"] == (
        2 * h * d * (band - s * (s + 1) // 2) / s)
    proj = 2304 * (32 + 4 + 4) * 128 + 32 * 128 * 2304
    assert part["full_attention"] == proj + 2 * h * d * (s + 1) / 2
    assert part["routed_moe"] == 2304 * 64 + 8 * 16 / 64 * 3 * 2304 * 896
    step = flops.train_step_flops(cut)
    assert 24e12 < step < 25e12, step
    work = flops.attention(cut, "sliding_attention")
    full = flops.attention(cut, "full_attention")
    assert set(work) == set(full) == {"forward", "backward"}
    assert 0.2 < work["forward"]["flops"] / full["forward"]["flops"] < 0.25
    # k and v cross once a K/V head: q, o of 32 heads, k, v of 4, the row
    # statistic, at two sequences
    assert full["forward"]["bytes"] == 2 * s * (
        2 * 32 * 128 * 2 + 2 * 4 * 128 * 2 + 32 * 4)
    assert full["backward"]["flops"] == 2 * full["forward"]["flops"]
    assert len(flops.attention_passes(cut)) == 2 * 4


def test_serving_this_attention_is_turned_away_with_a_reason(caplog):
    from homebrewnlp_tpu.infer.kv_cache import cache_eligible
    with caplog.at_level("INFO"):
        assert not cache_eligible(Config(toy()))
    assert "ring of sliding_window positions" in caplog.text
    assert "rotated at its own offset" in caplog.text


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
