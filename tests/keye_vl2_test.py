"""Keye-VL-2.0's block parts (models/hybrid.py::gqa spelt
`gqa-full_attention-qknorm-sparse`, ops/sparse_attention.py, the routed
experts at a share of an eighth) against the plain reference
(benchmark/reference/keye_vl2.py), at toy sizes on the CPU in float32.
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
from homebrewnlp_tpu.ops import sparse_attention as sa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
MOE = "routed_moe-topk8-gated-in:silu"
GQA = "gqa-full_attention-qknorm-sparse"
SA = {"indexer_head_dim": 8, "indexer_num_heads": 2,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 16, "q_chunk_size": 16,
      "topk": 20}


def toy(**over):
    """The benchmark's configuration at a toy width: two layers, the same
    block parts and schedule, 64 tokens in tiles of 16, a row keeping 20 of
    its earlier keys (so rows from 20 on select)."""
    raw = dict(
        model_mode="gpt", sequence_length=64, heads=4, features_per_head=8,
        vocab_size=128, depth=2, train_batch_size=2, calc_accuracy=False,
        memory_reduction_strategy="checkpoint", weight_decay=0.0001,
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
        learning_rate=0.01, z_loss=1e-4, embedding_stddev=0.02,
        intermediate_feed_forward_multiplier=4.0, factorized_embedding=False,
        scale_by_depth=False, weight_centralisation=False,
        weight_standardisation=False, experts=32, experts_held=8,
        expert_offset=0, moe_intermediate_size=16, moe_balance_weight=1.0,
        rms_norm_eps=1e-6, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, rope_theta=100.0,
        rope_scaling={"mrope_section": [1, 2, 1], "rope_type": "default",
                      "type": "default"},
        sa_config=dict(SA),
        tpu_size=1, calculation_dtype="float32", slice_dtype="float32",
        storage_dtype="float32", optimizer_slice_dtype="bfloat16",
        block_config=[
            {"layer": ["rms_norm-scale", GQA], "skip": True},
            {"layer": ["rms_norm-scale", MOE], "skip": True}],
        block_schedule=[[0, 1], [0, 1]],
        output_block_config=[{"layer": ["rms_norm-scale"]}],
        learning_rate_config={"linear_warmup": {"final_step": 64}})
    raw.update(over)
    return raw

NAMES = ("batch", "sequence", "heads", "features_per_head")
TOKENS = ("batch", "sequence", "language_token_patch")
LAYER = dict(rtol=1e-5, atol=1e-5)
# the toy cell's limits: the benchmark's comparison, at the toy width
TOY_LIMITS = {"loss3": 1e-5, "sm3_median": 4e-3, "change_median": 1e-3,
              "change_leaf": 4e-3}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(os.path.join(BENCH, "reference", "keye_vl2.py"), "keye_vl2_ref")
compare = _load(os.path.join(BENCH, "compare.py"), "compare")
flops = _load(os.path.join(BENCH, "flops_keye_vl2.py"), "flops_keye_vl2")
SOUND = {k: jnp.float32(v) for k, v in ref.SOUND.items()}


def run_layer(cfg, spec, params, x):
    """One layer of the DSL on `x`, its parameters keyed as under its own
    scope.  Returns (output array, ctx)."""
    name, *extras = spec.split("-")
    ctx = Ctx(cfg, params=params, train=True)
    out = ctx.scoped(name + "_", LAYER_FUNCTIONS[name],
                     Args(ctx, NT(x, NAMES), extras))
    return out.transpose_to(NAMES).x, ctx


def part_params(sz, kind, seed, prefix):
    """Seeded weights of one block part, by the reference's rules."""
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(mean, std, shape), jnp.float32)
            for k, (shape, (mean, std)) in ref._part_leaves(sz, kind).items()
            if k.startswith(prefix)}


def stream(seed):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(2, 64, 4, 8)),
                       jnp.float32)


def attention_case(seed, **over):
    """(cfg, sz, params, u) of the attention part at the toy width; `over`
    changes the program's configuration alone."""
    sz = ref.Sizes.from_config(toy())
    return (Config(toy(**over)), sz, part_params(sz, "gqa", seed, "gqa_/"),
            stream(seed + 100))


# -- (a) the whole update -----------------------------------------------------

@pytest.fixture(scope="module")
def followed():
    """Three updates of the toy model through `Trainer.step`, driven and read
    as the benchmark's runner does, and the reference's three (over the
    whole batch at once: the balance term is not a mean over rows)."""
    sys.path[:0] = [BENCH]
    runner = _load(os.path.join(BENCH, "runners", "train_step.py"),
                   "train_step")
    with open(os.path.join(BENCH, "traffic", "train.json")) as f:
        traffic = json.load(f)

    class Spans:
        @contextlib.contextmanager
        def span(self, name):
            yield

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
    assert all(g > 0 for g in want["grad_leaf"])        # every leaf moved


def test_the_runner_sees_the_parameters_the_reference_names(followed):
    _, want, program = followed
    assert sorted(ref.shapes(program.sizes)) == want["names"]
    # table, head, final norm; 2 attention parts of 11, 2 expert parts of 5
    assert len(want["names"]) == 3 + 2 * 11 + 2 * 5


def test_step_reports_the_kept_pairs_and_the_indexer_loss(followed):
    program = followed[2]
    program.state, metrics = program.trainer.step(
        program.state, program.ring[0], jax.random.key(0))
    kept = flops.kept_pairs(toy()) / (64 * 65 / 2)
    for layer in range(2):
        assert float(metrics[f"dsa_kept_pairs/{layer}"]) == pytest.approx(
            kept, rel=1e-6)
        assert 0 < float(metrics[f"dsa_indexer_kl/{layer}"]) < 10


@pytest.mark.parametrize("case", sorted(ref.LOWER))
def test_every_planted_fault_is_caught_by_the_toy_limits(followed, case):
    """Each case of `LOWER` in the program's place fails the toy cell's
    limits, which the program passes."""
    got, want, program = followed
    assert compare.correct(compare.against(compare.readings(got, want),
                                           TOY_LIMITS))
    fault = ref.follow(program.sizes, program.seed, program.host_batches, 3,
                       2, lower=case)
    assert not compare.correct(compare.against(
        compare.readings(fault, want), TOY_LIMITS)), case


@pytest.mark.parametrize("own_selection", [False, True])
def test_logits_match_the_reference(own_selection, monkeypatch):
    """The model's logits against the reference's, the reference choosing
    its own kept sets or running on the program's (as a comparison at the
    cell's size would, where a score that rounds differently at the
    `topk`-th could otherwise hide an error in the attention)."""
    from homebrewnlp_tpu.models import build
    # no checkpoint around the parts, so the kept sets leave them as values
    raw = toy(memory_reduction_strategy="none")
    cfg, sz = Config(raw), ref.Sizes.from_config(raw)
    params = ref.init_weights(sz, 7)
    x = jnp.asarray(np.random.default_rng(8).integers(32, 123, (2, 64)),
                    jnp.int32)
    masks = []
    real = sa.select

    def spy(*args, **kw):
        out = real(*args, **kw)
        masks.append(out[0] != 0)
        return out

    monkeypatch.setattr(sa, "select", spy)
    ctx = Ctx(cfg, params=params, train=True, rng=jax.random.key(0))
    out = build(ctx, {"token_x": NT(x[..., None], TOKENS),
                      "token_y": NT(jnp.roll(x, -1, 1)[..., None], TOKENS)})
    assert len(masks) == 2
    with jax.default_matmul_precision("highest"):
        u, _, _ = ref.forward(params, x, sz, SOUND,
                              chosen=masks if own_selection else None)
        want = u @ params[ref._HEAD][:, :, 0].reshape(u.shape[-1], -1)
    got = out.token_out.x.reshape(want.shape)
    np.testing.assert_allclose(got, want, **LAYER)


# -- (b) the attention layer alone --------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_selection_is_the_references_index_for_index(seed):
    cfg, sz, params, u = attention_case(seed)
    got, ctx = run_layer(cfg, GQA, params, u)
    with jax.default_matmul_precision("highest"):
        want, kl, keep = ref._attention(params, u, sz, SOUND, rows=16,
                                        keep_sets=True)
    kept = float(jnp.sum(keep)) / (2 * 64 * 65 / 2)
    assert float(ctx.dsa_kept[0]) == pytest.approx(kept, rel=1e-6)
    np.testing.assert_allclose(got, want, **LAYER)
    np.testing.assert_allclose(float(ctx.dsa_kl[0]), float(kl), rtol=1e-5)


@pytest.mark.parametrize("seed", [4, 5])
def test_program_mask_equals_the_reference_kept_sets(seed, monkeypatch):
    """The mask `select` hands the attention is the reference's scattered
    top-k, position for position, in every row."""
    cfg, sz, params, u = attention_case(seed)
    seen = {}
    real = sa.select

    def spy(*args, **kw):
        out = real(*args, **kw)
        seen["mask"] = out[0]
        return out

    monkeypatch.setattr(sa, "select", spy)
    run_layer(cfg, GQA, params, u)
    with jax.default_matmul_precision("highest"):
        _, _, keep = ref._attention(params, u, sz, SOUND, rows=16,
                                    keep_sets=True)
    np.testing.assert_array_equal(np.asarray(seen["mask"]) != 0,
                                  np.asarray(keep))


def test_layer_gradients_match_and_the_indexer_learns_from_its_loss_alone():
    cfg, sz, params, u = attention_case(6)
    cot = stream(7)

    def program(p, x, with_output, with_loss):
        out, ctx = run_layer(cfg, GQA, p, x)
        return (with_output * jnp.sum(out * cot)
                + with_loss * ctx.aux_losses[0] * 2)

    def reference(p, x, with_output, with_loss):
        y, kl = ref._attention(p, x, sz, SOUND, rows=16)
        return with_output * jnp.sum(y * cot) + with_loss * kl

    with jax.default_matmul_precision("highest"):
        for mix in ((1.0, 1.0), (1.0, 0.0), (0.0, 1.0)):
            got = jax.grad(program, (0, 1))(params, u, *mix)
            want = jax.grad(reference, (0, 1))(params, u, *mix)
            for name in params:
                np.testing.assert_allclose(got[0][name], want[0][name],
                                           rtol=2e-4, atol=2e-5,
                                           err_msg=f"{name} {mix}")
                indexer = name.startswith("gqa_/indexer/")
                if mix == (1.0, 0.0) and indexer:
                    assert not np.any(np.asarray(got[0][name])), name
                if mix == (0.0, 1.0) and not indexer:
                    assert not np.any(np.asarray(got[0][name])), name
            np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-5)


def test_a_topk_past_the_sequence_is_the_dense_layer():
    """With `topk` >= the sequence every row keeps every earlier key: the
    sparse layer's output is the dense `gqa-...-qknorm` layer's."""
    cfg, _, params, u = attention_case(9, sa_config=dict(SA, topk=64))
    got, ctx = run_layer(cfg, GQA, params, u)
    dense_cfg = Config(toy(sa_config=None))
    dense = {k: v for k, v in params.items()
             if not k.startswith("gqa_/indexer/")}
    want, _ = run_layer(dense_cfg, "gqa-full_attention-qknorm", dense, u)
    np.testing.assert_allclose(got, want, **LAYER)
    assert float(ctx.dsa_kept[0]) == pytest.approx(1.0)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips of 4 experts each: their parts add up to the layer that
    holds all 32 (no shared expert, so nothing is counted twice)."""
    raw = toy(experts_held=32)
    sz = ref.Sizes.from_config(raw)
    params = part_params(sz, "routed_moe", 11, "routed_moe_/")
    u = stream(12)
    whole, _ = run_layer(Config(raw), MOE, params, u)
    total = 0.0
    for share in range(8):
        mine = dict(params)
        for i in ("", 1, 2):
            key = f"routed_moe_/orthogonal_var{i}/orthogonal_var"
            mine[key] = params[key][4 * share:4 * share + 4]
        part, _ = run_layer(Config(toy(experts_held=4,
                                       expert_offset=4 * share)),
                            MOE, mine, u)
        total = total + part
    np.testing.assert_allclose(total, whole, **LAYER)


def test_qk_norm_norms_each_head_over_its_width():
    cfg, sz, params, u = attention_case(13)
    got, _ = run_layer(cfg, GQA, params, u)
    flat = {k: v for k, v in params.items()}
    flat["gqa_/proj/q_norm"] = flat["gqa_/proj/q_norm"] * 3.0
    scaled, _ = run_layer(cfg, GQA, flat, u)
    # a weight three times larger scales every query, so the layer moves
    assert not np.allclose(got, scaled, atol=1e-3)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._attention(flat, u, sz, SOUND, rows=16)
    np.testing.assert_allclose(scaled, want, **LAYER)


@pytest.mark.parametrize("sections,ok", [([16, 24, 24], True),
                                         ([16, 24, 16], False),
                                         ([32, 24, 24], False)])
def test_mrope_sections_must_cover_the_head(sections, ok):
    from homebrewnlp_tpu.ops import rotary
    entry = {"rope_type": "default", "type": "default",
             "rope_theta": 1e7, "mrope_section": sections}
    if not ok:
        with pytest.raises(ValueError, match="mrope_section"):
            rotary.inverse_frequencies(entry, 128)
        return
    got, factor = rotary.inverse_frequencies(entry, 128)
    want, _ = rotary.inverse_frequencies({"rope_theta": 1e7}, 128)
    np.testing.assert_array_equal(got, want)
    assert factor == 1.0


def test_config_takes_the_rotary_entry_from_rope_scaling():
    cfg = Config(toy())
    assert cfg.rope_parameters == {"full_attention": {
        "mrope_section": [1, 2, 1], "rope_type": "default",
        "type": "default", "rope_theta": 100.0}}


@pytest.mark.parametrize("spelling,over,match", [
    (GQA, {"sa_config": None}, "sa_config"),
    ("gqa-full_attention-qknorm", {}, "sa_config"),
    ("gqa-sliding_attention-qknorm-sparse", {"sliding_window": 16,
                                             "rope_scaling": None,
                                             "rope_parameters": {
                                                 "sliding_attention": {
                                                     "rope_theta": 100.0}}},
     "window"),
])
def test_the_spelling_and_sa_config_must_agree(spelling, over, match):
    cfg, _, params, u = attention_case(14, **over)
    with pytest.raises(ValueError, match=match):
        run_layer(cfg, spelling, params, u)


@pytest.mark.parametrize("over", [
    {"memory_reduction_strategy": "revnet"},
    {"memory_reduction_strategy": "momentum"},
    {"pipeline_parallel": 2},
    {"pipeline_parallel": 2, "pipeline_schedule": "1f1b"},
])
def test_a_body_that_drops_the_indexer_loss_is_refused(over):
    """The indexer's KL loss is its only gradient and rides
    `ctx.aux_losses`: a reversible chain or a pipelined body would drop it,
    so the configuration is refused at build (the balance loss is off here,
    so its own refusal does not answer first)."""
    with pytest.raises(ValueError, match="indexer's KL loss"):
        Config(toy(moe_balance_weight=0.0, **over))


# -- (c) the kernels against a dense masked matrix -----------------------------

def _dense(q, k, v, keep):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, 1) for x in (k, v))
    s = jnp.where(keep[:, None], jnp.einsum("bhtd,bhsd->bhts", q, k),
                  -jnp.inf)
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), v), s


KERNEL_CASES = [(4, 2, 64, 16, 20), (4, 4, 64, 16, 20), (8, 2, 96, 32, 40),
                (4, 2, 64, 64, 20), (4, 2, 64, 16, 64)]


@pytest.mark.parametrize("h,g,t,block,topk", KERNEL_CASES)
def test_kernels_match_a_dense_masked_matrix(h, g, t, block, topk):
    """`select`, `attention` forward and backward and `indexer_kl` with its
    gradient, interpreted, against dense float32 `jax.numpy` over the same
    masked matrix: several tiles a row, grouped K/V heads, one tile, and a
    `topk` past the sequence."""
    rng = np.random.default_rng(h * t + block + topk)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    b, d, ni, di = 2, 16, 2, 8
    q, k, v = f(b, h, t, d) * 0.5, f(b, g, t, d), f(b, g, t, d)
    qi, ki, w = f(b, ni, t, di), f(b, t, di), f(b, t, ni)
    with jax.default_matmul_precision("highest"):
        mask, lse_i, kept = sa.select(qi, ki, w, topk, block, True)
        scores = jnp.einsum("btn,bnts->bts", w, jax.nn.relu(
            jnp.einsum("bnte,bse->bnts", qi, ki)))
        causal = jnp.tril(jnp.ones((t, t), bool))
        _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                              min(topk, t))
        keep = jnp.zeros((b, t, t), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
            at].set(True) & causal
        np.testing.assert_array_equal(np.asarray(mask) != 0, np.asarray(keep))
        assert int(kept) == int(jnp.sum(keep))
        o, lse = sa.attention(q, k, v, mask, block, True)
        want, logits = _dense(q, k, v, keep)
        np.testing.assert_allclose(o, want, **LAYER)
        np.testing.assert_allclose(
            jnp.swapaxes(lse, 2, 3).reshape(b, h, t),
            jax.nn.logsumexp(logits, -1), **LAYER)
        cot = f(b, h, t, d)
        got = jax.grad(lambda *a: jnp.sum(
            sa.attention(*a, mask, block, True)[0] * cot), (0, 1, 2))(q, k, v)
        exp = jax.grad(lambda *a: jnp.sum(_dense(*a, keep)[0] * cot),
                       (0, 1, 2))(q, k, v)
        for x, y in zip(got, exp):
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-4)

        def dense_kl(qi, ki, w):
            scores = jnp.einsum("btn,bnts->bts", w, jax.nn.relu(
                jnp.einsum("bnte,bse->bnts", qi, ki)))
            p = jnp.mean(jax.nn.softmax(logits, -1), 1)
            log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.mean(jnp.sum(jnp.where(keep & (p > 0), p * (jnp.log(
                jnp.where(p > 0, p, 1.0)) - log_q), 0.0), -1))

        kl = lambda *a: sa.indexer_kl(q, k, lse, *a, mask, lse_i, block, True)
        np.testing.assert_allclose(kl(qi, ki, w), dense_kl(qi, ki, w),
                                   rtol=1e-5)
        got = jax.grad(lambda *a: 3 * kl(*a), (0, 1, 2))(qi, ki, w)
        exp = jax.grad(lambda *a: 3 * dense_kl(*a), (0, 1, 2))(qi, ki, w)
        for x, y in zip(got, exp):
            np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


@contextlib.contextmanager
def plain_checkpoint():
    """`jax.checkpoint` with any policy dropped: every part recomputes its
    whole forward in the backward, as it did before the sparse attention
    named what to keep."""
    real = jax.checkpoint
    jax.checkpoint = lambda f, policy=None, **kw: real(f, **kw)
    try:
        yield
    finally:
        jax.checkpoint = real


def _token_batch(x):
    return {"token_x": NT(x[..., None], TOKENS),
            "token_y": NT(jnp.roll(x, -1, 1)[..., None], TOKENS)}


def _loss(cfg, x):
    from homebrewnlp_tpu.models import build

    def loss(p):
        ctx = Ctx(cfg, params=p, train=True, rng=jax.random.key(0))
        return build(ctx, _token_batch(x)).loss
    return loss


def _pallas_calls(jaxpr, counts):
    """Kernel function name -> `pallas_call`s in `jaxpr` and every jaxpr
    inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["jaxpr"].debug_info.func_name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, counts)
    return counts


@pytest.fixture(scope="module")
def kept_and_plain():
    """The toy model's gradient under the per-part checkpoint as it is and
    under plain `jax.checkpoint(f)`: for each, the kernels its jaxpr holds
    and the parameters' gradients (interpreted kernels)."""
    import collections
    raw = toy()
    cfg, sz = Config(raw), ref.Sizes.from_config(raw)
    params = ref.init_weights(sz, 7)
    x = jnp.asarray(np.random.default_rng(8).integers(32, 123, (2, 64)),
                    jnp.int32)
    out = {}
    for case in ("kept", "plain"):
        with plain_checkpoint() if case == "plain" else contextlib.nullcontext():
            grad = jax.grad(_loss(cfg, x))     # a new function: no trace kept
            jaxpr = jax.make_jaxpr(grad)(params).jaxpr
            out[case] = (_pallas_calls(jaxpr, collections.Counter()),
                         jax.jit(grad)(params))
    return out


@pytest.mark.parametrize("kernel", ["_select_kernel", "_fwd_kernel",
                                    "_kl_kernel"])
def test_the_checkpoint_keeps_the_sparse_forward_kernels_outputs(
        kernel, kept_and_plain):
    """Each of the three forward kernels runs once a sparse layer in the
    gradient (plain `jax.checkpoint` runs it again in the remat pass: twice),
    the backward's two kernels once either way, and the parameters'
    gradients are plain's bit for bit: the kept values are the arrays the
    remat would recompute."""
    (kept, got), (plain, want) = kept_and_plain["kept"], kept_and_plain["plain"]
    layers = 2
    assert kept[kernel] == layers and plain[kernel] == 2 * layers, (kept,
                                                                    plain)
    for backward in ("_dq_kernel", "_dkv_kernel"):
        assert kept[backward] == plain[backward] == layers
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def _gradient_stablehlo(raw):
    """StableHLO of the gradient of the toy's loss, locations stripped,
    the parameters given as shapes."""
    cfg = Config(raw)
    x = jnp.zeros((cfg.train_batch_size, cfg.sequence_length), jnp.int32)

    def collect():
        from homebrewnlp_tpu.models import build
        ctx = Ctx(cfg, params=None, seed=0, train=False)
        build(ctx, _token_batch(x))
        return ctx.collected

    params = jax.eval_shape(collect)
    return jax.jit(jax.grad(_loss(cfg, x))).lower(params).as_text()


@pytest.mark.parametrize("module", ["mellum2_test", "solar_open2_test",
                                    "kimi_linear_test"])
def test_parts_without_a_sparse_layer_lower_as_under_plain_checkpoint(module):
    """The toy of each cell that runs the per-part checkpoint without a
    sparse attention: its gradient lowers to the same StableHLO under the
    policy as under plain `jax.checkpoint(f)`, since none of its layers
    names a value to keep."""
    import importlib
    raw = importlib.import_module(f"tests.{module}").toy()
    kept = _gradient_stablehlo(raw)
    with plain_checkpoint():
        plain = _gradient_stablehlo(raw)
    assert kept == plain


@pytest.mark.parametrize("seed", [0, 1])
def test_selection_breaks_ties_as_top_k(seed):
    """Scores of small whole numbers tie everywhere: the kernel's bisection
    keeps `jax.lax.top_k`'s set (the lower positions of a tie at the
    `topk`-th score) and its log-sum-exp."""
    rng = np.random.default_rng(seed)
    b, ni, t, di, topk = 2, 2, 96, 4, 20
    qi = jnp.asarray(rng.integers(-1, 2, (b, ni, t, di)), jnp.float32)
    ki = jnp.asarray(rng.integers(-1, 2, (b, t, di)), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (b, t, ni)), jnp.float32)
    mask, lse, kept = sa.select(qi, ki, w, topk, 32, True)
    scores = jnp.einsum("btn,bnts->bts", w, jax.nn.relu(
        jnp.einsum("bnte,bse->bnts", qi, ki)))
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    keep = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None],
        at].set(True) & causal
    np.testing.assert_array_equal(np.asarray(mask) != 0, np.asarray(keep))
    assert int(kept) == int(jnp.sum(keep))
    np.testing.assert_allclose(lse[..., 0], jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), -1), **LAYER)


def test_kept_pairs_at_the_cell_are_a_quarter_of_the_triangle():
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b.json")) as f:
        model = json.load(f)
    assert flops.kept_pairs(model) == 1920 * 16384 + 1024
    assert flops.kept_pairs(model) / (16384 * 16385 / 2) == pytest.approx(
        0.234368, abs=1e-6)


def test_the_flops_count_every_product_at_the_cell():
    """The yardstick of `step_mfu.keye_vl2` and `dsa_indexer_roofline` by
    hand at the cell's sizes: q, k, v and the output; the kept pairs; the
    indexer's triangle; its projections forward and their weights' gradient
    (the input is detached: twice, not three times); the router and the held
    experts; the head."""
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b.json")) as f:
        model = json.load(f)
    d, s, layers = 2048, 16384, 6
    kept = flops.kept_pairs(model)
    gqa = d * (32 + 2 * 4) * 128 + 32 * 128 * d + 2 * 32 * 128 * kept / s
    triangle = 16 * 64 * (s + 1) / 2
    projections = d * (16 * 64 + 64 + 16)
    moe = d * 128 + 8 * 16 / 128 * 3 * d * 768
    forward = layers * (gqa + triangle + projections + moe) + d * 18992
    assert flops.train_step_flops(model) == pytest.approx(
        2 * s * (3 * forward - layers * projections), rel=1e-12)
    work = flops.indexer(model)
    assert work["flops"] == 2 * 16 * 64 * (s * (s + 1) // 2) + 4 * s * (
        projections)
    assert len(flops.indexer_passes(model)) == layers
