"""End-to-end model tests: forward, gradients, memory-reduction strategy
parity, shared-weight identity.  Covers what the reference never tested
(SURVEY.md §4: no train-step tests exist upstream)."""
import jax
import jax.numpy as jnp
import pytest

from homebrewnlp_tpu.models import build, init_params
from homebrewnlp_tpu.models.ctx import Ctx

from .backend import init_and_loss, mixer_config, text_batch, tiny_config


def test_forward_loss_reasonable():
    cfg = mixer_config()
    params, axes, batch, loss_fn = init_and_loss(cfg)
    loss = jax.jit(loss_fn)(params, jax.random.key(0))
    # z-loss regularized CE near ln(vocab) at init
    assert 2.0 < float(loss) < 6.0


@pytest.mark.parametrize("strategy", ["none", "checkpoint", "revnet", "momentum"])
def test_memory_strategies_train(strategy):
    cfg = mixer_config(memory_reduction_strategy=strategy)
    params, axes, batch, loss_fn = init_and_loss(cfg)
    g = jax.jit(jax.grad(loss_fn))(params, jax.random.key(0))
    for k, v in g.items():
        assert jnp.all(jnp.isfinite(v.astype(jnp.float32))), k
    total = sum(float(jnp.sum(jnp.abs(v.astype(jnp.float32)))) for v in g.values())
    assert total > 0


def test_revnet_grads_match_numeric():
    """Reversible custom_vjp backward (input reconstruction) must agree with
    a numeric directional derivative of the same loss."""
    cfg_rev = mixer_config(memory_reduction_strategy="revnet")
    p_rev, _, batch, loss_rev = init_and_loss(cfg_rev)
    g_rev = jax.jit(jax.grad(loss_rev))(p_rev, jax.random.key(0))
    key = jax.random.key(42)
    vec = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape, jnp.float32)
           for i, (k, v) in enumerate(sorted(p_rev.items()))}
    eps = 1e-3

    def lf(p):
        return loss_rev(p, jax.random.key(0))

    lp = float(jax.jit(lf)({k: v + eps * vec[k] for k, v in p_rev.items()}))
    lm = float(jax.jit(lf)({k: v - eps * vec[k] for k, v in p_rev.items()}))
    numeric = (lp - lm) / (2 * eps)
    analytic = sum(float(jnp.sum(g_rev[k].astype(jnp.float32) * vec[k]))
                   for k in vec)
    assert abs(numeric - analytic) < 5e-2 * max(1.0, abs(numeric)), \
        (numeric, analytic)


def test_shared_weights_identity():
    """'shared' DSL flag: depth iterations reuse one tensor per call slot."""
    cfg = mixer_config(depth=3)
    batch = text_batch(cfg)
    params, axes = init_params(cfg, batch)
    shared = [k for k in params if "/shared_" in k]
    # two shared attention bias maps (one per call slot in block config 1)
    assert len(shared) == 2, shared
    # no per-depth copies of the attention embedding exist
    assert not any("attention" in k and "@d" in k and "embed" in k for k in params)


def test_sgd_loss_decreases():
    cfg = mixer_config(depth=1)
    params, axes, batch, loss_fn = init_and_loss(cfg)

    @jax.jit
    def step(p, rng):
        l, g = jax.value_and_grad(loss_fn)(p, rng)
        return l, {k: v - 0.03 * g[k].astype(v.dtype) for k, v in p.items()}

    rng = jax.random.key(0)
    first = None
    loss = None
    for i in range(20):
        loss, params = step(params, jax.random.fold_in(rng, i))
        if first is None:
            first = float(loss)
    assert float(loss) < first, (first, float(loss))


def test_relative_embedding_finite_large_features():
    """Regression: the reference's relative-embedding formula overflows f32
    for feature counts > ~89 (exp of the raw flat feature index); our
    geometric-frequency form must stay finite at any width."""
    import numpy as np
    from homebrewnlp_tpu.models.ctx import Args
    from homebrewnlp_tpu.models.embedding import relative_embedding
    cfg = mixer_config(heads=8, features_per_head=64)  # 512 features
    ctx = Ctx(cfg, params={})
    args = Args(ctx, None, ["relative"])
    out = relative_embedding(
        args, [("sequence", 128)], [("heads", 8), ("features_per_head", 64)],
        [("sequence", 128), ("heads", 8), ("features_per_head", 64)])
    x = np.asarray(out.x, np.float32)
    assert np.isfinite(x).all()
    assert 0 < np.abs(x).max() <= cfg.embedding_stddev + 1e-6


def test_dtype_policy_bf16():
    """Device-resident params live in slice_dtype (MTF's per-device slice
    copy); storage_dtype only affects the checkpoint master (see
    test_checkpoint_master_dtype_roundtrip)."""
    cfg = mixer_config(calculation_dtype="bfloat16", storage_dtype="bfloat16",
                       slice_dtype="float32")
    params, axes, batch, loss_fn = init_and_loss(cfg)
    assert all(v.dtype == jnp.float32 for v in params.values())
    loss = jax.jit(loss_fn)(params, jax.random.key(0))
    assert jnp.isfinite(loss)
    assert loss.dtype == jnp.float32  # losses accumulate in f32

    cfg2 = mixer_config(calculation_dtype="bfloat16",
                        storage_dtype="bfloat16", slice_dtype="bfloat16")
    params2, _, _, loss_fn2 = init_and_loss(cfg2)
    assert all(v.dtype == jnp.bfloat16 for v in params2.values())
    assert jnp.isfinite(jax.jit(loss_fn2)(params2, jax.random.key(0)))


def test_einsum_f32_accumulation():
    """bf16 einsum must accumulate in f32 (preferred_element_type) and cast
    back — output dtype bf16, but dot_general runs with an f32 accumulator."""
    from homebrewnlp_tpu import nd
    from homebrewnlp_tpu.nd import NT

    a = NT(jnp.ones((4, 8), jnp.bfloat16), ("row", "inner"))
    b = NT(jnp.ones((8, 3), jnp.bfloat16), ("inner", "col"))

    out = nd.einsum([a, b], ("row", "col"))
    assert out.dtype == jnp.bfloat16  # storage stays half-precision

    jaxpr = jax.make_jaxpr(
        lambda x, y: nd.einsum([NT(x, a.names), NT(y, b.names)],
                               ("row", "col")).x)(a.x, b.x)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots, "einsum should lower to dot_general"
    for e in dots:
        assert e.params["preferred_element_type"] == jnp.float32

    # f32 inputs keep an f32 accumulator and f32 output
    af = NT(jnp.ones((4, 8), jnp.float32), ("row", "inner"))
    bf = NT(jnp.ones((8, 3), jnp.float32), ("inner", "col"))
    assert nd.einsum([af, bf], ("row", "col")).dtype == jnp.float32


def test_pallas_causal_map_attention_parity():
    """Interpret-mode parity of the (measured-and-rejected) pallas mixer
    kernel against the production masked einsum (docs/perf/README.md)."""
    import numpy as np

    from homebrewnlp_tpu.ops.pallas_attn import (_fwd_einsum, _fwd_pallas,
                                                 causal_map_attention)
    k1, k2 = jax.random.split(jax.random.key(0))
    bias = jax.random.normal(k1, (2, 256, 256), jnp.float32)
    val = jax.random.normal(k2, (2, 256, 2, 128), jnp.float32)
    a = np.asarray(_fwd_einsum(bias, val))
    b = np.asarray(_fwd_pallas(bias, val, interpret=True))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    # custom_vjp grads match autodiff through the einsum form
    def loss_k(bias, val):
        return jnp.sum(jnp.square(causal_map_attention(bias, val, False)))

    def loss_e(bias, val):
        return jnp.sum(jnp.square(_fwd_einsum(bias, val)))

    ga = jax.grad(loss_k, argnums=(0, 1))(bias, val)
    ge = jax.grad(loss_e, argnums=(0, 1))(bias, val)
    for x, y in zip(ga, ge):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_tri_map_attention_parity():
    """Interpret-mode parity of the (measured-and-rejected) large-S
    triangular map-attention kernels — fwd AND both backward kernels —
    against the masked einsum (docs/perf/README.md round 5c)."""
    import numpy as np

    from homebrewnlp_tpu.ops.pallas_tri_attn import (tri_map_attention,
                                                     tri_reference)
    k1, k2 = jax.random.split(jax.random.key(0))
    # S=512 -> 2 row tiles (the fori + diagonal paths both execute);
    # K=256 -> the key axis splits into 2 half-panels
    bias = jax.random.normal(k1, (2, 512, 512), jnp.float32) * 0.02
    val = jax.random.normal(k2, (2, 512, 2, 256), jnp.float32)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(tri_reference(bias, val))
        b = np.asarray(tri_map_attention(bias, val, True))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        gr = jax.grad(lambda t: jnp.sum(tri_reference(*t) ** 2))((bias, val))
        gf = jax.grad(
            lambda t: jnp.sum(tri_map_attention(*t, True) ** 2))((bias, val))
    for name, x, y in zip(("dbias", "dval"), gr, gf):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_blocked_causal_map_matches_masked_einsum():
    """models/layers.py::_blocked_map_rows: the block decomposition of the
    causal triangle must reproduce the masked einsum inside the REAL model
    (identical params — the embed scope walk is unchanged) and at the
    helper level for every depth, including depths past the 256-row leaf
    cutoff."""
    import numpy as np

    from homebrewnlp_tpu.models.layers import _blocked_map_rows
    k1, k2 = jax.random.split(jax.random.key(1))
    bias = jax.random.normal(k1, (2, 512, 512), jnp.float32) * 0.02
    val = jax.random.normal(k2, (2, 512, 2, 64), jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (512, 512), 1)
    ref = jnp.einsum("hst,bthk->bshk", bias * (row >= col), val,
                     preferred_element_type=jnp.float32)
    with jax.default_matmul_precision("highest"):
        for depth in (0, 1, 2, 5):
            out = _blocked_map_rows(bias, val, depth)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"depth {depth}")

    # model level: same params, same loss/grads
    dt = dict(calculation_dtype="float32", storage_dtype="float32",
              slice_dtype="float32", optimizer_slice_dtype="float32")
    shape = dict(sequence_length=512, features_per_head=64, heads=2,
                 depth=2, train_batch_size=2,
                 memory_reduction_strategy="none")
    cfg0 = mixer_config(**shape, **dt)
    cfg1 = mixer_config(**shape, **dt, blocked_causal_map=3)
    p0, _, _, l0 = init_and_loss(cfg0)
    p1, _, _, l1 = init_and_loss(cfg1)
    assert set(p0) == set(p1)
    with jax.default_matmul_precision("highest"):
        a = float(jax.jit(l0)(p0, jax.random.key(0)))
        b = float(jax.jit(l1)(p0, jax.random.key(0)))
        assert abs(a - b) < 1e-5 * max(1.0, abs(a)), (a, b)
        g0 = jax.jit(jax.grad(l0))(p0, jax.random.key(0))
        g1 = jax.jit(jax.grad(l1))(p0, jax.random.key(0))
    for k in g0:
        x = np.asarray(g0[k], np.float32)
        y = np.asarray(g1[k], np.float32)
        scale = max(1e-3, float(np.abs(x).max()))
        assert np.abs(x - y).max() < 1e-4 * scale, (
            k, float(np.abs(x - y).max()))


def test_blocked_causal_map_composes_with_sharding(eight_devices):
    """blocked_causal_map on a data x model mesh: the decomposition slices
    only the (unsharded) sequence axis, so GSPMD composition must hold."""
    import numpy as np

    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    cfg = mixer_config(sequence_length=512, features_per_head=64, heads=2,
                       depth=2, train_batch_size=8, tpu_size=8,
                       blocked_causal_map=3)
    mesh = make_mesh(cfg)
    assert mesh.size == 8
    trainer = Trainer(cfg, mesh)
    batch = text_batch(cfg)
    state = trainer.init(batch)
    state, m = trainer.step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_reversible_cotangent_dtype_is_noop_under_bf16():
    import numpy as np
    """Round-4 measured finding pinned as a test: under bf16 calculation
    dtype the inter-block cotangent streams are already bf16, so the
    reversible_cotangent_dtype barrier must be a numeric NO-OP (bit-identical
    grads).  If this ever fails, the backward started carrying f32 streams
    and the barrier became a real lever again (docs/perf/README.md)."""
    base = dict(memory_reduction_strategy="revnet",
                calculation_dtype="bfloat16", storage_dtype="bfloat16",
                slice_dtype="bfloat16")
    cfg_a = mixer_config(**base)
    cfg_b = mixer_config(**base, reversible_cotangent_dtype="bfloat16")
    p, _, batch, loss_a = init_and_loss(cfg_a)
    _, _, _, loss_b = init_and_loss(cfg_b)
    ga = jax.jit(jax.grad(loss_a))(p, jax.random.key(0))
    gb = jax.jit(jax.grad(loss_b))(p, jax.random.key(0))
    for k in ga:
        np.testing.assert_array_equal(np.asarray(ga[k]).view(np.uint16),
                                      np.asarray(gb[k]).view(np.uint16),
                                      err_msg=k)


def test_reversible_cotangent_squash_f32_runs():
    import numpy as np
    """f32-calculation configs with the bf16 cotangent squash must train (the
    squash rounds through bf16 and casts back, so block vjps still see f32
    cotangents) and produce grads close to the exact ones."""
    base = dict(memory_reduction_strategy="revnet",
                calculation_dtype="float32", storage_dtype="float32",
                slice_dtype="float32")
    cfg_a = mixer_config(**base)
    cfg_b = mixer_config(**base, reversible_cotangent_dtype="bfloat16")
    p, _, batch, loss_a = init_and_loss(cfg_a)
    _, _, _, loss_b = init_and_loss(cfg_b)
    ga = jax.jit(jax.grad(loss_a))(p, jax.random.key(0))
    gb = jax.jit(jax.grad(loss_b))(p, jax.random.key(0))
    for k in ga:
        a, b = np.asarray(ga[k], np.float32), np.asarray(gb[k], np.float32)
        assert np.all(np.isfinite(b)), k
        # bf16 rounding on the streams: close but not exact
        np.testing.assert_allclose(a, b, rtol=0.1, atol=1e-3, err_msg=k)


def test_vocab_weight_factorization_shapes_and_grads():
    """Factorized vocab embedding (reference src/model/__init__.py:76-82):
    the token embedding table gathers into a SMALL intermediate
    (intermediate_size * vocab_weight_factorization) and a linear lifts it
    to features, so the table is (vocab, small) instead of
    (vocab, intermediate) — the memory lever that makes vocab 65536
    affordable.  Grads must flow through both factors."""
    import numpy as np
    factor = 0.25
    cfg = tiny_config(vocab_size=512, vocab_weight_factorization=factor)
    params, axes, batch, loss_fn = init_and_loss(cfg)
    small = int(cfg.intermediate_size * factor)
    assert small < cfg.intermediate_size
    # exactly one parameter carries the vocab axis on the input side: the
    # factorized gather table
    emb = [(k, v) for k, v in params.items()
           if "input" in k and cfg.vocab_size in v.shape]
    assert len(emb) == 1, [k for k, _ in emb]
    k_emb, table = emb[0]
    assert sorted(table.shape) == sorted((cfg.vocab_size, small)), (
        k_emb, table.shape)
    # the lift linear maps (token_patch, small) -> features
    g = jax.jit(jax.grad(loss_fn))(params, jax.random.key(0))
    gt = np.asarray(g[k_emb], np.float32)
    assert np.isfinite(gt).all()
    # only gathered rows receive grads; at least one row must be nonzero
    assert np.abs(gt).sum() > 0
    # unfactorized control: table widens to the full intermediate
    cfg1 = tiny_config(vocab_size=512, vocab_weight_factorization=1.0)
    params1, _, _, _ = init_and_loss(cfg1)
    t1 = params1[k_emb]
    assert sorted(t1.shape) == sorted((cfg1.vocab_size,
                                       cfg1.intermediate_size))


def test_fused_mixer_block_matches_unfused():
    """ops/pallas_mixer.py (interpret mode on CPU): the fused
    [norm, map-attn, norm, gelu, map-attn] kernel must reproduce the
    unfused layer chain inside the REAL model — identical parameter names
    (checkpoints interchange) and matching loss/grads in f32."""
    import numpy as np
    dt = dict(calculation_dtype="float32", storage_dtype="float32",
              slice_dtype="float32", optimizer_slice_dtype="float32")
    shape = dict(sequence_length=128, features_per_head=128, heads=2,
                 depth=2, train_batch_size=2)
    cfg_u = mixer_config(**shape, **dt)
    cfg_f = mixer_config(**shape, **dt, fused_mixer_block=True)
    pu, axu, batch, loss_u = init_and_loss(cfg_u)
    pf, axf, _, loss_f = init_and_loss(cfg_f)
    # identical scope walk => identical parameter census
    assert set(pu) == set(pf)
    for k in pu:
        np.testing.assert_array_equal(np.asarray(pu[k]), np.asarray(pf[k]))

    lu = float(jax.jit(loss_u)(pu, jax.random.key(0)))
    lf = float(jax.jit(loss_f)(pu, jax.random.key(0)))
    assert abs(lu - lf) < 1e-4 * max(1.0, abs(lu)), (lu, lf)

    gu = jax.jit(jax.grad(loss_u))(pu, jax.random.key(0))
    gf = jax.jit(jax.grad(loss_f))(pu, jax.random.key(0))
    for k in gu:
        a = np.asarray(gu[k], np.float32)
        b = np.asarray(gf[k], np.float32)
        scale = max(1e-3, float(np.abs(a).max()))
        assert np.abs(a - b).max() < 5e-3 * scale, (
            k, float(np.abs(a - b).max()), scale)


@pytest.mark.parametrize("heads,dtype,tol", [(2, "float32", 2e-4),
                                             (8, "float32", 2e-4),
                                             (8, "bfloat16", 4e-2)])
def test_fused_mixer_kernel_batch_accumulation(heads, dtype, tol):
    """Kernel-level: the backward's cross-grid-cell parameter-grad
    accumulation (the pl.when(b != 0) path) must run — batch large enough
    that the batch grid axis has multiple steps — and all seven gradients
    match the unfused reference: in f32, and at the head count and dtype
    the 32mixer_group cell runs (bf16 rounds in another order than the
    reference, as chip_smoke.py's KERNEL_REL_TOL allows on the chip)."""
    import numpy as np

    from homebrewnlp_tpu.ops.pallas_mixer import (_block_rows,
                                                  fused_mixer_block,
                                                  mixer_chain_reference)
    B, S, H, K = 16, 128, heads, 128
    assert B > _block_rows(B, S, K)  # multiple batch grid steps
    ks = jax.random.split(jax.random.key(3), 7)
    f32 = jnp.float32
    x = jax.random.normal(ks[0], (B, S, H, K), f32)
    b1 = jax.random.normal(ks[1], (H, S, S), f32) * 0.02
    b2 = jax.random.normal(ks[2], (H, S, S), f32) * 0.02
    s1 = 1 + jax.random.normal(ks[3], (H, K), f32) * 0.02
    sh1 = jax.random.normal(ks[4], (H, K), f32) * 0.02
    s2 = 1 + jax.random.normal(ks[5], (H, K), f32) * 0.02
    sh2 = jax.random.normal(ks[6], (H, K), f32) * 0.02
    args = tuple(a.astype(dtype) for a in (x, b1, b2, s1, sh1, s2, sh2))

    def loss(fn):
        return lambda a: jnp.sum(fn(*a).astype(f32) ** 2)

    gr = jax.grad(loss(mixer_chain_reference))(args)
    gf = jax.grad(loss(lambda *a: fused_mixer_block(*a, True)))(args)
    for name, a, b_ in zip(("dx", "db1", "db2", "ds1", "dsh1", "ds2",
                            "dsh2"), gr, gf):
        assert b_.dtype == a.dtype == jnp.dtype(dtype), (name, b_.dtype)
        a = np.asarray(a, np.float32)
        b_ = np.asarray(b_, np.float32)
        scale = max(1e-3, float(np.abs(a).max()))
        assert np.abs(a - b_).max() < tol * scale, (
            name, float(np.abs(a - b_).max()), scale)


def test_fused_mixer_kernels_keep_their_names():
    """benchmark/layer_metrics/mixer_block_roofline.json finds the fused
    block's device events by the instruction names XLA derives from the two
    jitted functions that hold the Mosaic calls: `_fwd_pallas` and
    `_bwd_pallas`.  Renamed, the roofline reads nothing and a claimed gain
    has no bound.  Lowered for the TPU platform (no chip is needed to
    lower), each function must still exist and hold one tpu_custom_call."""
    import re

    from homebrewnlp_tpu.ops.pallas_mixer import fused_mixer_block
    B, S, H, K = 8, 128, 2, 128
    bf16 = jnp.bfloat16
    args = ((jnp.zeros((B, S, H, K), bf16),)
            + (jnp.zeros((H, S, S), bf16),) * 2
            + (jnp.zeros((H, K), bf16),) * 4)
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(  # squared: the backward needs the forward's out
            fused_mixer_block(*a, False).astype(jnp.float32) ** 2),
        argnums=tuple(range(7))))
    text = grad.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    bodies = {re.match(r"\w+", chunk).group(): chunk
              for chunk in text.split("func.func private @")[1:]}
    for name in ("_fwd_pallas", "_bwd_pallas"):
        assert name in bodies, sorted(bodies)
        assert bodies[name].count("@tpu_custom_call") == 1, name


def test_fused_group_block_matches_unfused():
    """ops/pallas_group.py (interpret mode on CPU): the fused two-kernel
    [group norm, bottleneck_group_linear] pair must reproduce the unfused
    layer chain inside the REAL model — identical parameter names
    (checkpoints interchange) and matching loss/grads in f32."""
    import numpy as np
    dt = dict(calculation_dtype="float32", storage_dtype="float32",
              slice_dtype="float32", optimizer_slice_dtype="float32")
    # memory_reduction_strategy="none" for the tight grad assertion: revnet's
    # stream reconstruction (x1 = y1 - f(y2)) chaotically amplifies the
    # fusion's benign summation-order differences (measured: 6e-7 rel grads
    # under "none" vs 1.6e-2 under revnet for the SAME kernels — the same
    # caveat docs/perf/README.md records for every remat/fusion change)
    shape = dict(sequence_length=128, features_per_head=128, heads=2,
                 depth=2, train_batch_size=2,
                 memory_reduction_strategy="none")
    cfg_u = mixer_config(**shape, **dt)
    cfg_f = mixer_config(**shape, **dt, fused_group_linear=True)
    # lane-aligned widths: K=128, mid=256, bottleneck I=128, N=256
    assert cfg_f.intermediate_size % 128 == 0
    pu, axu, batch, loss_u = init_and_loss(cfg_u)
    pf, axf, _, loss_f = init_and_loss(cfg_f)
    # identical scope walk => identical parameter census
    assert set(pu) == set(pf)
    for k in pu:
        np.testing.assert_array_equal(np.asarray(pu[k]), np.asarray(pf[k]))

    # XLA:CPU's DEFAULT f32 dot is split-bf16 (~1e-3 wobble, shape-
    # dependent); pin exact-f32 dots on both paths so parity is tight
    with jax.default_matmul_precision("highest"):
        lu = float(jax.jit(loss_u)(pu, jax.random.key(0)))
        lf = float(jax.jit(loss_f)(pu, jax.random.key(0)))
        assert abs(lu - lf) < 1e-5 * max(1.0, abs(lu)), (lu, lf)

        gu = jax.jit(jax.grad(loss_u))(pu, jax.random.key(0))
        gf = jax.jit(jax.grad(loss_f))(pu, jax.random.key(0))
    for k in gu:
        a = np.asarray(gu[k], np.float32)
        b = np.asarray(gf[k], np.float32)
        scale = max(1e-3, float(np.abs(a).max()))
        assert np.abs(a - b).max() < 1e-4 * scale, (
            k, float(np.abs(a - b).max()), scale)

    # under revnet the kernels still train the same model: loss parity holds
    # (grads deviate only through the reconstruction's rounding chaos)
    cfg_ur = mixer_config(**{**shape, "memory_reduction_strategy": "revnet"},
                          **dt)
    cfg_fr = mixer_config(**{**shape, "memory_reduction_strategy": "revnet"},
                          **dt, fused_group_linear=True)
    pur, _, _, loss_ur = init_and_loss(cfg_ur)
    _, _, _, loss_fr = init_and_loss(cfg_fr)
    with jax.default_matmul_precision("highest"):
        lur = float(jax.jit(loss_ur)(pur, jax.random.key(0)))
        lfr = float(jax.jit(loss_fr)(pur, jax.random.key(0)))
    assert abs(lur - lfr) < 1e-4 * max(1.0, abs(lur)), (lur, lfr)


def test_fused_group_kernel_row_accumulation():
    """Kernel-level: the backward's cross-grid-cell parameter-grad
    accumulation (the pl.when(r != 0) path) must run — rows beyond one
    grid cell of BOTH kernels — and match the unfused reference in f32."""
    import numpy as np

    from homebrewnlp_tpu.ops.pallas_group import (fused_group_linear_block,
                                                  group_chain_reference)
    B, S, H, K, I, J = 8, 128, 2, 128, 128, 256
    assert B * S > 512  # > kernel IN's row budget => multiple grid cells
    ks = jax.random.split(jax.random.key(3), 8)
    f32 = jnp.float32
    x = jax.random.normal(ks[0], (B, S, H, K), f32)
    w1 = jax.random.normal(ks[1], (H, K, I), f32) * 0.05
    w2 = jax.random.normal(ks[2], (I, H, J), f32) * 0.05
    w3 = jax.random.normal(ks[3], (H, J, K), f32) * 0.05
    s0 = 1 + jax.random.normal(ks[4], (H, K), f32) * 0.02
    h0 = jax.random.normal(ks[5], (H, K), f32) * 0.02
    s1 = 1 + jax.random.normal(ks[6], (H, J), f32) * 0.02
    h1 = jax.random.normal(ks[7], (H, J), f32) * 0.02
    args = (x, w1, w2, w3, s0, h0, s1, h1)
    # XLA:CPU's DEFAULT f32 dot is split-bf16 (~1e-3 wobble, shape-
    # dependent); pin exact-f32 dots on both paths so parity is tight
    with jax.default_matmul_precision("highest"):
        gr = jax.grad(
            lambda a: jnp.sum(group_chain_reference(*a) ** 2))(args)
        gf = jax.grad(
            lambda a: jnp.sum(fused_group_linear_block(*a, True) ** 2))(args)
    for name, a, b_ in zip(("dx", "dw1", "dw2", "dw3", "ds0", "dh0",
                            "ds1", "dh1"), gr, gf):
        a = np.asarray(a, np.float32)
        b_ = np.asarray(b_, np.float32)
        scale = max(1e-3, float(np.abs(a).max()))
        assert np.abs(a - b_).max() < 2e-4 * scale, (
            name, float(np.abs(a - b_).max()), scale)


def test_fused_group_falls_back_under_sharded_mesh(eight_devices):
    """fused_group_linear=true on a multi-device mesh must silently take
    the unfused GSPMD chain (pallas custom calls cannot be partitioned) —
    the knob is safe to leave on in a config that also runs sharded."""
    import numpy as np

    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    cfg = mixer_config(sequence_length=128, features_per_head=128, heads=2,
                       depth=2, train_batch_size=8, tpu_size=8,
                       fused_group_linear=True)
    mesh = make_mesh(cfg)
    assert mesh.size == 8
    trainer = Trainer(cfg, mesh)
    batch = text_batch(cfg)
    state = trainer.init(batch)
    state, m = trainer.step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))


def test_fused_mixer_falls_back_under_sharded_mesh(eight_devices):
    """fused_mixer_block=true on a multi-device mesh must silently take the
    unfused GSPMD chain (pallas custom calls cannot be partitioned) — the
    knob is safe to leave on in a config that also runs sharded."""
    import numpy as np

    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    cfg = mixer_config(sequence_length=128, features_per_head=128, heads=2,
                       depth=2, train_batch_size=8, tpu_size=8,
                       fused_mixer_block=True)
    mesh = make_mesh(cfg)
    assert mesh.size == 8
    trainer = Trainer(cfg, mesh)
    batch = text_batch(cfg)
    state = trainer.init(batch)
    state, m = trainer.step(state, batch, jax.random.key(0))
    assert np.isfinite(float(m["loss"]))
