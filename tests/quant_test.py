"""Low-precision compute (ISSUE 6): quant/dequant round-trip bounds, the
custom-vjp int8 einsum (quantized forward, exact high-precision backward),
default-off bit-identical parity, the int8 train smoke, the bench accept
gate + compile-budget evaluation, and the opaque-kernel FLOPs lower bound."""
import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from homebrewnlp_tpu import main as cli, nd
from homebrewnlp_tpu.nd import NT
from homebrewnlp_tpu.ops import quant
from homebrewnlp_tpu.train.metrics import read_metric_rows

from .backend import mixer_config, tiny_config


def _args(steps):
    return argparse.Namespace(steps=steps, profile="", workers=None)


def _losses(path):
    return [r["loss"] for r in read_metric_rows(str(path))]


# -- quantize / dequantize round-trip ----------------------------------------

def test_per_tensor_round_trip_bound():
    """Symmetric int8 round-trip error is bounded by half a quantization
    step (scale/2) everywhere inside the clip range."""
    x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32) * 3.0
    s = quant.per_tensor_scale(x, "int8")
    q = quant.quantize(x, s, "int8")
    assert q.dtype == jnp.int8
    err = jnp.max(jnp.abs(quant.dequantize(q, s) - x))
    assert float(err) <= float(s) / 2 + 1e-6, (float(err), float(s))


def test_per_channel_beats_per_tensor_on_skewed_channels():
    """Per-channel scales adapt to per-channel magnitude spread — the
    reason the weight operand quantizes per output channel."""
    key = jax.random.key(1)
    x = jax.random.normal(key, (128, 8), jnp.float32)
    x = x * (10.0 ** jnp.arange(-3, 5, dtype=jnp.float32))  # wild channels
    st = quant.per_tensor_scale(x, "int8")
    err_t = jnp.abs(quant.dequantize(quant.quantize(x, st, "int8"), st) - x)
    sc = quant.per_channel_scale(x, (0,), "int8")
    err_c = jnp.abs(
        quant.dequantize(quant.quantize(x, sc[None, :], "int8"),
                         sc[None, :]) - x)
    # compare on the small-magnitude channels where per-tensor collapses
    assert float(jnp.max(err_c[:, 0])) < float(jnp.max(err_t[:, 0])) / 100


def test_zero_tensor_quantizes_to_zero():
    x = jnp.zeros((4, 4), jnp.float32)
    s = quant.per_tensor_scale(x, "int8")
    assert float(s) > 0  # floored, no div-by-zero
    assert float(jnp.max(jnp.abs(
        quant.dequantize(quant.quantize(x, s, "int8"), s)))) == 0.0


# -- quant_einsum: forward accuracy + backward exactness ---------------------

def _rand_nt(key, shape, names, dtype):
    return NT(jax.random.normal(key, shape, jnp.float32).astype(dtype), names)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_einsum_forward_close_and_backward_exact(dtype):
    """Forward: the W8A8 contraction tracks the high-precision einsum
    (int8 rounding noise only).  Backward: EXACTLY the gradients of the
    unquantized contraction (straight-through custom_vjp contract)."""
    kx, kw = jax.random.split(jax.random.key(2))
    x = _rand_nt(kx, (4, 16, 8, 32), ("batch", "sequence", "heads",
                                      "features_per_head"), dtype)
    w = _rand_nt(kw, (8, 32, 64), ("heads", "features_per_head",
                                   "intermediate"), dtype)
    out_names = ("batch", "sequence", "intermediate")
    ref = nd.einsum([x, w], out_names)
    got = quant.quant_einsum(x, w, out_names, "int8")
    assert got.names == ref.names and got.dtype == ref.dtype
    rel = (jnp.linalg.norm((got.x - ref.x).astype(jnp.float32))
           / jnp.linalg.norm(ref.x.astype(jnp.float32)))
    assert float(rel) < 0.02, float(rel)

    def loss_q(xa, wa):
        return jnp.sum(quant.quant_einsum(
            NT(xa, x.names), NT(wa, w.names), out_names, "int8"
        ).x.astype(jnp.float32))

    def loss_ref(xa, wa):
        return jnp.sum(nd.einsum(
            [NT(xa, x.names), NT(wa, w.names)], out_names
        ).x.astype(jnp.float32))

    gq = jax.grad(loss_q, argnums=(0, 1))(x.x, w.x)
    gr = jax.grad(loss_ref, argnums=(0, 1))(x.x, w.x)
    for a, b in zip(gq, gr):
        assert a.dtype == b.dtype == dtype
        assert bool(jnp.all(a == b)), "backward must be the exact " \
                                      "high-precision vjp"


def test_quant_einsum_batched_head_axis():
    """The per-head block-diagonal contraction (group linear: HEADS stays
    on both sides) — the grouped-mixer shape the tentpole targets."""
    kx, kw = jax.random.split(jax.random.key(3))
    x = _rand_nt(kx, (2, 8, 4, 16), ("batch", "sequence", "heads",
                                     "features_per_head"), jnp.float32)
    w = _rand_nt(kw, (4, 16, 32), ("heads", "features_per_head",
                                   "_features_per_head"), jnp.float32)
    out_names = ("batch", "sequence", "heads", "_features_per_head")
    ref = nd.einsum([x, w], out_names)
    got = quant.quant_einsum(x, w, out_names, "int8")
    assert got.x.shape == ref.x.shape
    rel = (jnp.linalg.norm(got.x - ref.x) / jnp.linalg.norm(ref.x))
    assert float(rel) < 0.02, float(rel)


@pytest.mark.skipif(not quant.supported("fp8"),
                    reason="toolchain lacks fp8 dtypes")
def test_quant_einsum_fp8_path():
    kx, kw = jax.random.split(jax.random.key(4))
    x = _rand_nt(kx, (4, 8), ("batch", "features_per_head"), jnp.float32)
    w = _rand_nt(kw, (8, 16), ("features_per_head", "intermediate"),
                 jnp.float32)
    out = quant.quant_einsum(x, w, ("batch", "intermediate"), "fp8")
    ref = nd.einsum([x, w], ("batch", "intermediate"))
    assert bool(jnp.all(jnp.isfinite(out.x)))
    rel = (jnp.linalg.norm(out.x - ref.x) / jnp.linalg.norm(ref.x))
    assert float(rel) < 0.1, float(rel)  # e4m3: 3 mantissa bits


# -- scope selection ---------------------------------------------------------

def test_scope_matching_and_pattern_quantized():
    assert quant.scope_matches(["bottleneck_group_linear"],
                               "gpt/block_/bottleneck_group_linear_3/x")
    assert not quant.scope_matches(["bottleneck_group_linear"],
                                   "gpt/block_/attention_/x")
    cfg = mixer_config(quant_blocks=["bottleneck_group_linear"])
    from homebrewnlp_tpu.models.layers import MIXER_FUSED_PATTERN
    # 32mixer_group's block 1: a pattern that holds the quantized layer
    # yields; the mixer block holds none and keeps its fused kernel
    GROUP_PATTERN = (
        "norm-shift-scale-features-group",
        "bottleneck_group_linear-in:relu-mid:relu-mid:norm-mid:shift-mid:scale"
        "-mid:features")
    assert quant.pattern_quantized(cfg, GROUP_PATTERN)
    assert not quant.pattern_quantized(cfg, MIXER_FUSED_PATTERN)
    assert not quant.pattern_quantized(mixer_config(), GROUP_PATTERN)
    # seeded regression: the slash-anchored disambiguation form (and a
    # trailing-underscore scope form) must ALSO disable fusion — bare-name
    # matching here once let the fused kernel bypass a declared scope
    anchored = mixer_config(quant_blocks=["/bottleneck_group_linear"])
    assert quant.pattern_quantized(anchored, GROUP_PATTERN)
    # "/group_linear" selects only the plain per-head linear: it matches
    # neither the bottleneck scope in linear() nor the fused pattern here
    only_plain = mixer_config(quant_blocks=["/group_linear"])
    assert not quant.pattern_quantized(only_plain, GROUP_PATTERN)
    assert not quant.scope_matches(
        ["/group_linear"], "gpt/block_/bottleneck_group_linear_/w")


# -- parity: quant_blocks unset => bit-identical pre-quant graph -------------

def test_quant_off_parity_8_steps(tmp_path, eight_devices):
    """Acceptance: the default config and a declared-but-unmatched scope
    both compile the exact pre-quant graph — loss sequences bit-identical.
    (The committed census goldens pin the stronger structural fact: n_eqns
    of every pre-quant config is unchanged.)"""
    cli.train(tiny_config(model_path=str(tmp_path / "off")), _args(8))
    cli.train(tiny_config(model_path=str(tmp_path / "nomatch"),
                          quant_blocks=["no_such_layer_name"]), _args(8))
    off = _losses(tmp_path / "off")
    assert len(off) == 8
    assert off == _losses(tmp_path / "nomatch")


@pytest.mark.slow
def test_quant_off_parity_300_steps(tmp_path, eight_devices):
    base = dict(async_inflight_steps=0, device_prefetch_depth=0)
    cli.train(tiny_config(model_path=str(tmp_path / "off"), **base),
              _args(300))
    cli.train(tiny_config(model_path=str(tmp_path / "nomatch"),
                          quant_blocks=["no_such_layer_name"], **base),
              _args(300))
    off = _losses(tmp_path / "off")
    assert len(off) == 300
    assert off == _losses(tmp_path / "nomatch")


def test_int8_train_smoke_and_trajectory(tmp_path, eight_devices):
    """8 updates with the feed-forward linears quantized: finite losses,
    training still progresses, and the trajectory stays near the
    high-precision one (the tiny-scale twin of the bench accept gate)."""
    cli.train(tiny_config(model_path=str(tmp_path / "base")), _args(8))
    cli.train(tiny_config(model_path=str(tmp_path / "q"),
                          quant_blocks=["feed_forward"]), _args(8))
    base, q = _losses(tmp_path / "base"), _losses(tmp_path / "q")
    assert all(l == l for l in q)
    assert q[-1] < q[0]
    assert max(abs(a - b) for a, b in zip(base, q)) < 0.1, (base, q)


def test_quant_config_validation():
    with pytest.raises(ValueError):
        tiny_config(quant_dtype="int4")
    with pytest.raises(ValueError):
        tiny_config(quant_blocks=[""])
    with pytest.raises(ValueError):
        # a bare string would explode into per-character substrings and
        # silently quantize nearly everything
        tiny_config(quant_blocks="feed_forward")
    cfg = tiny_config(quant_blocks=["feed_forward"], quant_dtype="int8")
    assert cfg.quant_blocks == ["feed_forward"]


# -- bench accept gate + compile budget (pure evaluators) --------------------

def test_evaluate_quant_gate_verdicts():
    from bench import evaluate_quant_gate
    base = [7.0, 5.0, 4.0, 3.5]
    ok = evaluate_quant_gate(base, [7.05, 5.02, 4.03, 3.52], rel_tol=0.1)
    assert ok["pass"] and ok["finite"] and ok["trains"]
    # deviation beyond tolerance: measured REJECT, numbers still reported
    bad = evaluate_quant_gate(base, [7.0, 5.0, 4.0, 5.9], rel_tol=0.1)
    assert not bad["pass"] and bad["max_rel_dev"] > 0.1
    nan = evaluate_quant_gate(base, [7.0, float("nan"), 4.0, 3.5])
    assert not nan["pass"] and not nan["finite"]
    flat = evaluate_quant_gate(base, [7.0, 7.0, 7.0, 7.0], rel_tol=10.0)
    assert not flat["pass"] and not flat["trains"]
    assert not evaluate_quant_gate(base, [1.0])["pass"]  # length mismatch


def test_evaluate_compile_budget():
    from bench import evaluate_compile_budget
    budgets = {"a": 100.0, "b": 50.0}
    rows, ok = evaluate_compile_budget(
        {"a": {"compile_and_warmup_s": 110.0},
         "b": {"compile_and_warmup_s": 49.0},
         "c": {"compile_and_warmup_s": 999.0}},  # no budget: skipped
        budgets)
    assert ok and rows["a"]["pass"] and rows["b"]["pass"] and "c" not in rows
    rows, ok = evaluate_compile_budget(
        {"a": {"compile_and_warmup_s": 121.0}}, budgets)
    assert not ok and not rows["a"]["pass"] and rows["a"]["ratio"] == 1.21
    # errored workload rows (no compile figure) are not regressions
    rows, ok = evaluate_compile_budget({"a": {"error": "boom"}}, budgets)
    assert ok and not rows


def test_compile_ratchet_cli_on_committed_bench():
    """The CI entry point passes on the committed BENCH_r*.json + budget."""
    import subprocess
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "compile_ratchet.py")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


# -- opaque-kernel FLOPs lower bound (satellite) -----------------------------

def test_utilization_lower_bound_under_opaque_kernels(eight_devices):
    """A fused-kernel config's live utilization adopts the unfused twin's
    executed flops as an explicit lower bound (BENCH_r05's mfu:null fix)."""
    from homebrewnlp_tpu.train import flops as flops_mod
    from homebrewnlp_tpu.train.state import Trainer
    from .backend import text_batch

    cfg = tiny_config()
    trainer = Trainer(cfg)
    batch = text_batch(cfg)
    state = trainer.init(batch)
    base = flops_mod.utilization_for(trainer, state, batch, 32)
    assert base.flops_per_step > 0 and not base.flops_lower_bound

    cfg_f = tiny_config(fused_mixer_block=True)  # opaque knob on (the tiny
    # shapes keep the unfused chain, so the twin's count equals the step's)
    tr_f = Trainer(cfg_f)
    tr_f.axes = trainer.axes
    from homebrewnlp_tpu.optim import Optimizer
    tr_f.optimizer = Optimizer(cfg_f, trainer.axes)
    util = flops_mod.utilization_for(tr_f, state, batch, 32)
    assert util.flops_lower_bound
    assert util.flops_per_step == base.flops_per_step
