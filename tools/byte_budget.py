"""Per-component HBM byte budget for a workload config (VERDICT r4 item 4).

The 32mixer_group roofline (docs/perf/README.md) proves the step is
bandwidth-bound; this tool breaks the bytes down so the remaining GB are
attributable.  It cost-analyzes, via XLA on the live backend:

- the FULL train step (default knobs, remat off, fused-mixer on/off),
- each layer family standalone (one fwd+bwd call at the workload's
  activation shape): norm, masked-map attention, the gelu glue, the whole
  5-layer mixer block unfused vs fused (ops/pallas_mixer.py), and the
  bottleneck-group-linear block,
- the optimizer update alone (grads -> new params/slots),

and prints a JSON table plus derived "per step" extrapolations (calls per
step x per-call bytes).  NOTE pallas kernels are opaque to XLA cost
analysis (their in-kernel flops/bytes are not counted); the fused rows'
"bytes" are therefore the true HBM traffic at the pallas_call boundary
(exactly what the lever claims to cut) while their "flops" UNDERCOUNT —
wall-clock and the unfused flop count are the honest comparators.

Usage:
  python tools/byte_budget.py [--config configs/32mixer_group.json]
      [--batch 64] [--steps-probe]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def cost_of(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    c = dict(c or {})
    return {"flops": float(c.get("flops", 0.0)),
            "bytes": float(c.get("bytes accessed", 0.0))}


def layer_rows(cfg, shape, cfg_fused=None) -> dict:
    """Standalone fwd+bwd cost per layer family at the workload shape."""
    from homebrewnlp_tpu.models.ctx import Args, Ctx
    from homebrewnlp_tpu.models.registry import _get_block_part
    from homebrewnlp_tpu.config import BlockConfig
    from homebrewnlp_tpu.models import init_params
    from homebrewnlp_tpu.nd import NT

    names = ("batch", "sequence", "heads", "features_per_head")
    x = jax.random.normal(jax.random.key(0), shape).astype(
        cfg.calculation_dtype)

    chains = {
        "norm": ["norm-shift-scale-features-group"],
        "map_attention": [
            "attention-biased_attention_map-absolute-input_as_value-shared"],
        "gelu": ["activation-gelu"],
        "mixer_block_unfused": None,   # filled from the config
        "group_linear_block": None,
    }
    from homebrewnlp_tpu.models.layers import MIXER_FUSED_PATTERN
    chains["mixer_block_unfused"] = list(MIXER_FUSED_PATTERN)
    chains["group_linear_block"] = list(cfg.block_config[0]["layer"]
                                        if isinstance(cfg.block_config[0], dict)
                                        else cfg.block_config[0].layer)

    rows = {}
    for label, layer_list in chains.items():
        conf = BlockConfig(layer=layer_list, skip=False,
                           memory_reduction_strategy="none")

        def init_chain():
            ctx = Ctx(cfg, params=None, train=True)
            ctx._scope = ["probe"]
            _get_block_part(conf, ctx, NT(x, names))
            return ctx.collected

        params = jax.jit(init_chain)()

        def fwd_bwd(p, t):
            def f(p, t):
                ctx = Ctx(cfg, params=p, train=True)
                ctx._scope = ["probe"]
                out = _get_block_part(conf, ctx, NT(t, names))
                return jnp.sum(out.x.astype(jnp.float32))
            g = jax.grad(f, argnums=(0, 1))(p, t)
            return g

        rows[label] = cost_of(fwd_bwd, dict(params), x)
        if label == "mixer_block_unfused" and cfg_fused is not None:
            def fwd_bwd_fused(p, t):
                def f(p, t):
                    ctx = Ctx(cfg_fused, params=p, train=True)
                    ctx._scope = ["probe"]
                    out = _get_block_part(conf, ctx, NT(t, names))
                    return jnp.sum(out.x.astype(jnp.float32))
                return jax.grad(f, argnums=(0, 1))(p, t)
            rows["mixer_block_fused"] = cost_of(fwd_bwd_fused,
                                                dict(params), x)
    return rows


def main() -> None:
    from homebrewnlp_tpu.utils import (ONE_CHIP_COMMON,
                                       enable_compilation_cache, load_config,
                                       random_text_batch)
    from homebrewnlp_tpu.train import Trainer

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="configs/32mixer_group.json")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--skip-step", action="store_true",
                    help="layer rows only (no full-step compiles)")
    args = ap.parse_args()

    common = dict(ONE_CHIP_COMMON, train_batch_size=args.batch)
    cfg = load_config(args.config, **common)
    enable_compilation_cache()

    out = {"config": args.config, "batch": args.batch,
           "device": jax.devices()[0].device_kind}

    shape = (cfg.train_batch_size, cfg.sequence_length, cfg.heads,
             cfg.features_per_head)
    out["activation_shape"] = list(shape)
    cfg_fused = load_config(args.config, **common, fused_mixer_block=True)
    out["layers"] = layer_rows(cfg, shape, cfg_fused)

    if not args.skip_step:
        variants = {
            "step_remat_off": dict(reversible_remat_blocks=False),
            "step_remat_on": dict(reversible_remat_blocks=True),
            "step_fused_mixer": dict(reversible_remat_blocks=False,
                                     fused_mixer_block=True),
        }
        out["step"] = {}
        for label, over in variants.items():
            c = load_config(args.config, **common, **over)
            tr = Trainer(c)
            batch = random_text_batch(c)
            state = tr.init(batch)
            cost = tr.step_cost_analysis(state, batch)
            out["step"][label] = {
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0))}

        # parameter/optimizer-state footprint (bf16 resident)
        n_params = sum(int(v.size) for v in state.params.values())
        n_slots = sum(int(x.size) for x in jax.tree_util.tree_leaves(
            state.opt_state))
        out["param_count"] = n_params
        out["opt_slot_count"] = n_slots
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
