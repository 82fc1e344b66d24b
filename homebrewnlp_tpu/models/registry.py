"""Layer-DSL registry + block assembly.

Layer spec strings are ``"name-extra1-extra2"`` (reference
src/model/frontend.py:21-36); ``split_path`` builds add/multiply parallel
branches from ``;``/``,``-separated sub-configs (frontend.py:39-55).
"""
from __future__ import annotations

import typing

from ..config import BlockConfig
from ..nd import NT
from ..ops.activations import activate
from .ctx import Args, Ctx
from . import hybrid, layers


def _get_block_part(block_part_config: BlockConfig, ctx: Ctx, block_input: NT) -> NT:
    if layers.fused_mixer_eligible(ctx, block_part_config, block_input):
        # the mixer block-2 chain as ONE pallas fwd kernel + one full-vjp
        # bwd kernel (ops/pallas_mixer.py) — same parameters, same scope
        # walk, a fraction of the HBM traffic
        out = layers.fused_mixer_block_part(block_part_config, ctx,
                                            block_input)
    else:
        out = block_input
        for idx, layer in enumerate(block_part_config.layer, 1):
            name, *extras = layer.split("-")
            if name not in LAYER_FUNCTIONS:
                raise ValueError(f"unknown layer {name!r} in spec {layer!r}; "
                                 f"known layers: {sorted(LAYER_FUNCTIONS)}")
            args = Args(ctx, out, extras, idx == len(block_part_config.layer))
            out = ctx.scoped(name + "_", LAYER_FUNCTIONS[name], args)
    if block_part_config.skip and block_part_config.memory_reduction_strategy in ("none", "checkpoint"):
        # a scope of its own: what sits bare under `block_` reads as a fused
        # block's kernel (obs/profile.py::step_scope)
        out = ctx.scoped("skip_", lambda: out + block_input)
    return out


def block_part_fn(ctx: Ctx, block_part_config: BlockConfig, block_input: NT,
                  name_prefix: str = "block") -> NT:
    return ctx.scoped(f"{name_prefix}_", _get_block_part, block_part_config, ctx,
                      block_input)


def split_path(args: Args) -> NT:
    base, *branch_confs = "-".join(args.name_extras).split(";")
    base = base.split("-")
    if "add" in base:
        out: typing.Union[NT, int] = 0
        combine = lambda a, b: b if isinstance(a, int) else a + b
    elif "multiply" in base:
        out = 1
        combine = lambda a, b: b if isinstance(a, int) else a * b
    else:
        raise ValueError(f"split_path needs add/multiply base, got {base}")
    for conf in branch_confs:
        branch = _get_block_part(
            BlockConfig(layer=conf.split(","), skip=False,
                        memory_reduction_strategy=""),
            args.ctx, args.tensor)
        out = combine(out, branch)
    return out


LAYER_FUNCTIONS: typing.Dict[str, typing.Callable[[Args], NT]] = {
    "feed_forward": layers.feed_forward,
    "attention": layers.attention,
    "cummean": layers.cummean,
    "cumsum": layers.cumsum,
    "norm": layers.norm,
    "rezero": layers.rezero,
    "activation": activate,
    "convolution": layers.convolution,
    "dropout": layers.dropout,
    "group_linear": layers.group_linear,
    "split_path": split_path,
    "feed_forward_product_key_memory": layers.feed_forward_product_key_memory,
    "product_key_memory": layers.product_key_memory,
    "reduced_half_linear": layers.reduced_half_linear,
    "transpose_sequence_features": layers.transpose_sequence_features,
    "bottleneck_group_linear": layers.bottleneck_group_linear,
    "sum_heads": layers.sum_heads,
    # extensions (models/hybrid.py; the reference has none of them)
    "rms_norm": hybrid.rms_norm,
    "gated_feed_forward": hybrid.gated_feed_forward,
    "kda": hybrid.kda,
    "mla": hybrid.mla,
    "gqa": hybrid.gqa,
    "routed_moe": hybrid.routed_mixture_of_experts,
}
