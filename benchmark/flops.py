"""Operations and bytes the mathematics needs, from a configuration's sizes.

The yardstick of `step_mfu` and `mixer_block_roofline`.  Nothing here reads a
compiled program: the same work is counted whatever kernels, remat or fusion
the program uses, and recomputation counts nothing.

Counted: the matrix products of the forward pass (2 flops a multiply-add),
times three for forward plus backward (each product has two transposed
products in its backward).  Norms, activations, the gather and the loss are
not counted.  The causal maps are counted as the triangle they are
(S*(S+1)/2 rows by columns, not S*S): a program that multiplies the masked
square does work the model does not require.  `dense_maps=True` counts the
square, which is what an XLA-visible masked einsum executes and what the
cross-check against a jaxpr count uses.
"""
from __future__ import annotations


def _sizes(model: dict) -> dict:
    h, k = model["heads"], model["features_per_head"]
    glf = model["group_linear_factor"]
    inter = int(k * glf * model["intermediate_feed_forward_multiplier_multiplier"])
    return dict(h=h, k=k, s=model["sequence_length"], d=model["depth"],
                v=model["vocab_size"], i=inter, m=k * glf,
                e=int(inter * model.get("vocab_weight_factorization", 0.125)))


def map_macs_per_sequence(model: dict, dense_maps: bool = False) -> float:
    """Multiply-adds of ONE causal map product over one sequence, all heads:
    [S,S] @ [S,K] per head."""
    z = _sizes(model)
    pairs = z["s"] * z["s"] if dense_maps else z["s"] * (z["s"] + 1) / 2
    return z["h"] * pairs * z["k"]


def forward_macs_per_sequence(model: dict, dense_maps: bool = False) -> float:
    z = _sizes(model)
    hk = z["h"] * z["k"]
    per_token_block0 = hk * z["i"] + z["i"] * z["h"] * z["m"] + z["h"] * z["m"] * z["k"]
    per_depth = z["s"] * per_token_block0 + 2 * map_macs_per_sequence(
        model, dense_maps)
    ends = z["s"] * (z["e"] * hk + hk * z["v"])
    return z["d"] * per_depth + ends


def train_step_flops(model: dict, batch: int, dense_maps: bool = False
                     ) -> float:
    """Flops one update requires: forward + backward of `batch` sequences."""
    return 3 * 2 * forward_macs_per_sequence(model, dense_maps) * batch


def mixer_block(model: dict, batch: int, act_bytes: int = 2,
                param_bytes: int = 2) -> dict:
    """Required flops and call-boundary bytes of ONE mixer block
    (norm, map, norm, gelu, map), forward and backward, for `batch`
    sequences.  Bytes are what must cross the block's boundary once:
    forward reads x and writes out; backward reads x and d(out) and writes
    dx; both read the two maps and the four norm vectors, the backward
    writes their gradients in float32."""
    z = _sizes(model)
    act = batch * z["s"] * z["h"] * z["k"] * act_bytes
    maps = 2 * z["h"] * z["s"] * z["s"]
    norms = 4 * z["h"] * z["k"]
    fwd_flops = 2 * 2 * map_macs_per_sequence(model) * batch
    return {
        "fwd": {"flops": fwd_flops,
                "bytes": 2 * act + (maps + norms) * param_bytes},
        "bwd": {"flops": 2 * fwd_flops,
                "bytes": 3 * act + (maps + norms) * (param_bytes + 4)},
    }


def least_seconds(work: dict, peak: dict) -> float:
    """Roofline: the larger of flops over peak and bytes over bandwidth."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
