"""Operations the mathematics of a Keye-VL-2.0 update needs, from a
configuration's sizes: the yardstick of `step_mfu.keye_vl2`,
`dsa_attention_roofline` and `dsa_indexer_roofline`.

Nothing here reads a compiled program: the same work is counted whatever
kernels, masks, remat or dispatch the program uses, and recomputation counts
nothing.  Counted, as multiply-adds a token of the forward pass (2 flops
each, times three for forward plus backward):

- every matrix product: the attention's projections (q over
  `num_attention_heads`, k and v over `num_key_value_heads`, the output),
  the indexer's three (`indexer_num_heads` x `indexer_head_dim`, one key
  head, a weight a head; their input is detached, so their backward is the
  weights' gradient alone: times two, not three), the router, the head;
- attention over the kept pairs only: row `t` meets `min(t + 1, topk)`
  keys, `head_dim` for the scores and again for the values, every query
  head;
- the indexer's scores over the whole causal triangle: a row of a sequence
  of S meets (S + 1) / 2 keys on average, `indexer_head_dim` a head;
- the held routed experts at their expected load: of a token's
  `num_experts_per_tok` picks, `experts_held / experts` fall here.

Norms, rotations, the relu and weighting of the scores, the selection, the
softmax, the indexer's loss beyond its scores, the gather and sort of the
dispatch and the loss are not counted.
"""
from __future__ import annotations


def _kinds(model: dict):
    """The kind of every block part kept, in order."""
    return [model["block_config"][c]["layer"][-1].split("-")[0]
            for row in model["block_schedule"] for c in row]


def kept_pairs(model: dict) -> int:
    """Pairs (row, key) a sequence keeps: ``sum_t min(t + 1, topk)``."""
    s, k = model["sequence_length"], model["sa_config"]["topk"]
    k = min(k, s)
    return k * (k + 1) // 2 + (s - k) * k


def part_macs_per_token(model: dict) -> dict:
    """Forward multiply-adds a token of one block part of each kind."""
    d = model["heads"] * model["features_per_head"]
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    s = model["sequence_length"]
    sa = model["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    gqa = (d * (h + 2 * g) * w + h * w * d
           + 2 * h * w * kept_pairs(model) / s)
    indexer = ni * di * (s + 1) / 2
    expert = 3 * d * model["moe_intermediate_size"]
    spec = next(b["layer"][-1] for b in model["block_config"]
                if b["layer"][-1].startswith("routed_moe")).split("-")
    topk = next(int(e[4:]) for e in spec if e.startswith("topk"))
    moe = d * model["experts"] + topk * model["experts_held"] / model[
        "experts"] * expert
    return {"gqa": gqa + indexer, "routed_moe": moe}


def indexer_projection_macs_per_token(model: dict) -> float:
    """Forward multiply-adds a token of one layer's indexer projections."""
    d = model["heads"] * model["features_per_head"]
    sa = model["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * (ni * di + di + ni)


def forward_macs_per_token(model: dict) -> float:
    part = part_macs_per_token(model)
    d = model["heads"] * model["features_per_head"]
    return (sum(part[kind] for kind in _kinds(model)) + d * model["vocab_size"]
            + _sparse_layers(model) * indexer_projection_macs_per_token(model))


def train_step_flops(model: dict) -> float:
    """Flops one update requires: forward and backward of the whole batch
    (the indexer's projections forward and their weights' gradient)."""
    tokens = model["train_batch_size"] * model["sequence_length"]
    projections = (_sparse_layers(model)
                   * indexer_projection_macs_per_token(model))
    return 2 * (3 * forward_macs_per_token(model) - projections) * tokens


def attention(model: dict, act_bytes: int = 2) -> dict:
    """Required flops and boundary bytes of the selected attention of ONE
    sparse layer, forward and backward, for the whole batch.  Forward: two
    products over the kept pairs; reads q, k, v, writes the output and one
    float32 statistic a row.  Backward: the transposes of both products
    (twice the forward's flops); reads q, k, v, the output, the statistic
    and the output's cotangent, writes the three gradients."""
    b, s = model["train_batch_size"], model["sequence_length"]
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    forward = 2 * 2 * b * h * w * kept_pairs(model)
    per_query_head = b * s * h * w * act_bytes          # q, o, dq, do
    per_kv_head = b * s * g * w * act_bytes             # k, v, dk, dv
    stat = b * s * h * 4
    return {
        "forward": {"flops": forward,
                    "bytes": 2 * per_query_head + 2 * per_kv_head + stat},
        "backward": {"flops": 2 * forward,
                     "bytes": 4 * per_query_head + 4 * per_kv_head + stat},
    }


def indexer(model: dict, act_bytes: int = 2) -> dict:
    """Required flops and boundary bytes of the indexer of ONE sparse layer
    for the whole batch, what the scopes `gqa_/indexer` and `gqa_/select`
    hold: the causal triangle's scores at `indexer_num_heads` x
    `indexer_head_dim`, forward only (the selection takes no gradient), and
    the three projections forward and their weights' gradient (the input is
    detached); reads the layer's input twice, writes and reads qI, kI and the
    weights, writes the kept set as `topk` positions a row (4 bytes each)."""
    b, s = model["train_batch_size"], model["sequence_length"]
    sa = model["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    d = model["heads"] * model["features_per_head"]
    projections = 2 * 2 * b * s * indexer_projection_macs_per_token(model)
    return {"flops": 2 * b * ni * di * (s * (s + 1) // 2) + projections,
            "bytes": b * s * (2 * d * act_bytes
                              + (ni + 1) * di * act_bytes + ni * 4
                              + min(sa["topk"], s) * 4)}


def _sparse_layers(model: dict) -> int:
    return sum(kind == "gqa" for kind in _kinds(model))


def attention_passes(model: dict) -> list:
    """The work of the selected attention of one update."""
    return [work for _ in range(_sparse_layers(model))
            for work in attention(model).values()]


def indexer_passes(model: dict) -> list:
    """The work of the indexer's scores of one update."""
    return [indexer(model) for _ in range(_sparse_layers(model))]
