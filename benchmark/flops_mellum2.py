"""Operations the mathematics of a Mellum 2 update needs, from a
configuration's sizes: the yardstick of `step_mfu.mellum2` and of
`gqa_attention_roofline`.

Nothing here reads a compiled program: the same work is counted whatever
kernels, chunking, remat or dispatch the program uses, and recomputation
counts nothing.  Counted, as multiply-adds a token of the forward pass
(2 flops each, times three for forward plus backward):

- every matrix product: the attention's four projections (q over
  `num_attention_heads`, k and v over `num_key_value_heads`), the router,
  the head;
- attention over the pairs the mask leaves (`visible_pairs`): on a full
  layer the triangle, a query row of a sequence of S meets (S + 1) / 2 keys
  on average; on a sliding layer the band, row i meets min(i + 1,
  `sliding_window`) keys; `head_dim` for the scores and again for the values;
- the held routed experts at their expected load: of a token's
  `num_experts_per_tok` picks, `experts_held / experts` fall here.

Norms, the rotation, activations, the softmax, the gather, the sort of the
dispatch and the loss are not counted.

`attention` gives the attention proper (scores, softmax, values: what lies
between the rotated q, k, v and the heads' outputs) one layer and pass:
its flops and the bytes that must cross its boundary once, `k` and `v`
counted once a K/V head, not once a query head.
"""
from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"


def visible_pairs(sequence: int, window) -> int:
    """(row, key) pairs of one sequence and head that the mask leaves: key <=
    row and, under a window, row - key < window."""
    if window is None or window >= sequence:
        return sequence * (sequence + 1) // 2
    return window * (window + 1) // 2 + (sequence - window) * window


def _window(model: dict, kind: str):
    return model["sliding_window"] if kind == SLIDING else None


def _kinds(model: dict):
    """The kind of every block part kept, in order: a layer type for an
    attention part, `routed_moe` for an expert part."""
    out = []
    for row in model["block_schedule"]:
        for c in row:
            name, *extras = model["block_config"][c]["layer"][-1].split("-")
            out.append(extras[0] if name == "gqa" else name)
    return out


def part_macs_per_token(model: dict) -> dict:
    """Forward multiply-adds a token of one block part of each kind."""
    d = model["heads"] * model["features_per_head"]
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    s = model["sequence_length"]
    projections = d * (h + 2 * g) * w + h * w * d
    out = {kind: projections + 2 * h * w * visible_pairs(
        s, _window(model, kind)) / s for kind in (SLIDING, FULL)}
    spec = next(b["layer"][-1] for b in model["block_config"]
                if b["layer"][-1].startswith("routed_moe")).split("-")
    topk = next(int(e[4:]) for e in spec if e.startswith("topk"))
    out["routed_moe"] = (
        d * model["experts"] + topk * model["experts_held"] / model["experts"]
        * 3 * d * model["moe_intermediate_size"])
    return out


def forward_macs_per_token(model: dict) -> float:
    part = part_macs_per_token(model)
    d = model["heads"] * model["features_per_head"]
    return sum(part[kind] for kind in _kinds(model)) + d * model["vocab_size"]


def train_step_flops(model: dict) -> float:
    """Flops one update requires: forward and backward of the whole batch."""
    tokens = model["train_batch_size"] * model["sequence_length"]
    return 3 * 2 * forward_macs_per_token(model) * tokens


def attention(model: dict, kind: str, act_bytes: int = 2) -> dict:
    """Required flops and boundary bytes of the attention proper of ONE
    layer of `kind`, forward and backward, for the whole batch.  Forward:
    two products over the visible pairs; reads q, k, v, writes the output
    and one float32 statistic a row.  Backward: the transposes of both
    products (twice the forward's flops; the scores a kernel computes again
    are recomputation); reads q, k, v, the output, the statistic and the
    output's cotangent, writes the three gradients."""
    b, s = model["train_batch_size"], model["sequence_length"]
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    forward = 2 * 2 * b * h * w * visible_pairs(s, _window(model, kind))
    per_query_head = b * s * h * w * act_bytes          # q, o, dq, do
    per_kv_head = b * s * g * w * act_bytes             # k, v, dk, dv
    stat = b * s * h * 4
    return {
        "forward": {"flops": forward,
                    "bytes": 2 * per_query_head + 2 * per_kv_head + stat},
        "backward": {"flops": 2 * forward,
                     "bytes": 4 * per_query_head + 4 * per_kv_head + stat},
    }


def attention_passes(model: dict) -> list:
    """The work of the attention proper of one update: `attention`'s forward
    and backward of every attention layer kept."""
    return [work for kind in _kinds(model) if kind in (SLIDING, FULL)
            for work in attention(model, kind).values()]
