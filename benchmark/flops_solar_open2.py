"""Operations the mathematics of a Solar-Open2 update needs, from a
configuration's sizes: the yardstick of `step_mfu.solar_open2` and of
`gqa_attention_roofline.solar_open2`.

Nothing here reads a compiled program: the same work is counted whatever
kernels, chunking, remat or dispatch the program uses, and recomputation
counts nothing.  Counted, as multiply-adds a token of the forward pass
(2 flops each, times three for forward plus backward):

- every matrix product: the delta-rule layers' projections and low-rank
  gate pairs, the attention's projections (q and the gate over
  `num_attention_heads`, k and v over `num_key_value_heads`), the router,
  the shared expert, the head; all over the heads held here;
- the delta rule's three products against the state a token and head
  (`k^T S`, the rank-one update, `S^T q`): `3 d_k d_v`;
- attention as the triangle the causal mask leaves: a query row of a
  sequence of S meets (S + 1) / 2 keys on average, `head_dim` for the
  scores and again for the values;
- the held routed experts at their expected load: of a token's
  `num_experts_per_tok` picks, `experts_held / experts` fall here.

Norms, activations, convolutions, the decay, the gates' products, the
softmax, the gather, the sort of the dispatch and the loss are not counted.

`attention` gives the attention proper (scores, softmax, values: what lies
between q, k, v and the heads' ungated outputs) one layer and pass: its
flops and the bytes that must cross its boundary once, `k` and `v` counted
once a K/V head, not once a query head.
"""
from __future__ import annotations


def _kinds(model: dict):
    """The kind of every block part kept, in order."""
    return [model["block_config"][c]["layer"][-1].split("-")[0]
            for row in model["block_schedule"] for c in row]


def part_macs_per_token(model: dict) -> dict:
    """Forward multiply-adds a token of one block part of each kind."""
    d = model["heads"] * model["features_per_head"]
    la = model["linear_attn_config"]
    inner = la["num_heads"] * la["head_dim"]
    rank = la["head_dim"]                   # of the two low-rank gate pairs
    kda = (3 * d * inner + 2 * (d * rank + rank * inner)
           + d * la["num_heads"] + inner * d
           + 3 * la["num_heads"] * la["head_dim"] ** 2)
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    s = model["sequence_length"]
    gqa = d * (2 * h + 2 * g) * w + h * w * d + 2 * h * w * (s + 1) / 2
    expert = 3 * d * model["moe_intermediate_size"]
    spec = next(b["layer"][-1] for b in model["block_config"]
                if b["layer"][-1].startswith("routed_moe")).split("-")
    topk = next(int(e[4:]) for e in spec if e.startswith("topk"))
    shared = next((int(e[6:]) for e in spec if e.startswith("shared")), 0)
    moe = (d * model["experts"] + shared * expert
           + topk * model["experts_held"] / model["experts"] * expert)
    return {"kda": kda, "gqa": gqa, "routed_moe": moe}


def forward_macs_per_token(model: dict) -> float:
    part = part_macs_per_token(model)
    d = model["heads"] * model["features_per_head"]
    return sum(part[kind] for kind in _kinds(model)) + d * model["vocab_size"]


def train_step_flops(model: dict) -> float:
    """Flops one update requires: forward and backward of the whole batch."""
    tokens = model["train_batch_size"] * model["sequence_length"]
    return 3 * 2 * forward_macs_per_token(model) * tokens


def attention(model: dict, act_bytes: int = 2) -> dict:
    """Required flops and boundary bytes of the attention proper of ONE
    `gqa` layer, forward and backward, for the whole batch.  Forward: two
    products over the triangle; reads q, k, v, writes the output and one
    float32 statistic a row.  Backward: the transposes of both products
    (twice the forward's flops; the scores a kernel computes again are
    recomputation); reads q, k, v, the output, the statistic and the
    output's cotangent, writes the three gradients."""
    b, s = model["train_batch_size"], model["sequence_length"]
    h, g, w = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    forward = 2 * 2 * b * h * w * (s * (s + 1) // 2)
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
    and backward of every `gqa` layer kept."""
    return [work for kind in _kinds(model) if kind == "gqa"
            for work in attention(model).values()]
