"""Operations the mathematics of a Kimi-Linear update needs, from a
configuration's sizes: the yardstick of `step_mfu.kimi_linear`.

Nothing here reads a compiled program: the same work is counted whatever
kernels, chunking, remat or dispatch the program uses, and recomputation
counts nothing.  Counted, as multiply-adds a token of the forward pass
(2 flops each, times three for forward plus backward):

- every matrix product: the mixers' projections and low-rank gate pairs,
  the dense feed-forward, the router, the shared expert, the head;
- the held routed experts at their expected load: of a token's
  `num_experts_per_token` picks, `experts_held / experts` fall here;
- attention as the triangle the causal mask leaves: a query row of a
  sequence of S meets (S + 1) / 2 keys on average, at `qk_nope + qk_rope`
  for the scores and `v_head_dim` for the values;
- the delta rule's three products against the state a token and head
  (`k^T S`, the rank-one update, `S^T q`): `3 d_k d_v`.

Norms, activations, convolutions, the decay, the gather, the sort of the
dispatch and the loss are not counted.
"""
from __future__ import annotations


def _kinds(model: dict):
    """(mixer, feed-forward) kind of every layer kept, in order."""
    return [[model["block_config"][c]["layer"][-1].split("-")[0] for c in row]
            for row in model["block_schedule"]]


def part_macs_per_token(model: dict) -> dict:
    """Forward multiply-adds a token of one block part of each kind."""
    d = model["heads"] * model["features_per_head"]
    la = model["linear_attn_config"]
    inner = la["num_heads"] * la["head_dim"]
    rank = la["head_dim"]                   # of the two low-rank gate pairs
    kda = (3 * d * inner + 2 * (d * rank + rank * inner)
           + d * la["num_heads"] + inner * d
           + 3 * la["num_heads"] * la["head_dim"] ** 2)
    h, s = model["heads"], model["sequence_length"]
    q_dim = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    latent = model["kv_lora_rank"]
    mla = (d * h * q_dim + d * (latent + model["qk_rope_head_dim"])
           + latent * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
           + h * model["v_head_dim"] * d
           + h * (s + 1) / 2 * (q_dim + model["v_head_dim"]))
    expert = 3 * d * model["moe_intermediate_size"]
    spec = next(b["layer"][-1] for b in model["block_config"]
                if b["layer"][-1].startswith("routed_moe")).split("-")
    topk = next(int(e[4:]) for e in spec if e.startswith("topk"))
    shared = next((int(e[6:]) for e in spec if e.startswith("shared")), 0)
    moe = (d * model["experts"] + shared * expert
           + topk * model["experts_held"] / model["experts"] * expert)
    dense = 3 * d * int(d * model["intermediate_feed_forward_multiplier"])
    return {"kda": kda, "mla": mla, "gated_feed_forward": dense,
            "routed_moe": moe}


def forward_macs_per_token(model: dict) -> float:
    part = part_macs_per_token(model)
    d = model["heads"] * model["features_per_head"]
    return sum(part[kind] for row in _kinds(model) for kind in row
               ) + d * model["vocab_size"]


def train_step_flops(model: dict) -> float:
    """Flops one update requires: forward and backward of the whole batch."""
    tokens = model["train_batch_size"] * model["sequence_length"]
    return 3 * 2 * forward_macs_per_token(model) * tokens
