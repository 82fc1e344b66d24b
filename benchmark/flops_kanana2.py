"""Operations the mathematics of a Kanana-2 update needs, from a
configuration's sizes: the yardstick of `step_mfu.kanana2` and
`mla_attention_roofline`.

Nothing here reads a compiled program: the same work is counted whatever
kernels, walks, remat or dispatch the program uses, and recomputation counts
nothing.  Counted, as multiply-adds a token of the forward pass (2 flops
each, times three for forward plus backward):

- every matrix product: the latent attention's projections (q over
  `num_attention_heads` at `qk_nope_head_dim + qk_rope_head_dim`, the latent
  and the decoupled key, the latent's expansion to keys and values, the
  output), the dense feed-forward, the router, the shared experts, the head;
- attention as the triangle the causal mask leaves: a query row of a
  sequence of S meets (S + 1) / 2 keys on average, at `qk_nope + qk_rope`
  for the scores and `v_head_dim` for the values, every head;
- the held routed experts at their expected load: of a token's `topk`
  picks, `experts_held / experts` fall here.

Norms, the rotation, the softmax, the gather and sort of the dispatch and
the loss are not counted.
"""
from __future__ import annotations

KINDS = ("mla", "gated_feed_forward", "routed_moe")


def kinds(model: dict):
    """The kind of every block part kept, in order."""
    return [model["block_config"][c]["layer"][-1].split("-")[0]
            for row in model["block_schedule"] for c in row]


def _heads(model: dict) -> int:
    return model.get("num_attention_heads") or model["heads"]


def _widths(model: dict):
    """(query and key width, value width) of a head."""
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
            model["v_head_dim"])


def part_macs_per_token(model: dict) -> dict:
    """Forward multiply-adds a token of one block part of each kind."""
    d = model["heads"] * model["features_per_head"]
    h, s = _heads(model), model["sequence_length"]
    q_dim, v_dim = _widths(model)
    latent, rope = model["kv_lora_rank"], model["qk_rope_head_dim"]
    mla = (d * h * q_dim + d * (latent + rope)
           + latent * h * (model["qk_nope_head_dim"] + v_dim)
           + h * v_dim * d + h * (s + 1) / 2 * (q_dim + v_dim))
    expert = 3 * d * model["moe_intermediate_size"]
    spec = next(b["layer"][-1] for b in model["block_config"]
                if b["layer"][-1].startswith("routed_moe")).split("-")
    topk = next(int(e[4:]) for e in spec if e.startswith("topk"))
    shared = next((int(e[6:]) for e in spec if e.startswith("shared")), 0)
    moe = (d * model["experts"] + shared * expert
           + topk * model["experts_held"] / model["experts"] * expert)
    dense = 3 * d * int(d * model["intermediate_feed_forward_multiplier"])
    return {"mla": mla, "gated_feed_forward": dense, "routed_moe": moe}


def forward_macs_per_token(model: dict) -> float:
    part = part_macs_per_token(model)
    d = model["heads"] * model["features_per_head"]
    return sum(part[kind] for kind in kinds(model)) + d * model["vocab_size"]


def train_step_flops(model: dict) -> float:
    """Flops one update requires: forward and backward of the whole batch."""
    tokens = model["train_batch_size"] * model["sequence_length"]
    return 3 * 2 * forward_macs_per_token(model) * tokens


def attention(model: dict, act_bytes: int = 2) -> dict:
    """Required flops and boundary bytes of the attention proper of ONE
    latent-attention layer (what the scope `mla_/attention` holds), forward
    and backward, for the whole batch.  Forward: two products over the
    triangle; reads q, k, v (a key per head: the decoupled part broadcast),
    writes the output and one float32 statistic a row.  Backward: the
    transposes of both products (twice the forward's flops); reads q, k, v,
    the output, the statistic and the output's cotangent, writes the three
    gradients."""
    b, s, h = model["train_batch_size"], model["sequence_length"], _heads(
        model)
    q_dim, v_dim = _widths(model)
    pairs = s * (s + 1) // 2
    forward = 2 * b * h * (q_dim + v_dim) * pairs
    qk = b * s * h * q_dim * act_bytes                  # q or k
    vo = b * s * h * v_dim * act_bytes                  # v or o
    stat = b * s * h * 4
    return {
        "forward": {"flops": forward, "bytes": 2 * qk + 2 * vo + stat},
        "backward": {"flops": 2 * forward,
                     "bytes": 4 * qk + 4 * vo + stat},
    }


def attention_passes(model: dict) -> list:
    """The work of the latent attention proper of one update."""
    return [work for kind in kinds(model) if kind == "mla"
            for work in attention(model).values()]
