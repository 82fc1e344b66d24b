"""Causal softmax attention in blocks of query rows and of keys.

``[B, H, S, S]`` scores of a long sequence do not fit (8.6 GB a sequence of
8,192 tokens over 32 heads in float32), so the rows are taken ``rows`` at a
time against the keys up to the block's last row (the triangle the mask
leaves, not the square), and those keys ``rows`` at a time with a running
maximum, sum and output (the online softmax), in float32.  Each tile is
recomputed in the backward, so only the running triple outlives it.  The
tiles are unrolled (the blocks' key counts differ): ``S / rows`` blocks of
rows, ``(S / rows + 1) / 2`` tiles each on average.

Square tiles are what the chip's compiler handles well here: with the
softmax taken over whole rows of 8,192 keys it ran the row maximum and the
exponentials at a tenth of this form's rate (498 against 50 ms for the
forward of one layer at 2 x 8,192 tokens, 32 heads; my chip run, PR 27).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..nd import einsum_f32


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _tile(carry, q, k, v, ahead: int):
    """One tile of the online softmax: rows ``q [B, H, R, D]`` against keys
    ``k [B, H, T, D]``, the first of which lies ``ahead`` positions before
    the first row.  ``carry`` is the running (maximum, sum, output).
    Recomputed in the backward: only the carry outlives a tile."""
    top, total, out = carry
    scores = einsum_f32("bhrd,bhtd->bhrt", q, k)
    if k.shape[2] > ahead:             # a tile on the diagonal
        seen = (ahead + jnp.arange(q.shape[2]))[:, None] >= jnp.arange(
            k.shape[2])[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
    # the result does not depend on the running maximum: no gradient
    new_top = jax.lax.stop_gradient(jnp.maximum(top, jnp.max(scores, -1)))
    weights = jnp.exp(scores - new_top[..., None])
    keep = jnp.exp(top - new_top)
    out = out * keep[..., None] + einsum_f32(
        "bhrt,bhtd->bhrd", weights.astype(v.dtype), v)
    return new_top, total * keep + jnp.sum(weights, -1), out


def causal_attention(q, k, v, rows: int = 1024):
    """``softmax(q k^T + causal) v`` for ``q, k [B, S, H, D]`` (``q`` already
    scaled) and ``v [B, S, H, Dv]``."""
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    s = q.shape[2]
    blocks = []
    for first in range(0, s, rows):
        mine = q[:, :, first:first + rows]
        if blocks:
            # one block of rows after the other: left free, the compiler
            # runs many blocks' tiles at once and their scores do not fit
            mine, blocks[-1] = jax.lax.optimization_barrier(
                (mine, blocks[-1]))
        carry = (jnp.full(mine.shape[:3], -jnp.inf, jnp.float32),
                 jnp.zeros(mine.shape[:3], jnp.float32),
                 jnp.zeros(mine.shape[:3] + v.shape[-1:], jnp.float32))
        for at in range(0, min(first + rows, s), rows):
            carry = _tile(carry, mine, k[:, :, at:at + rows],
                          v[:, :, at:at + rows], first - at)
        blocks.append((carry[2] / carry[1][..., None]).astype(v.dtype))
    out = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=2)
    return jnp.swapaxes(out, 1, 2)
