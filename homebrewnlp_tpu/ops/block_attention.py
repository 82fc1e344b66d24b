"""Causal softmax attention in blocks of query rows and of keys.

``[B, H, S, S]`` scores of a long sequence do not fit (8.6 GB a sequence of
8,192 tokens over 32 heads in float32), so the rows are taken a block at a
time against the keys up to the block's last row (the triangle the mask
leaves, not the square), and those keys a block at a time with a running
maximum, sum and output (the online softmax), in float32.

Two layers call it (``models/hybrid.py``): ``mla`` with as many K/V heads
as query heads, and ``gqa``, whose ``k`` and ``v`` have fewer heads (query
head ``h`` reads K/V head ``h // group``) and whose sliding layers pass a
``window``: a row then sees its last ``window`` positions alone, itself
among them, and only the key tiles that meet that band are computed.

What runs where is decided by the operands' shape alone (:func:`walk`;
any group and any window run on every path), counted as each call is
traced (``WALK_EVENT``, which ``obs/compile_log.py`` counts as
``hbnlp_attention_path_total{path}`` in the registry) and said in the log
once a shape.  A sequence of whole blocks of 512 at head widths of
whole or half lane tiles runs as Mosaic kernels of ``ops/pallas_mla.py``,
forward and backward of one ``custom_vjp``: a tile's scores, running maximum
and sum, exponentials and weights stay in VMEM, only ``q``, ``k``, ``v``, the
output and one float32 statistic a row (the log of its sum of exponentials)
cross HBM, and the backward recomputes each tile from them.  Two walks:

- ``resident`` where a head's keys, values and their gradients fit VMEM
  whole (``pallas_mla.vmem_bytes``: up to 10,752 tokens at the latent
  attention's 192 / 128 widths; ``kimi_linear_48b``: 8,192 tokens, keys 192
  wide, values 128; ``mellum2_12b``: 8,192 tokens, 32 query heads over 4
  K/V heads, 128 wide, window 1,024 on three layers of four): a cell a block
  of rows, the head's keys held over its blocks (on one v5e, 14.6 ms a
  forward and 44.2 forward and backward at the Kimi cell's shape, against
  49.2 and 164.4 for the tiles);
- ``key_blocks`` past that (``kanana2_30b``: 32,768 tokens): the grid walks
  a head's keys ``pallas_mla.KEYS`` at a time, the forward and ``dQ`` a
  block of rows against its cells of keys, ``dK`` and ``dV`` a cell of keys
  against its blocks of rows; nothing held grows with the sequence.

Both round where ``_tile`` does: scores from operands in their own type
added up in float32; mask, maximum, exponentials, sum and the output
accumulator float32; the weights rounded to ``v``'s type before the second
product; the output rounded once at the end; in the backward the weights,
``dS`` and ``dO`` enter their products in the operands' type and every
gradient is summed in float32 and rounded once.

Every other shape (the toy configurations, odd sequence lengths, narrow
heads) takes ``rows x rows`` tiles unrolled in XLA (``unrolled``), which
are also the kernels' oracle: each tile is recomputed in the backward, so
only the running triple outlives it, and the blocks' key counts differ, so
the tiles are unrolled: ``S / rows`` blocks of rows, ``(S / rows + 1) / 2``
tiles each on average, every one through HBM several times.

Square tiles are what the chip's compiler handles well in that form: with
the softmax taken over whole rows of 8,192 keys it ran the row maximum and
the exponentials at a tenth of this form's rate (498 against 50 ms for the
forward of one layer at 2 x 8,192 tokens, 32 heads; my chip run, PR 27).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from ..nd import einsum_f32
from .pallas_mla import (BLOCK, VMEM_BYTES, flash_attention,
                         key_block_attention, key_chunk, vmem_bytes)

#: the walks :func:`causal_attention` can take, by :func:`walk`
WALKS = ("resident", "key_blocks", "unrolled")
#: the ``jax.monitoring`` event each call emits as it is traced, with its
#: walk as ``path``; ``obs/compile_log.py`` counts it in the registry as
#: ``hbnlp_attention_path_total{path}``
WALK_EVENT = "/hbnlp/attention/walk"


@functools.partial(jax.checkpoint, static_argnums=(4, 5))
def _tile(carry, q, k, v, ahead: int, window=None):
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
    far = window is not None and ahead + q.shape[2] > window
    if far:                            # a tile across the band's far edge
        near = (ahead + jnp.arange(q.shape[2]))[:, None] - jnp.arange(
            k.shape[2])[None, :] < window
        scores = jnp.where(near, scores, -jnp.inf)
    # the result does not depend on the running maximum: no gradient
    new_top = jax.lax.stop_gradient(jnp.maximum(top, jnp.max(scores, -1)))
    if far:
        # a row the far edge hides this whole tile from, before its first key
        new_top = jnp.where(new_top > -jnp.inf, new_top, 0.0)
    weights = jnp.exp(scores - new_top[..., None])
    keep = jnp.exp(top - new_top)
    out = out * keep[..., None] + einsum_f32(
        "bhrt,bhtd->bhrd", weights.astype(v.dtype), v)
    return new_top, total * keep + jnp.sum(weights, -1), out


def takes_kernels(q, v) -> bool:
    """Whether ``ops/pallas_mla.py``'s kernels run this shape: the sequence a
    whole number of their blocks (so a tile fills the matrix unit) and the
    head widths whole or half lane tiles."""
    s, d, d_v = q.shape[1], q.shape[-1], v.shape[-1]
    return s % BLOCK == 0 and d % 64 == 0 and d_v % 64 == 0


def walk(q, v) -> str:
    """Which of :data:`WALKS` this shape takes: the kernels that hold a
    head's keys, values and their gradients in VMEM where those fit
    (``pallas_mla.vmem_bytes``), else the kernels that walk the keys in
    cells through the grid; the unrolled tiles where no kernel runs."""
    if not takes_kernels(q, v):
        return "unrolled"
    s, d, d_v = q.shape[1], q.shape[-1], v.shape[-1]
    if vmem_bytes(s, d, d_v, q.dtype.itemsize) <= VMEM_BYTES:
        return "resident"
    return "key_blocks"


def _count(path: str, q, k, v, window) -> None:
    """The walk of one call, emitted as it is traced, and said once a
    shape."""
    jax.monitoring.record_event(WALK_EVENT, path=path)
    _say_once(path, tuple(q.shape), tuple(k.shape), tuple(v.shape),
              str(q.dtype), window)


@functools.lru_cache(maxsize=None)
def _say_once(path: str, q, k, v, dtype: str, window) -> None:
    what = (f"causal attention q {list(q)} k {list(k)} v {list(v)} {dtype}"
            f"{'' if window is None else f' window {window}'}")
    if path == "unrolled":
        logging.getLogger(__name__).warning(
            "%s runs as unrolled tiles in XLA: the sequence is no whole "
            "number of %d-blocks or a head width no multiple of 64", what,
            BLOCK)
    elif path == "key_blocks":
        logging.getLogger(__name__).info(
            "%s runs as the key-block Mosaic kernels, %d keys a cell", what,
            key_chunk(q[1]))
    else:
        logging.getLogger(__name__).info(
            "%s runs as the resident Mosaic kernels", what)


def causal_attention(q, k, v, rows: int = 1024, window=None):
    """``softmax(q k^T + mask) v`` for ``q [B, S, H, D]`` (already scaled),
    ``k [B, S, H / group, D]`` and ``v [B, S, H / group, Dv]``: a row sees
    the keys up to itself and, with ``window``, only the last ``window`` of
    them; ``rows`` is the unrolled tiles' size."""
    path = walk(q, v)
    _count(path, q, k, v, window)
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if path == "unrolled":
        out = _unrolled_tiles(q, k, v, rows, window)
    else:
        from . import pallas_interpret
        if path == "resident":
            out = flash_attention(q, k, v, BLOCK, window, pallas_interpret())
        else:
            out = key_block_attention(q, k, v, BLOCK, key_chunk(q.shape[2]),
                                      window, pallas_interpret())
    return jnp.swapaxes(out, 1, 2)


def _unrolled_tiles(q, k, v, rows: int, window=None):
    """The same for heads-major ``[B, H, S, .]`` by the online softmax over
    unrolled ``rows x rows`` tiles in XLA; a group's K/V head is repeated
    plainly (the small shapes that come here can afford it, and autodiff
    sums its gradient over the group)."""
    s, group = q.shape[2], q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
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
            if window is not None and first - (at + rows - 1) >= window:
                continue                # the whole tile lies before the band
            carry = _tile(carry, mine, k[:, :, at:at + rows],
                          v[:, :, at:at + rows], first - at, window)
        blocks.append((carry[2] / carry[1][..., None]).astype(v.dtype))
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=2)
