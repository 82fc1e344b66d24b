"""Pallas TPU kernel for causal bias-map ("mixer") attention.

The flagship mixer layers (configs/32big_mixer.json block 2) use attention
with a LEARNED per-head position-pair map and no dot-product: per layer

    out[b,s,h,k] = sum_{t<=s} bias[h,s,t] * val[b,t,h,k]

XLA executes this as mask-multiply + full [S,S]@[S,K] batched matmul — it
cannot skip the strictly-upper-triangular tiles the causal mask zeroes.  This
kernel tiles the row/col axes at the 128-lane MXU size and only issues the
lower-triangle tile matmuls (4 row tiles at S=512: 10 of 16 tile products,
asymptotically 2x fewer MXU FLOPs), masking just the diagonal tiles on the
VPU.  f32 accumulation, output cast back to the value dtype.

The backward pass stays in XLA einsums (jax.custom_vjp below).

**Status: evaluated and REJECTED for the production path** (docs/perf/
README.md): measured on a real v5e at flagship shapes the kernel is bit-exact
but 10-25% slower than the XLA masked einsum — XLA's batched-matmul
pipelining beats the 1.6x causal FLOP skip.  models/layers.py::attention
keeps the einsum (reference semantics: spatial.py:19-23,65-75); this module
remains as the measured experiment with an interpret-mode parity test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TILE = 128


def _fwd_kernel(bias_ref, val_ref, out_ref, *, seq: int, key: int):
    n = seq // TILE
    for i in range(n):
        width = (i + 1) * TILE
        b = bias_ref[0, i * TILE:(i + 1) * TILE, 0:width]
        # causal mask: row (i*TILE + r) sees columns <= that row; only the
        # last column tile is partial, but one fused where is VPU-cheap
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE, width), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (TILE, width), 1)
        b = jnp.where(row + i * TILE >= col, b, jnp.zeros_like(b))
        v = val_ref[0, 0:width, :]
        acc = jnp.dot(b, v, preferred_element_type=jnp.float32)
        out_ref[0, i * TILE:(i + 1) * TILE, :] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_pallas(bias: jnp.ndarray, val: jnp.ndarray, interpret: bool = False
                ) -> jnp.ndarray:
    """bias [H,S,S], val [B,S,H,K] -> out [B,S,H,K]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b, seq, n_h, key = val.shape
    # view the (head, key) pair as one lane axis so the per-head block is a
    # [seq, key] column slice — pallas requires the trailing block dims be
    # lane/sublane aligned, which a size-1 head axis is not
    val2 = val.reshape(n_b, seq, n_h * key)
    kern = functools.partial(_fwd_kernel, seq=seq, key=key)
    # batch is the fastest-varying grid axis: the bias block index is then
    # unchanged across consecutive steps, so pallas skips re-fetching the
    # [seq, seq] map for every batch row
    grid = (n_h, n_b)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, seq, seq), lambda h, b: (h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, seq, key), lambda h, b: (b, 0, h),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, seq, key), lambda h, b: (b, 0, h),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(val2.shape, val.dtype),
        interpret=interpret,
    )(bias, val2)
    return out.reshape(val.shape)


def _tril(seq: int, dtype) -> jnp.ndarray:
    row = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
    return (row >= col).astype(dtype)


def _fwd_einsum(bias: jnp.ndarray, val: jnp.ndarray) -> jnp.ndarray:
    masked = (bias.astype(jnp.float32)
              * _tril(bias.shape[-1], jnp.float32)).astype(bias.dtype)
    out = jnp.einsum("hst,bthk->bshk", masked, val,
                     preferred_element_type=jnp.float32)
    return out.astype(val.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_map_attention(bias: jnp.ndarray, val: jnp.ndarray,
                         use_pallas: bool = True) -> jnp.ndarray:
    """out[b,s,h,k] = sum_{t<=s} bias[h,s,t] * val[b,t,h,k]."""
    if use_pallas:
        return _fwd_pallas(bias, val)
    return _fwd_einsum(bias, val)


def _vjp_fwd(bias, val, use_pallas):
    return causal_map_attention(bias, val, use_pallas), (bias, val)


def _vjp_bwd(use_pallas, res, d_out):
    bias, val = res
    tril = _tril(bias.shape[-1], jnp.float32)
    masked = (bias.astype(jnp.float32) * tril).astype(bias.dtype)
    d_val = jnp.einsum("hst,bshk->bthk", masked, d_out,
                       preferred_element_type=jnp.float32).astype(val.dtype)
    d_bias = jnp.einsum("bshk,bthk->hst", d_out, val,
                        preferred_element_type=jnp.float32)
    d_bias = (d_bias * tril).astype(bias.dtype)
    return d_bias, d_val


causal_map_attention.defvjp(_vjp_fwd, _vjp_bwd)


def pallas_eligible(seq: int, key: int, backend: str) -> bool:
    return (backend == "tpu" and seq % TILE == 0
            and key % TILE == 0)
