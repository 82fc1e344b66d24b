"""Triangular bias-map attention for long sequences (the 32ctx FLOP lever).

The mixer attention ``out[b,s,h,k] = sum_{t<=s} bias[h,s,t] * val[b,t,h,k]``
(reference spatial.py:19-23,65-75) is a masked [S,S]@[S,K] matmul.  XLA
executes the FULL rectangle (the causal mask only zeroes operands), and at
seq 2048 the seq^2 map family is over half the 32ctx step's 46.4 TFLOP —
the step is compute-bound at 50.6% MFU (docs/perf/README.md), so skipping
the strictly-upper-triangular tile products is the lever that pays there:
(n+1)/2n of the tile matmuls at n = S/256 row tiles (56% at n=8), applied
to the forward AND both backward contractions.

Round 2 measured a whole-[S,S]-resident variant (ops/pallas_attn.py) LOSING
10-25% at the flagship's seq 512 — that step is HBM-bound, where a FLOP
skip buys nothing.  This module is the large-S redesign: row/column PANELS
of the map are blocked per grid cell and the triangular inner loop runs as
a ``fori_loop`` over dynamic 256-aligned slices (mosaic supports
lane-dynamic reads/writes at these alignments — probed on v5e).  Block
residency is sized for the 16 MB scoped-VMEM limit: the fwd/dval value and
cotangent panels split the per-head key axis across the grid (a full-batch
[B,S,K] panel measured 18.25 MB double-buffered — over the limit), and the
dbias kernel walks per-batch value blocks while its [TILE,S] f32 row panel
accumulates across the batch grid axis (b fastest, init at b==0).

Three kernels:

- fwd   (grid hk,i,b): bias row panel [T,S] x val half-panel -> out rows
- dval  (grid hk,j,b): bias col panel [S,T]^T x dout half-panel -> dval
- dbias (grid h,i,b):  dout rows x val^T -> dbias row panel [T,S] f32

The kernels keep the model's [B,S,H,K] activation layout ((head,key) viewed
as one lane axis — no relayouts) and never materialize the masked bias,
removing the mask-multiply traffic as a side effect.  Dtype walk matches
nd.einsum: calculation-dtype operands, f32 MXU accumulation, cast on exit
(dbias accumulates f32 across batch and casts outside the kernel).

Single-device (same guard as the other fused kernels); the GSPMD/sharded
paths keep the einsum chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TILE = 256
KSPLIT = 128  # lane-axis half-panel width for the fwd/dval value blocks


def _diag_mask(t: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return row >= col


def _fwd_kernel(bias_ref, val_ref, out_ref, *, n_tiles: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    b = pl.program_id(2)
    f32 = jnp.float32
    t = TILE
    k = out_ref.shape[-1]

    def body(j, acc):
        bt = bias_ref[0, :, pl.ds(j * t, t)]
        vt = val_ref[b, pl.ds(j * t, t), :]
        return acc + jnp.dot(bt, vt, preferred_element_type=f32)

    acc = jax.lax.fori_loop(0, i, body, jnp.zeros((t, k), f32))
    # diagonal tile: rows i*t+r see columns <= their own position
    bt = bias_ref[0, :, pl.ds(i * t, t)]
    bt = jnp.where(_diag_mask(t), bt, jnp.zeros_like(bt))
    vt = val_ref[b, pl.ds(i * t, t), :]
    acc = acc + jnp.dot(bt, vt, preferred_element_type=f32)
    out_ref[0] = acc.astype(out_ref.dtype)


def _dval_kernel(bias_ref, dout_ref, dval_ref, *, n_tiles: int):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    b = pl.program_id(2)
    f32 = jnp.float32
    t = TILE
    k = dval_ref.shape[-1]
    cdims = (((0,), (0,)), ((), ()))  # bias^T: contract the row axis

    def body(i, acc):
        bt = bias_ref[0, pl.ds(i * t, t), :]
        dt = dout_ref[b, pl.ds(i * t, t), :]
        return acc + jax.lax.dot_general(bt, dt, cdims,
                                         preferred_element_type=f32)

    acc = jax.lax.fori_loop(j + 1, n_tiles, body, jnp.zeros((t, k), f32))
    bt = bias_ref[0, pl.ds(j * t, t), :]
    bt = jnp.where(_diag_mask(t), bt, jnp.zeros_like(bt))
    dt = dout_ref[b, pl.ds(j * t, t), :]
    acc = acc + jax.lax.dot_general(bt, dt, cdims,
                                    preferred_element_type=f32)
    dval_ref[0] = acc.astype(dval_ref.dtype)


def _dbias_kernel(dout_ref, val_ref, dbias_ref, *, n_tiles: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    b = pl.program_id(2)
    f32 = jnp.float32
    t = TILE
    cdims = (((1,), (1,)), ((), ()))  # contract the key axis

    @pl.when(b == 0)
    def _zero():
        dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    dt = dout_ref[0]

    def body(j, _):
        vt = val_ref[0, pl.ds(j * t, t), :]
        prod = jax.lax.dot_general(dt, vt, cdims,
                                   preferred_element_type=f32)
        dbias_ref[0, :, pl.ds(j * t, t)] += prod
        return 0

    jax.lax.fori_loop(0, i, body, 0)
    vt = val_ref[0, pl.ds(i * t, t), :]
    prod = jax.lax.dot_general(dt, vt, cdims, preferred_element_type=f32)
    prod = jnp.where(_diag_mask(t), prod, jnp.zeros_like(prod))
    dbias_ref[0, :, pl.ds(i * t, t)] += prod


def _grid_call(kern, grid, specs, out_spec, out_shape, interpret, *args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kern, grid=grid, in_specs=specs, out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*args)


def _ksplit(key: int) -> int:
    return KSPLIT if key % KSPLIT == 0 and key > KSPLIT else key


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd(bias, val, interpret: bool = False):
    from jax.experimental import pallas as pl

    n_b, seq, n_h, key = val.shape
    n = seq // TILE
    ks = _ksplit(key)
    splits = key // ks  # key half-panels per head; grid axis 0 = h*splits
    val2 = val.reshape(n_b, seq, n_h * key)
    out = _grid_call(
        functools.partial(_fwd_kernel, n_tiles=n),
        (n_h * splits, n, n_b),
        [pl.BlockSpec((1, TILE, seq),
                      lambda hk, i, b: (hk // splits, i, 0)),
         # full-batch per-(head, key-half) value panel: constant across the
         # row/batch grid axes, sized to half the double-buffered VMEM limit
         pl.BlockSpec((n_b, seq, ks), lambda hk, i, b: (0, 0, hk))],
        pl.BlockSpec((1, TILE, ks), lambda hk, i, b: (b, i, hk)),
        jax.ShapeDtypeStruct(val2.shape, val.dtype),
        interpret, bias, val2)
    return out.reshape(val.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dval(bias, dout, interpret: bool = False):
    from jax.experimental import pallas as pl

    n_b, seq, n_h, key = dout.shape
    n = seq // TILE
    ks = _ksplit(key)
    splits = key // ks
    dout2 = dout.reshape(n_b, seq, n_h * key)
    dval = _grid_call(
        functools.partial(_dval_kernel, n_tiles=n),
        (n_h * splits, n, n_b),
        [pl.BlockSpec((1, seq, TILE),
                      lambda hk, j, b: (hk // splits, 0, j)),
         pl.BlockSpec((n_b, seq, ks), lambda hk, j, b: (0, 0, hk))],
        pl.BlockSpec((1, TILE, ks), lambda hk, j, b: (b, j, hk)),
        jax.ShapeDtypeStruct(dout2.shape, dout.dtype),
        interpret, bias, dout2)
    return dval.reshape(dout.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dbias(dout, val, interpret: bool = False):
    from jax.experimental import pallas as pl

    n_b, seq, n_h, key = val.shape
    n = seq // TILE
    val2 = val.reshape(n_b, seq, n_h * key)
    dout2 = dout.reshape(n_b, seq, n_h * key)
    dbias = _grid_call(
        functools.partial(_dbias_kernel, n_tiles=n),
        (n_h, n, n_b),
        [pl.BlockSpec((1, TILE, key), lambda h, i, b: (b, i, h)),
         # per-batch value block (a full-batch panel would double-buffer
         # over the VMEM limit); refetched per grid step — ~0.6 ms/call of
         # overlapped DMA at the 32ctx shape
         pl.BlockSpec((1, seq, key), lambda h, i, b: (b, 0, h))],
        pl.BlockSpec((1, TILE, seq), lambda h, i, b: (h, i, 0)),
        jax.ShapeDtypeStruct((n_h, seq, seq), jnp.float32),
        interpret, dout2, val2)
    return dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def tri_map_attention(bias, val, interpret: bool = False):
    """out[b,s,h,k] = sum_{t<=s} bias[h,s,t] * val[b,t,h,k].

    bias [H,S,S] UNMASKED (the causal triangle is applied in-kernel);
    val [B,S,H,K]; both in the calculation dtype.  Equivalent to
    ``einsum(bias * tril, val)`` with nd.einsum's f32-accumulate policy;
    executes only the lower-triangle tile products."""
    return _fwd(bias, val, interpret=interpret)


def _tri_vjp_fwd(bias, val, interpret: bool = False):
    return _fwd(bias, val, interpret=interpret), (bias, val)


def _tri_vjp_bwd(interpret, res, dout):
    bias, val = res
    d_val = _dval(bias, dout, interpret=interpret)
    d_bias = _dbias(dout, val, interpret=interpret)
    return d_bias.astype(bias.dtype), d_val


tri_map_attention.defvjp(_tri_vjp_fwd, _tri_vjp_bwd)


def tri_reference(bias, val):
    """Masked-einsum oracle (the unfused model path's math)."""
    seq = bias.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
    masked = bias * (row >= col).astype(bias.dtype)
    out = jnp.einsum("hst,bthk->bshk", masked, val,
                     preferred_element_type=jnp.float32)
    return out.astype(val.dtype)


def tri_eligible(seq: int, key: int, n_b: int, backend: str) -> bool:
    """Tiling + residency constraints: 256-aligned seq, lane-aligned key,
    and the full-batch (key-split) value half-panel must fit VMEM
    double-buffered next to a bias panel."""
    ks = KSPLIT if key % KSPLIT == 0 and key > KSPLIT else key
    return (backend in ("tpu", "cpu")
            and seq % TILE == 0
            and key % 128 == 0
            and n_b * seq * ks * 2 * 2 <= 11 * 1024 * 1024)
