"""Fused mixer-block pallas kernel: the bytes lever for the map-attention
blocks (VERDICT r4 item 4).

The mixer configs' second block (configs/32mixer_group.json /
32big_mixer.json, reference semantics spatial.py:65-75 + frontend chain)
is the 5-layer chain

    n1  = norm_{scale1,shift1}(x)          # per-head, over features
    a1  = (bias1 . causal) @ n1            # learned [H,S,S] map, masked
    n2  = norm_{scale2,shift2}(a1)
    g   = gelu(n2)
    out = (bias2 . causal) @ g

on a ``[B, S, H, K]`` activation.  Under XLA every arrow above is a
separate HLO with a full ``[B,S,H,K]`` HBM round-trip, and the backward
doubles it with recompute reads plus f32 grad temporaries.  Per (batch,
head) slice, however, the whole chain is a pair of tiny ``[S,S] @ [S,K]``
matmuls with elementwise glue — it fits VMEM whole.  This kernel runs the
chain (forward) and its entire vjp (backward) per ``(head, batch-block)``
grid cell — each cell covers ``_block_rows`` batch rows (python-unrolled),
amortizing the per-cell bias load, causal-mask build and DMA latency: the
forward reads x and writes out ONCE; the backward reads x and d(out) once,
writes dx once, recomputes the internals in VMEM (remat-in-kernel — the
same FLOPs XLA's remat executes, for a fraction of the bytes), and
accumulates the parameter gradients (dbias1, dbias2, dscale/dshift) in f32
across the batch grid axis.

Layout notes.  XLA stores the residual stream of the mixer models as
``[B,S,H,K]{1,3,2,0}``: physically ``[B,H,K,S]``, the sequence in the lanes
(read off the v5e compiler's HLO, PERF.md §7).  The kernels therefore take
and return activations as ``[B,H,K,S]``: ``x.transpose(0, 2, 3, 1)`` is a
bitcast of what XLA already holds, the custom call takes its neighbour's
fusion directly, and a grid cell works on transposed ``[K, S]`` tiles
(block ``(n_bt, 1, K, S)``).  Inside, the chain is the same chain written
for that tile: norm moments reduce over axis 0, ``a1T = n1T @ b1m^T``,
``outT = gT @ b2m^T``; backward ``dgT = doutT @ b2m``, ``db2 = doutT^T @
gT`` (likewise ``dn1T``, ``db1``).  The tiny ``[H, K]`` scale / shift
vectors ride as ``[H, K, 1]`` columns with a ``(1, K, 1)`` per-head block:
they broadcast along a tile's lanes as they are, and a head-blocked window
needs no in-kernel dynamic indexing.

Two boundaries that cost more (v5e compiles of the 32mixer_group model,
never timed on their own; the first was the code until PR 26):

* a flat ``[B, S, H*K]`` view with ``[S, K]`` column slices a head asks XLA
  for row-major ``[B,S,H*K]``, so every activation crossing a call was
  transposed through HBM, in and out: two 268 MB copies a call, 192 an
  update, 15% of that cell's step (ledger, PR 25);
* rank 4 as the program names it, ``[B,S,H,K]`` row-major, leaves no copy at
  the call but makes XLA re-lay the neighbours (norm, the stream's adds):
  more copies in the module than the flat view had.

The ``optimization_barrier`` in ``_to_kernel`` pins the transpose to the
call.  Without it XLA sinks the transpose through the residual add that
produced x, which splits that add in two (one in kernel order for the call,
one in stream order for everybody else); the stream's own add then has no
consumer that needs it in memory, XLA re-derives every stream value from
all earlier blocks' outputs, and the step's temporaries grow with depth
(11.3 GB against 3.4 GB at depth 8; depth 32 does not fit a v5e).
tests/tpu_compile_test.py guards both the bitcasts and this.

Numerics match the unfused chain's dtype walk: norms compute in f32 from
the stored dtype (models/layers.py::norm), map matmuls take
calculation-dtype operands with f32 MXU accumulation and cast back
(nd.einsum policy), gelu runs in the calculation dtype.  Bit-parity with
XLA is NOT expected in bf16 (the fusion changes rounding order, like any
remat/fusion change — guarded the same way, by the real-corpus trajectory
check); f32 parity is pinned in tests/model_test.py.

The kernel is single-device (used under jit on an unsharded mesh; the
GSPMD/sharded paths keep the unfused chain).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp


def _norm_fwd(x32: jnp.ndarray, scale: jnp.ndarray, shift: jnp.ndarray,
              axis: int = 1) -> jnp.ndarray:
    """models/layers.py::norm on one 2-D f32 slice whose features run along
    ``axis`` (``[S, K]`` rows with ``[K]`` scale / shift by default, ``[K, S]``
    columns with ``[K, 1]`` for ``axis=0``): one-pass moments, clamped var,
    affine fold."""
    vec = (1, -1) if axis == 1 else (-1, 1)
    m1 = jnp.mean(x32, axis=axis, keepdims=True)
    m2 = jnp.mean(x32 * x32, axis=axis, keepdims=True)
    var = jnp.maximum(m2 - m1 * m1, 0.0)
    mul = jax.lax.rsqrt(var + 1e-5) * scale.reshape(vec)
    return x32 * mul + (shift.reshape(vec) - m1 * mul)


def _norm_bwd(x32: jnp.ndarray, scale: jnp.ndarray, dy: jnp.ndarray,
              axis: int = 1) -> typing.Tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """vjp of _norm_fwd wrt (x, scale, shift), all f32; dscale / dshift come
    back in scale's own shape (``[K]`` for ``axis=1``, ``[K, 1]`` for 0)."""
    m1 = jnp.mean(x32, axis=axis, keepdims=True)
    m2 = jnp.mean(x32 * x32, axis=axis, keepdims=True)
    var = jnp.maximum(m2 - m1 * m1, 0.0)
    r = jax.lax.rsqrt(var + 1e-5)
    xhat = (x32 - m1) * r
    u = dy * scale.reshape((1, -1) if axis == 1 else (-1, 1))
    dx = r * (u - jnp.mean(u, axis=axis, keepdims=True)
              - xhat * jnp.mean(u * xhat, axis=axis, keepdims=True))
    dscale = jnp.sum(dy * xhat, axis=1 - axis, keepdims=axis == 0)
    dshift = jnp.sum(dy, axis=1 - axis, keepdims=axis == 0)
    return dx, dscale, dshift


def _causal(seq: int, dtype) -> jnp.ndarray:
    row = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
    return (row >= col).astype(dtype)


def _dot(a, b, contract_a: int, contract_b: int):
    """a . b over the given axes on the MXU, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((contract_a,), (contract_b,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _chain_fwd_tiles(xt, b1m, b2m, s1, sh1, s2, sh2, cdtype):
    """Forward chain on one transposed [K, S] slice; returns (outT,
    intermediates), every tile [K, S].  ``a1 = b1m @ n1`` reads
    ``a1T = n1T @ b1m^T`` here.  Dtype walk mirrors the unfused layers: f32
    norms, cdtype matmul operands with f32 accumulation, cdtype gelu."""
    n1 = _norm_fwd(xt.astype(jnp.float32), s1, sh1, axis=0).astype(cdtype)
    a1 = _dot(n1, b1m, 1, 1).astype(cdtype)
    n2 = _norm_fwd(a1.astype(jnp.float32), s2, sh2, axis=0).astype(cdtype)
    g = jax.nn.gelu(n2)
    out = _dot(g, b2m, 1, 1).astype(cdtype)
    return out, (n1, a1, n2, g)


def _load_params(b1_ref, b2_ref, s1_ref, sh1_ref, s2_ref, sh2_ref, seq: int,
                 cdtype):
    """The cell's head: masked [S, S] maps and f32 [K, 1] norm columns."""
    mask = _causal(seq, cdtype)
    vecs = (r[0].astype(jnp.float32)
            for r in (s1_ref, sh1_ref, s2_ref, sh2_ref))
    return (mask, b1_ref[0] * mask, b2_ref[0] * mask, *vecs)


def _fwd_kernel(x_ref, b1_ref, b2_ref, s1_ref, sh1_ref, s2_ref, sh2_ref,
                out_ref, *, seq: int, n_bt: int):
    cdtype = x_ref.dtype
    _, b1m, b2m, s1, sh1, s2, sh2 = _load_params(
        b1_ref, b2_ref, s1_ref, sh1_ref, s2_ref, sh2_ref, seq, cdtype)
    for i in range(n_bt):  # unrolled: amortizes mask/bias setup + grid DMA
        out, _ = _chain_fwd_tiles(x_ref[i, 0], b1m, b2m, s1, sh1, s2, sh2,
                                  cdtype)
        out_ref[i, 0] = out


def _bwd_kernel(x_ref, b1_ref, b2_ref, s1_ref, sh1_ref, s2_ref, sh2_ref,
                dout_ref, dx_ref, db1_ref, db2_ref, ds1_ref, dsh1_ref,
                ds2_ref, dsh2_ref, *, seq: int, n_bt: int):
    from jax.experimental import pallas as pl

    cdtype = x_ref.dtype
    f32 = jnp.float32
    b = pl.program_id(1)  # batch is the fastest grid axis: accumulate here

    mask, b1m, b2m, s1, sh1, s2, sh2 = _load_params(
        b1_ref, b2_ref, s1_ref, sh1_ref, s2_ref, sh2_ref, seq, cdtype)
    maskf = mask.astype(f32)

    db1 = db2 = ds1 = dsh1 = ds2 = dsh2 = None
    acc = lambda t, u: u if t is None else t + u
    for i in range(n_bt):  # unrolled over the cell's batch rows
        x = x_ref[i, 0]
        # recompute the forward internals in VMEM (remat-in-kernel)
        _, (n1, a1, n2, g) = _chain_fwd_tiles(x, b1m, b2m, s1, sh1, s2,
                                              sh2, cdtype)
        dout = dout_ref[i, 0]
        # out = b2m @ g, all tiles transposed: dgT = doutT @ b2m,
        # db2 = dout @ g^T = doutT^T @ gT
        dg = _dot(dout, b2m, 1, 0)
        db2 = acc(db2, _dot(dout, g, 0, 0))
        # g = gelu(n2) in cdtype (vjp evaluated in f32 of the cdtype-rounded
        # n2, matching the unfused chain's value to rounding); the vjp
        # cotangent comes back in n2's dtype — grads accumulate in f32
        _, gelu_vjp = jax.vjp(lambda t: jax.nn.gelu(t.astype(f32)), n2)
        (dn2,) = gelu_vjp(dg)
        dn2 = dn2.astype(f32)
        # n2 = norm(a1)
        da1, ds2_i, dsh2_i = _norm_bwd(a1.astype(f32), s2, dn2, axis=0)
        da1c = da1.astype(cdtype)
        # a1 = b1m @ n1
        dn1 = _dot(da1c, b1m, 1, 0)
        db1 = acc(db1, _dot(da1c, n1, 0, 0))
        # n1 = norm(x)
        dx, ds1_i, dsh1_i = _norm_bwd(x.astype(f32), s1, dn1, axis=0)
        dx_ref[i, 0] = dx.astype(dx_ref.dtype)
        ds1 = acc(ds1, ds1_i)
        dsh1 = acc(dsh1, dsh1_i)
        ds2 = acc(ds2, ds2_i)
        dsh2 = acc(dsh2, dsh2_i)
    db1 = db1 * maskf
    db2 = db2 * maskf

    # parameter grads accumulate across the batch grid axis in f32; every
    # param block window is per-head and moves only when the head
    # coordinate advances, so each re-inits at b == 0 and accumulates
    # across the (fastest) batch axis
    @pl.when(b == 0)
    def _init():
        db1_ref[0] = db1
        db2_ref[0] = db2
        ds1_ref[0] = ds1
        dsh1_ref[0] = dsh1
        ds2_ref[0] = ds2
        dsh2_ref[0] = dsh2

    @pl.when(b != 0)
    def _acc():
        db1_ref[0] += db1
        db2_ref[0] += db2
        ds1_ref[0] += ds1
        dsh1_ref[0] += dsh1
        ds2_ref[0] += ds2
        dsh2_ref[0] += dsh2


def _block_rows(n_b: int, seq: int, key: int) -> int:
    """Batch rows per grid cell: amortize the per-cell bias load + mask
    build + DMA latency, bounded by a ~14 MB VMEM budget for the backward's
    ~12 live [S,K]-f32 tiles per row."""
    budget = 14 * 1024 * 1024 // max(1, 12 * seq * key * 4)
    bt = max(1, min(8, budget))
    while n_b % bt:
        bt -= 1
    return bt


def _specs(seq: int, key: int, n_bt: int):
    from jax.experimental import pallas as pl
    # activations cross as [B, H, K, S] (see "Layout notes"): per-head block
    # = n_bt [K, S] tiles; maps blocked per head
    x_spec = pl.BlockSpec((n_bt, 1, key, seq), lambda h, b: (b, h, 0, 0))
    map_spec = pl.BlockSpec((1, seq, seq), lambda h, b: (h, 0, 0))
    # [H,K] vectors ride as [H,K,1] columns with a (1,K,1) per-head block:
    # they broadcast along the lanes of a [K,S] tile as they are, and a
    # head-blocked window needs no in-kernel dynamic indexing at all
    vec_spec = pl.BlockSpec((1, key, 1), lambda h, b: (h, 0, 0))
    return x_spec, map_spec, vec_spec


def _to_kernel(x):
    """[B,S,H,K] as the program names it -> [B,H,K,S], the order XLA stores
    the stream in (a bitcast of its ``{1,3,2,0}`` layout).  The barrier
    keeps the transpose at the call: "Layout notes" above."""
    return jax.lax.optimization_barrier(x).transpose(0, 2, 3, 1)


def _from_kernel(xt):
    return xt.transpose(0, 3, 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_pallas(x, bias1, bias2, scale1, shift1, scale2, shift2,
                interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b, seq, n_h, key = x.shape
    n_bt = _block_rows(n_b, seq, key)
    x_spec, map_spec, vec_spec = _specs(seq, key, n_bt)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, seq=seq, n_bt=n_bt),
        grid=(n_h, n_b // n_bt),
        in_specs=[x_spec, map_spec, map_spec, vec_spec, vec_spec, vec_spec,
                  vec_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((n_b, n_h, key, seq), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(_to_kernel(x), bias1, bias2, scale1[..., None], shift1[..., None],
      scale2[..., None], shift2[..., None])
    return _from_kernel(out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_pallas(x, bias1, bias2, scale1, shift1, scale2, shift2, dout,
                interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_b, seq, n_h, key = x.shape
    n_bt = _block_rows(n_b, seq, key)
    x_spec, map_spec, vec_spec = _specs(seq, key, n_bt)
    f32 = jnp.float32
    vec3 = (n_h, key, 1)
    outs = (jax.ShapeDtypeStruct((n_b, n_h, key, seq), x.dtype),   # dx
            jax.ShapeDtypeStruct(bias1.shape, f32),                # dbias1
            jax.ShapeDtypeStruct(bias2.shape, f32),                # dbias2
            jax.ShapeDtypeStruct(vec3, f32),                       # dscale1
            jax.ShapeDtypeStruct(vec3, f32),                       # dshift1
            jax.ShapeDtypeStruct(vec3, f32),                       # dscale2
            jax.ShapeDtypeStruct(vec3, f32))                       # dshift2
    res = pl.pallas_call(
        functools.partial(_bwd_kernel, seq=seq, n_bt=n_bt),
        grid=(n_h, n_b // n_bt),
        in_specs=[x_spec, map_spec, map_spec, vec_spec, vec_spec, vec_spec,
                  vec_spec, x_spec],
        out_specs=(x_spec, map_spec, map_spec, vec_spec, vec_spec, vec_spec,
                   vec_spec),
        out_shape=outs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(_to_kernel(x), bias1, bias2, scale1[..., None], shift1[..., None],
      scale2[..., None], shift2[..., None], _to_kernel(dout))
    dx, db1, db2, ds1, dsh1, ds2, dsh2 = res
    return (_from_kernel(dx), db1, db2, ds1[..., 0], dsh1[..., 0],
            ds2[..., 0], dsh2[..., 0])


def mixer_chain_reference(x, bias1, bias2, scale1, shift1, scale2, shift2):
    """The unfused chain as plain jnp on [B,S,H,K] (same math the layer
    stack composes) — parity oracle for the kernels."""
    cdtype = x.dtype
    f32 = jnp.float32
    mask = _causal(x.shape[1], cdtype)

    def norm(t, scale, shift):
        t32 = t.astype(f32)
        m1 = jnp.mean(t32, axis=-1, keepdims=True)
        m2 = jnp.mean(t32 * t32, axis=-1, keepdims=True)
        var = jnp.maximum(m2 - m1 * m1, 0.0)
        mul = jax.lax.rsqrt(var + 1e-5) * scale[None, None].astype(f32)
        add = shift[None, None].astype(f32) - m1 * mul
        return (t32 * mul + add).astype(cdtype)

    def apply_map(bias, v):
        bm = bias * mask[None]
        out = jnp.einsum("hst,bthk->bshk", bm, v,
                         preferred_element_type=f32)
        return out.astype(cdtype)

    n1 = norm(x, scale1, shift1)
    a1 = apply_map(bias1, n1)
    n2 = norm(a1, scale2, shift2)
    g = jax.nn.gelu(n2)
    return apply_map(bias2, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def fused_mixer_block(x, bias1, bias2, scale1, shift1, scale2, shift2,
                      interpret: bool = False):
    """norm -> masked-map attention -> norm -> gelu -> masked-map attention
    in one pallas kernel (fwd) + one kernel for the full vjp (bwd).

    x: [B,S,H,K]; bias*: [H,S,S]; scale/shift*: [H,K] (all in the
    calculation dtype).  Param cotangents come back in the primal dtype
    (f32-accumulated in-kernel, cast on exit — nd.einsum's policy)."""
    return _fwd_pallas(x, bias1, bias2, scale1, shift1, scale2, shift2,
                       interpret=interpret)


def _fused_fwd(x, bias1, bias2, scale1, shift1, scale2, shift2,
               interpret: bool = False):
    out = _fwd_pallas(x, bias1, bias2, scale1, shift1, scale2, shift2,
                      interpret=interpret)
    return out, (x, bias1, bias2, scale1, shift1, scale2, shift2)


def _fused_bwd(interpret, res, dout):
    x, bias1, bias2, scale1, shift1, scale2, shift2 = res
    dx, db1, db2, ds1, dsh1, ds2, dsh2 = _bwd_pallas(
        x, bias1, bias2, scale1, shift1, scale2, shift2, dout,
        interpret=interpret)
    return (dx, db1.astype(bias1.dtype), db2.astype(bias2.dtype),
            ds1.astype(scale1.dtype), dsh1.astype(shift1.dtype),
            ds2.astype(scale2.dtype), dsh2.astype(shift2.dtype))


fused_mixer_block.defvjp(_fused_fwd, _fused_bwd)
