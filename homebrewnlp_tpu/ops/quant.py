"""Quantized-compute layer: int8/fp8 matmuls for the DSL linear family.

The grouped-mixer workload sits at 0.31 algorithmic MFU and is ABOVE its
bandwidth bound after the round-5 fusion experiments (docs/perf/README.md
round 5b: moving fewer bytes was measured REJECT), so the remaining lever
is making the MXU math itself cheaper.  TPU MXUs run int8 matmuls at 2-4x the
bf16 rate (and fp8 at 2x on v5p+); this module provides the quantized
forward path behind the ``quant_blocks`` / ``quant_dtype`` config knobs
(docs/performance.md "Low-precision compute"):

- **Dynamic symmetric quantization, scales computed in-graph** — no
  calibration pass, no extra state: ``per_tensor_scale`` /
  ``per_channel_scale`` reduce |max| at trace time, so every step
  re-derives its own scales from the live values.
- **W8A8 forward** (``quant_einsum``): activations are quantized per
  output row (per-token — the kept, non-contracted axes), weights per
  output channel; the contraction runs as a quantized ``dot_general`` with
  **f32 accumulation** pinned by ``preferred_element_type`` (exact for
  int8 products; the classic silent-failure mode of int8 paths is an s8
  or bf16 accumulator), then the two scale vectors multiply back in f32
  and the result casts to the calculation dtype.
- **High-precision backward** (``custom_vjp``): the residuals are the
  UN-quantized operands and the backward is the ordinary
  calculation-dtype (bf16) einsum pair with f32 accumulation — i.e. a
  straight-through estimator through the rounding: quantized forward,
  exactly the gradients of the unquantized contraction.  Training
  stability rides on the backward, which is why it stays high-precision.

Default-off contract: with ``quant_blocks`` unset, ``models/linear.py``
never calls into this module and the graph is bit-identical to the
pre-quant one (parity-tested at 8 and 300 steps like
``telemetry_interval=0`` before it).
The graftcheck ``quant-dtype`` graph rule pins the complement: an int8/fp8
op in a config that declares no quant scope — or a declared scope whose
traced train step contains NO quantized dot (a silent high-precision
fallback) — fails static analysis (docs/static_analysis.md).
"""
from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from ..nd import NT, contraction_spec

#: quant_dtype knob -> jnp dtype.  fp8 uses e4m3 (the forward-pass format:
#: 3 mantissa bits, +-448 range); e5m2 is a gradient format and the
#: backward here stays bf16 anyway.
QUANT_DTYPES: typing.Dict[str, typing.Any] = {"int8": jnp.int8}
if hasattr(jnp, "float8_e4m3fn"):  # toolchain-gated
    QUANT_DTYPES["fp8"] = jnp.float8_e4m3fn

#: symmetric range limit per quant dtype ("qmax"): values quantize into
#: [-qmax, qmax].  int8 uses 127 (not 128) so the range stays symmetric;
#: fp8_e4m3fn's largest finite is 448.
_QMAX = {"int8": 127.0, "fp8": 448.0}

_EPS = 1e-12  # scale floor: an all-zero operand must not divide by zero


def supported(quant_dtype: str) -> bool:
    """True when this toolchain can represent ``quant_dtype``."""
    return quant_dtype in QUANT_DTYPES


# -- scale computation (in-graph, dynamic) -----------------------------------

def per_tensor_scale(x: jnp.ndarray, quant_dtype: str = "int8") -> jnp.ndarray:
    """One f32 scalar scale: amax(|x|) / qmax, floored away from zero."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jnp.maximum(amax / _QMAX[quant_dtype], _EPS)


def per_channel_scale(x: jnp.ndarray, reduce_axes: typing.Sequence[int],
                      quant_dtype: str = "int8") -> jnp.ndarray:
    """Per-channel f32 scales: amax over ``reduce_axes`` (the contracted
    axes), keeping one scale per kept-axis coordinate.  With
    ``reduce_axes`` covering every axis this degenerates to (a rank-0)
    ``per_tensor_scale``."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=tuple(reduce_axes))
    return jnp.maximum(amax / _QMAX[quant_dtype], _EPS)


def quantize(x: jnp.ndarray, scale: jnp.ndarray,
             quant_dtype: str = "int8") -> jnp.ndarray:
    """Symmetric quantization: round(x/scale) clipped to the dtype range.
    ``scale`` broadcasts against ``x`` (scalar for per-tensor; the caller
    reshapes per-channel scales)."""
    qmax = _QMAX[quant_dtype]
    v = jnp.clip(x.astype(jnp.float32) / scale, -qmax, qmax)
    if quant_dtype == "int8":
        v = jnp.round(v)
    return v.astype(QUANT_DTYPES[quant_dtype])


def dequantize(q: jnp.ndarray, scale: jnp.ndarray,
               dtype=jnp.float32) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# -- the quantized contraction ----------------------------------------------

def _parse_spec(spec: str) -> typing.Tuple[str, str, str]:
    ins, out = spec.split("->")
    x_l, w_l = ins.split(",")
    return x_l, w_l, out


def _channel_scale_for(arr: jnp.ndarray, letters: str, out_letters: str,
                       qname: str) -> typing.Tuple[jnp.ndarray, jnp.ndarray]:
    """(broadcastable-to-output scale, quantized operand) for one einsum
    operand: scales reduce over the operand's contracted axes (one scale
    per kept coordinate — per-token for activations, per-channel for
    weights), then transpose/reshape into the output letter order."""
    reduce_axes = [i for i, l in enumerate(letters) if l not in out_letters]
    kept = [l for l in letters if l in out_letters]
    if not kept:
        s = per_tensor_scale(arr, qname)
        return s, quantize(arr, s, qname)
    s = per_channel_scale(arr, reduce_axes, qname)
    # quantize wants the scale aligned to the OPERAND layout
    op_shape = [arr.shape[i] if l in out_letters else 1
                for i, l in enumerate(letters)]
    q = quantize(arr, s.reshape(op_shape), qname)
    # dequant wants it aligned to the OUTPUT layout: kept letters arrive in
    # operand order — permute into output order, then broadcast-reshape
    perm = sorted(range(len(kept)), key=lambda i: out_letters.index(kept[i]))
    s = jnp.transpose(s, perm)
    out_shape = []
    it = iter(s.shape)
    for l in out_letters:
        out_shape.append(next(it) if l in kept else 1)
    return s.reshape(out_shape), q


def _reference_einsum(spec: str, x: jnp.ndarray, w: jnp.ndarray,
                      out_dtype) -> jnp.ndarray:
    """The high-precision twin of the quantized contraction (nd.einsum's
    accumulation policy: f32 accumulator, cast back) — the backward below
    differentiates exactly this."""
    return jnp.einsum(spec, x, w,
                      preferred_element_type=jnp.float32).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qdot(x: jnp.ndarray, w: jnp.ndarray, spec: str, qname: str
          ) -> jnp.ndarray:
    x_l, w_l, out_l = _parse_spec(spec)
    sx, xq = _channel_scale_for(x, x_l, out_l, qname)
    sw, wq = _channel_scale_for(w, w_l, out_l, qname)
    # the quantized MXU contraction: int8 x int8 (or fp8 x fp8) operands,
    # f32 accumulation pinned — this dot_general is what the graftcheck
    # quant-dtype census counts
    acc = jnp.einsum(spec, xq, wq, preferred_element_type=jnp.float32)
    return (acc * sx * sw).astype(x.dtype)


def _qdot_fwd(x, w, spec, qname):
    return _qdot(x, w, spec, qname), (x, w)


def _qdot_bwd(spec, qname, res, g):
    x, w = res
    # high-precision grads: differentiate the unquantized contraction on
    # the stored (calculation-dtype) operands — straight-through through
    # the forward rounding
    _, vjp = jax.vjp(
        lambda a, b: _reference_einsum(spec, a, b, x.dtype), x, w)
    return vjp(g)


_qdot.defvjp(_qdot_fwd, _qdot_bwd)


def quant_einsum(x: NT, w: NT, out_names: typing.Sequence[str],
                 quant_dtype: str = "int8") -> NT:
    """Quantized twin of ``nd.einsum([x, w], out_names)``: same named
    contraction semantics (the spec comes from the same
    ``nd.contraction_spec`` builder, so the twins cannot drift), W8A8
    forward, high-precision backward."""
    out_names = tuple(out_names)
    spec = contraction_spec([x, w], out_names)
    return NT(_qdot(x.x, w.x, spec, quant_dtype), out_names)


# -- scope selection ---------------------------------------------------------

def scope_matches(quant_blocks: typing.Sequence[str], scope_path: str) -> bool:
    """True when any ``quant_blocks`` entry occurs in the model scope path
    (the DSL layer names ARE the scope components, models/ctx.py, so
    ``"bottleneck_group_linear"`` selects every linear inside that layer;
    note substring semantics — ``"group_linear"`` also matches the
    bottleneck layer, use ``"/group_linear"`` to select only the plain
    per-head linear)."""
    return any(s in scope_path for s in quant_blocks)


def eligible(cfg, tensor: NT) -> bool:
    """Static (trace-time) eligibility of one linear call: the knob is on,
    the dtype is representable on this toolchain, and the operand is a
    float tensor (the quantizer is meaningless on integer ids)."""
    return (bool(cfg.quant_blocks)
            and supported(cfg.quant_dtype)
            and jnp.issubdtype(tensor.dtype, jnp.floating))


def pattern_quantized(cfg, layer_specs: typing.Sequence[str]) -> bool:
    """True when any layer of a fused-kernel pattern falls inside the
    declared quant scope — the fused pallas path (ops/pallas_mixer.py)
    runs its own unquantized matmuls, so fusion must yield to quantization
    or the declared scope would silently fall back (exactly what the
    graftcheck quant-dtype rule rejects).

    Each layer name is tested as a SYNTHESIZED scope path fragment
    (``block_/<name>_/``) rather than the bare name, so this check agrees
    with the path ``linear()`` matches against: a slash-anchored entry like
    ``"/bottleneck_group_linear"`` (the documented disambiguation form)
    must disable fusion exactly when it would quantize the linear."""
    if not cfg.quant_blocks:
        return False
    names = [spec.split("-")[0] for spec in layer_specs]
    return any(scope_matches(cfg.quant_blocks, f"block_/{name}_/")
               for name in names)


__all__ = ["QUANT_DTYPES", "supported", "per_tensor_scale",
           "per_channel_scale", "quantize", "dequantize", "quant_einsum",
           "scope_matches", "eligible", "pattern_quantized"]
