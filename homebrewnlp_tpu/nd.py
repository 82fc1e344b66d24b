"""Named-axis tensor algebra over jax.numpy.

The reference expresses every model op over mtf named Dimensions; the layer DSL
depends on that algebra (axis-rotation attention, group linears, anonymize
markers — see /root/reference/src/utils_mtf.py).  This module provides the
minimal JAX-native equivalent: a :class:`NT` wrapper pairing a ``jnp.ndarray``
with a static tuple of axis names, plus einsum/reduce/broadcast helpers that
operate on names.  Unlike mtf this is pure tracing-time bookkeeping — XLA sees
ordinary arrays; there is no lowering step, and sharding is applied separately
via ``PartitionSpec`` keyed on the same names (parallel/sharding.py).
"""
from __future__ import annotations

import string
import typing

import jax
import jax.numpy as jnp

# -- axis-name registry ------------------------------------------------------
# Central registry of every logical axis name the framework may attach to an
# NT.  config.py registers its canonical dimension constants at import time;
# modules that invent additional axes (layer-local scratch axes and the like)
# register them where they are defined.  The registry is the ground truth for the graftcheck
# axis-literal lint (homebrewnlp_tpu/analysis/ast_rules.py): a string literal
# used in an axis position must resolve here, so a typoed axis name fails
# static analysis instead of silently building a mis-broadcast graph.
_KNOWN_AXES: typing.Set[str] = set()


def register_axis(*names: str) -> None:
    """Register logical axis names as valid (idempotent)."""
    _KNOWN_AXES.update(names)


def known_axes() -> typing.FrozenSet[str]:
    """Snapshot of every registered logical axis name."""
    return frozenset(_KNOWN_AXES)


# -- scope provider ----------------------------------------------------------
# Pointer at the model scope currently being built (pushed/popped by
# models/ctx.py's scope stack).  Two consumers: NT errors raised while a
# scope is active name the enclosing parameter path (diagnostics), and every
# push mirrors into ``jax.named_scope`` so compiled HLO instruction metadata
# (``op_name``) carries the layer path end to end — obs/profile.py joins
# profiler trace events against that metadata for per-layer device-time
# attribution (docs/observability.md "Profile attribution").
_SCOPE_STACK: typing.List[str] = []
_NAMED_SCOPE_CMS: typing.List[typing.Optional[typing.ContextManager]] = []


def push_scope(name: str) -> None:
    _SCOPE_STACK.append(name)
    # '@' is MLIR-special (symbol refs): a name containing it is scrubbed
    # from op_name entirely, so the depth token "@d0_x" emits as "d0_x"
    cm: typing.Optional[typing.ContextManager] = None
    try:
        cm = jax.named_scope(name.replace("@", ""))
        cm.__enter__()
    except Exception:
        cm = None
    _NAMED_SCOPE_CMS.append(cm)


def pop_scope() -> None:
    if _SCOPE_STACK:
        _SCOPE_STACK.pop()
        cm = _NAMED_SCOPE_CMS.pop()
        if cm is not None:
            try:
                cm.__exit__(None, None, None)
            except Exception:
                pass


def current_scope() -> str:
    """The innermost model scope path being built, or '' outside any scope."""
    return "/".join(_SCOPE_STACK)


@jax.tree_util.register_pytree_node_class
class NT:
    """A jnp array with named axes.  ``names`` is static metadata."""

    __slots__ = ("x", "names")

    def __init__(self, x: jnp.ndarray, names: typing.Sequence[str]):
        names = tuple(names)
        if hasattr(x, "ndim") and x.ndim != len(names):
            where = current_scope()
            raise ValueError(
                f"rank mismatch: array {x.shape} vs names {names}"
                + (f" (while building scope {where!r})" if where else ""))
        self.x = x
        self.names = names

    # pytree protocol
    def tree_flatten(self):
        return (self.x,), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        obj = object.__new__(cls)
        obj.x = children[0]
        obj.names = names
        return obj

    # -- introspection ------------------------------------------------------
    @property
    def shape(self) -> typing.Dict[str, int]:
        return dict(zip(self.names, self.x.shape))

    @property
    def dtype(self):
        return self.x.dtype

    @property
    def size(self) -> int:
        out = 1
        for s in self.x.shape:
            out *= s
        return out

    def dim_size(self, name: str) -> int:
        return self.x.shape[self.names.index(name)]

    def has(self, *names: str) -> bool:
        return all(n in self.names for n in names)

    def __repr__(self):
        return f"NT({dict(zip(self.names, getattr(self.x, 'shape', ())))}, {self.dtype})"

    # -- structural ops -----------------------------------------------------
    def rename(self, old: str, new: str) -> "NT":
        return NT(self.x, tuple(new if n == old else n for n in self.names))

    def astype(self, dtype) -> "NT":
        return NT(self.x.astype(dtype), self.names)

    def transpose_to(self, names: typing.Sequence[str]) -> "NT":
        names = tuple(names)
        if names == self.names:
            return self
        perm = [self.names.index(n) for n in names]
        return NT(self.x.transpose(perm), names)

    def expand(self, name: str, size: int, index: int = 0) -> "NT":
        """Insert a broadcast axis."""
        x = jnp.expand_dims(self.x, index)
        x = jnp.broadcast_to(x, x.shape[:index] + (size,) + x.shape[index + 1:])
        return NT(x, self.names[:index] + (name,) + self.names[index:])

    # -- arithmetic with name-based broadcasting ----------------------------
    def _binary(self, other, fn):
        if not isinstance(other, NT):
            return NT(fn(self.x, other), self.names)
        a, b = broadcast_union(self, other)
        return NT(fn(a.x, b.x), a.names)

    def __add__(self, other):
        return self._binary(other, jnp.add)

    def __radd__(self, other):
        return self._binary(other, lambda x, y: jnp.add(y, x))

    def __sub__(self, other):
        return self._binary(other, jnp.subtract)

    def __rsub__(self, other):
        return self._binary(other, lambda x, y: jnp.subtract(y, x))

    def __mul__(self, other):
        return self._binary(other, jnp.multiply)

    def __rmul__(self, other):
        return self._binary(other, lambda x, y: jnp.multiply(y, x))

    def __truediv__(self, other):
        return self._binary(other, jnp.divide)

    def __rtruediv__(self, other):
        return self._binary(other, lambda x, y: jnp.divide(y, x))

    def __neg__(self):
        return NT(-self.x, self.names)


def union_names(*tensors: NT) -> typing.Tuple[str, ...]:
    """Deduplicated concatenation of axis names, first-seen order (the mtf
    binary-op broadcast rule)."""
    seen: typing.List[str] = []
    for t in tensors:
        for n in t.names:
            if n not in seen:
                seen.append(n)
    return tuple(seen)


def broadcast_union(*tensors: NT) -> typing.List[NT]:
    names = union_names(*tensors)
    sizes = {}
    for t in tensors:
        sizes.update(t.shape)
    out = []
    for t in tensors:
        x = t.transpose_to([n for n in names if n in t.names])
        idx = 0
        for i, n in enumerate(names):
            if n not in t.names:
                x = NT(jnp.expand_dims(x.x, i), x.names[:i] + (n,) + x.names[i:])
        x = NT(jnp.broadcast_to(x.x, tuple(sizes[n] for n in names)), names)
        out.append(x)
    return out


_LETTERS = string.ascii_letters


def contraction_spec(inputs: typing.Sequence[NT],
                     out_names: typing.Sequence[str]) -> str:
    """The ``jnp.einsum`` spec string for a named contraction: axes mapped
    to letters in first-appearance order, everything absent from
    ``out_names`` contracted.  Shared by :func:`einsum` and its quantized
    twin (ops/quant.py::quant_einsum) so the two cannot drift."""
    out_names = tuple(out_names)
    mapping: typing.Dict[str, str] = {}
    for t in inputs:
        for n in t.names:
            if n not in mapping:
                mapping[n] = _LETTERS[len(mapping)]
    for n in out_names:
        if n not in mapping:
            raise ValueError(f"output axis {n} not present in any input")
    return (",".join("".join(mapping[n] for n in t.names) for t in inputs)
            + "->" + "".join(mapping[n] for n in out_names))


def einsum(inputs: typing.Sequence[NT], out_names: typing.Sequence[str],
           precision=None) -> NT:
    """Named einsum: contract all axes absent from ``out_names``."""
    out_names = tuple(out_names)
    spec = contraction_spec(inputs, out_names)
    # Accumulate half-precision matmuls in f32 (free on the MXU, strictly
    # better numerically — same policy as ops/losses.py) and cast the result
    # back to the input dtype so activation storage stays half-precision.
    in_dtype = inputs[0].dtype
    arrays = [t.x for t in inputs]
    if in_dtype in (jnp.bfloat16, jnp.float16):
        if jax.default_backend() in ("tpu", "gpu"):
            # native half-precision MXU dot with f32 accumulator
            x = jnp.einsum(spec, *arrays, precision=precision,
                           preferred_element_type=jnp.float32)
        else:
            # XLA:CPU's thunk runtime rejects BF16xBF16=F32 dots for some
            # shapes; upcast operands instead — bit-identical, since
            # half-precision products are exact in f32.
            x = jnp.einsum(spec, *[a.astype(jnp.float32) for a in arrays],
                           precision=precision)
        x = x.astype(in_dtype)
    else:
        x = jnp.einsum(spec, *arrays, precision=precision,
                       preferred_element_type=in_dtype)
    return NT(x, out_names)


def einsum_f32(spec: str, *arrays, precision=None):
    """``jnp.einsum`` over plain arrays that adds up in float32 and returns
    float32, under :func:`einsum`'s rule for half-precision operands."""
    if (arrays[0].dtype in (jnp.bfloat16, jnp.float16)
            and jax.default_backend() not in ("tpu", "gpu")):
        arrays = [a.astype(jnp.float32) for a in arrays]
    return jnp.einsum(spec, *arrays, precision=precision,
                      preferred_element_type=jnp.float32)


def _reduce(t: NT, fn, reduced: typing.Optional[typing.Sequence[str]] = None,
            out_names: typing.Optional[typing.Sequence[str]] = None) -> NT:
    if reduced is None:
        reduced = [n for n in t.names if n not in tuple(out_names or ())]
    axes = tuple(t.names.index(n) for n in reduced)
    names = tuple(n for n in t.names if n not in reduced)
    return NT(fn(t.x, axis=axes) if axes else t.x, names)


def reduce_sum(t: NT, reduced=None, out_names=None) -> NT:
    return _reduce(t, jnp.sum, reduced, out_names)


def reduce_mean(t: NT, reduced=None, out_names=None) -> NT:
    return _reduce(t, jnp.mean, reduced, out_names)


def reduce_max(t: NT, reduced=None, out_names=None) -> NT:
    return _reduce(t, jnp.max, reduced, out_names)


def reduce_min(t: NT, reduced=None, out_names=None) -> NT:
    return _reduce(t, jnp.min, reduced, out_names)


def nt_slice(t: NT, axis: str, start: int, end: int) -> NT:
    idx = t.names.index(axis)
    sl = [slice(None)] * len(t.names)
    sl[idx] = slice(start, end)
    return NT(t.x[tuple(sl)], t.names)


def concat(tensors: typing.Sequence[NT], axis: str) -> NT:
    """Concatenate along a named axis (reference utils_mtf.py:131-141 does this
    with an anonymize round-trip; XLA needs no such marker)."""
    names = tensors[0].names
    ts = [t.transpose_to(names) for t in tensors]
    return NT(jnp.concatenate([t.x for t in ts], axis=names.index(axis)), names)


def pad(t: NT, axis: str, before: int, after: int, value=0.0) -> NT:
    cfg = [(0, 0, 0)] * len(t.names)
    cfg[t.names.index(axis)] = (before, after, 0)
    return NT(jax.lax.pad(t.x, jnp.asarray(value, t.dtype), cfg), t.names)


def one_hot(t: NT, axis_name: str, depth: int, dtype=jnp.float32) -> NT:
    return NT(jax.nn.one_hot(t.x, depth, dtype=dtype), t.names + (axis_name,))


def arange(name: str, size: int, dtype=jnp.int32) -> NT:
    return NT(jnp.arange(size, dtype=dtype), (name,))


def cumsum(t: NT, axis: str) -> NT:
    return NT(jnp.cumsum(t.x, axis=t.names.index(axis)), t.names)


def stop_gradient(t: NT) -> NT:
    return NT(jax.lax.stop_gradient(t.x), t.names)


def zeros_like(t: NT) -> NT:
    return NT(jnp.zeros_like(t.x), t.names)


def cast(t: NT, dtype) -> NT:
    return t.astype(dtype)


def full(names: typing.Sequence[str], sizes: typing.Sequence[int], value, dtype) -> NT:
    return NT(jnp.full(tuple(sizes), value, dtype), tuple(names))


def compare_range(name0: str, size0: int, name1: str, size1: int, op, dtype) -> NT:
    """Causal-style mask from two iotas (reference utils_mtf.py:411-415)."""
    a = NT(jnp.arange(size0, dtype=jnp.int32)[:, None], (name0, name1))
    b = NT(jnp.arange(size1, dtype=jnp.int32)[None, :], (name0, name1))
    return NT(op(a.x, b.x).astype(dtype), (name0, name1))


def dedup(names: typing.Iterable[str]) -> typing.Tuple[str, ...]:
    seen: typing.List[str] = []
    for n in names:
        if n not in seen:
            seen.append(n)
    return tuple(seen)
