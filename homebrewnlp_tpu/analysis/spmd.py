"""graftspmd: static sharding propagation — census the *implicit* collectives.

The reference framework decides its communication layout statically
(SimdMeshImpl picks collectives from named-dim layouts at graph-build
time); the JAX port hands that decision to GSPMD, which inserts
all-reduces/all-gathers *silently* at compile time.  The census
(graph_rules.py) counts the manual collectives the traced jaxpr contains;
this module predicts the implicit ones the partitioner will add:

1. **Seed** every jaxpr input with the PartitionSpec the intended mesh
   would apply (``parallel/sharding.py::spec_for`` over the axis-name
   metadata the trace harness records per invar, ``StepTrace.in_axes``).
2. **Propagate** specs equation by equation — elementwise ops merge
   operand specs, ``dot_general``/reductions over a sharded dimension
   leave partial sums (⇒ implicit all-reduce / reduce-scatter), structural
   ops (transpose/reshape/broadcast/gather) remap dims, scan bodies
   multiply by trip count, ``shard_map`` regions are opaque (manual —
   the explicit census already prices them).
3. **Emit** an implicit-collective census — kind x mesh axis x payload
   bytes — plus a lint finding for every equation whose operands carry
   *conflicting* shardings (the classic accidental full replication).

Propagation is deliberately conservative: an input or intermediate whose
sharding cannot be tracked becomes UNKNOWN and stops charging (it can
under-count, never invent collectives), which is why the census is
validated against ground truth: on CPU-compilable configs
:func:`validate_hlo` lowers + compiles the real train step under the real
shardings and compares the predicted census with the collectives present
in the partitioned HLO text, within the documented tolerance
(:data:`HLO_TOLERANCE`).

Consumers: ``cost_model.step_resources`` (implicit bytes priced into the
roofline/mesh-search objective via ``StepResources.total_comm``), the
ratcheted ``implicit-collective`` graph rule (goldens under
``analysis/goldens/spmd/``), and ``tools/graftspmd.py``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import typing

from .findings import Finding
from .trace import ConfigTraces, StepTrace, eqn_location

GOLDENS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens")

#: growth past the recorded implicit-byte budget that fails the ratchet
RATCHET_RATIO = 1.10

#: HLO cross-validation tolerance: per-device collective payload bytes in
#: the partitioned HLO may differ from the prediction by at most this
#: factor (XLA's all-reduce combiner merges ops and GSPMD may choose
#: reduce-scatter/all-gather decompositions; bytes are conserved far
#: better than op counts), with :data:`HLO_BYTES_SLACK` absolute slack for
#: scalar bookkeeping collectives the propagation does not model.
HLO_TOLERANCE = 2.0
HLO_BYTES_SLACK = 64 * 1024
#: op-count slack: |predicted - lowered| <= max(HLO_COUNT_SLACK,
#: 0.5 * max(predicted, lowered))
HLO_COUNT_SLACK = 4

#: collective op spellings in optimized/partitioned HLO text.  The census
#: itself emits two families: ``psum`` (partial-sum contraction/reduction
#: over a sharded dim — lowered as all-reduce or reduce-scatter) and
#: ``all_gather`` (operand-sharding mismatch / gather-the-smaller-operand
#: strategy).
HLO_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")

# a spec is a tuple of (mesh axis | None) per dim; None (the whole spec)
# means UNKNOWN — propagation follows the known side and never charges
Spec = typing.Optional[typing.Tuple[typing.Optional[str], ...]]

_ELEMENTWISE = frozenset((
    "add", "sub", "mul", "div", "neg", "max", "min", "rem", "pow",
    "integer_pow", "exp", "log", "log1p", "expm1", "tanh", "logistic",
    "sqrt", "rsqrt", "cbrt", "abs", "sign", "floor", "ceil", "round",
    "erf", "erf_inv", "erfc", "sin", "cos", "clamp", "select_n",
    "and", "or", "xor", "not", "eq", "ne", "lt", "le", "gt", "ge",
    "is_finite", "square", "atan2", "nextafter", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "real", "imag",
    "stop_gradient", "copy", "reduce_precision", "add_any",
))

_IDENTITY = frozenset(("convert_element_type", "bitcast_convert_type",
                       "copy", "stop_gradient", "optimization_barrier"))

_REDUCE_PRIMS = frozenset(("reduce_sum", "reduce_prod", "reduce_max",
                           "reduce_min", "reduce_and", "reduce_or",
                           "reduce_xor", "argmax", "argmin",
                           "reduce_logsumexp"))

_OPAQUE_CALLS = frozenset(("shard_map",))


def _nbytes(aval) -> int:
    from .memory import aval_nbytes
    return aval_nbytes(aval)


@dataclasses.dataclass
class ChargeOption:
    """One way the partitioner could realize an implicit collective:
    ``full_bytes`` is the unsharded value moved, ``shard_axes`` the mesh
    axes sharding it (the per-device divide)."""
    family: str
    full_bytes: int
    shard_axes: typing.Tuple[str, ...]


@dataclasses.dataclass
class ImplicitRecord:
    """One implicit collective, mesh-size-symbolically: ``axis`` is the
    mesh axis it crosses; ``options`` the partitioner's candidate
    lowerings (e.g. all-reduce the partial-sum output vs all-gather the
    smaller operand and contract locally) — census picks the cheapest, as
    GSPMD's own sharding strategies do.  Sizes are applied at census time
    so one propagation serves every candidate mesh with the same >1-axis
    mask (the mesh searcher's loop)."""
    axis: str
    options: typing.Tuple[ChargeOption, ...]
    count: int
    location: str


@dataclasses.dataclass
class Conflict:
    location: str
    prim: str
    detail: str


@dataclasses.dataclass
class SpmdResult:
    step: str
    mask: typing.FrozenSet[str]
    records: typing.List[ImplicitRecord]
    conflicts: typing.List[Conflict]
    seeded: bool  # False: no usable in_axes metadata — nothing charged
    error: str = ""


class _MaskMesh:
    """spec_for-compatible stand-in whose axes are 2 when active in the
    mask, 1 otherwise — propagation only cares *which* axes shard."""

    def __init__(self, mask: typing.FrozenSet[str]):
        from ..parallel.mesh import MESH_AXES
        self.shape = {a: (2 if a in mask else 1) for a in MESH_AXES}
        self.axis_names = tuple(self.shape)


def _dedup(parts: typing.List[typing.Optional[str]]) -> Spec:
    """A mesh axis may shard at most one dim; first occurrence wins (the
    same contract as spec_for)."""
    seen: typing.Set[str] = set()
    out: typing.List[typing.Optional[str]] = []
    for p in parts:
        if p is not None and p in seen:
            p = None
        elif p is not None:
            seen.add(p)
        out.append(p)
    return tuple(out)


def _spec_axes(spec: Spec) -> typing.Tuple[str, ...]:
    return tuple(p for p in (spec or ()) if p is not None)


class _Propagator:
    """One forward walk over a (Closed)Jaxpr tree under a fixed axis mask."""

    def __init__(self, mask: typing.FrozenSet[str]):
        self.mask = mask
        self.records: typing.List[ImplicitRecord] = []
        self.conflicts: typing.List[Conflict] = []

    # -- env helpers ---------------------------------------------------------
    def read(self, env: dict, v) -> Spec:
        if hasattr(v, "val"):  # Literal
            rank = len(getattr(getattr(v, "aval", None), "shape", ()) or ())
            return (None,) * rank
        return env.get(v)

    def write(self, env: dict, v, spec: Spec) -> None:
        aval = getattr(v, "aval", None)
        if aval is None:
            return
        rank = len(getattr(aval, "shape", ()) or ())
        if spec is not None and len(spec) != rank:
            spec = None  # shape/spec drift: degrade to unknown, never lie
        env[v] = spec

    # -- charging ------------------------------------------------------------
    def charge(self, family: str, axis: str, full_bytes: int,
               shard_axes: typing.Sequence[str], mult: int, eqn,
               alternatives: typing.Sequence[ChargeOption] = ()) -> None:
        if axis not in self.mask or full_bytes <= 0 or mult <= 0:
            return
        opts = (ChargeOption(family, int(full_bytes),
                             tuple(a for a in shard_axes if a != axis)),)
        opts += tuple(ChargeOption(o.family, int(o.full_bytes),
                                   tuple(a for a in o.shard_axes
                                         if a != axis))
                      for o in alternatives if o.full_bytes > 0)
        self.records.append(ImplicitRecord(
            axis=axis, options=opts, count=int(mult),
            location=eqn_location(eqn)))

    def conflict(self, eqn, detail: str) -> None:
        self.conflicts.append(Conflict(eqn_location(eqn),
                                       eqn.primitive.name, detail))

    def _merge_dim(self, a: typing.Optional[str], b: typing.Optional[str],
                   eqn, dim: int, other_aval, other_spec: Spec,
                   mult: int) -> typing.Optional[str]:
        """Merge one dim's sharding across two operands.  Sharded beats
        replicated (GSPMD slices the replicated side locally — no
        movement); two DIFFERENT axes conflict: the partitioner reshards
        (typically by gathering one side to replicated), charged as an
        implicit all_gather plus a lint conflict."""
        if a is None or a == b:
            return b if a is None else a
        if b is None:
            return a
        self.conflict(eqn, f"dim {dim} sharded {a!r} vs {b!r} — GSPMD "
                           f"reshards (typically gathers one side to "
                           f"replicated)")
        self.charge("all_gather", b, _nbytes(other_aval),
                    _spec_axes(other_spec), mult, eqn)
        return a

    def merge_specs(self, eqn, vars_specs, mult: int) -> Spec:
        """Elementwise merge: operands of the output's rank merge per dim;
        lower-rank operands (scalars and other already-broadcast values)
        are replicated riders and neither constrain nor conflict."""
        rank = len(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        known = [(v, s) for v, s in vars_specs
                 if s is not None and len(s) == rank]
        if not known:
            return None if any(s is None for _, s in vars_specs) \
                else (None,) * rank
        parts: typing.List[typing.Optional[str]] = [None] * rank
        for v, s in known:
            for d in range(rank):
                parts[d] = self._merge_dim(parts[d], s[d], eqn, d,
                                           getattr(v, "aval", None), s, mult)
        return _dedup(parts)

    # -- the walk ------------------------------------------------------------
    def run(self, jaxpr, in_specs: typing.Sequence[Spec], mult: int = 1
            ) -> typing.List[Spec]:
        """Propagate through one (Closed)Jaxpr; returns outvar specs."""
        inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
        env: dict = {}
        for v, s in zip(inner.invars, in_specs):
            self.write(env, v, s)
        for v in getattr(inner, "constvars", ()):
            aval = getattr(v, "aval", None)
            rank = len(getattr(aval, "shape", ()) or ())
            self.write(env, v, (None,) * rank)
        for eqn in inner.eqns:
            self.eqn(env, eqn, mult)
        return [self.read(env, v) for v in inner.outvars]

    def eqn(self, env: dict, eqn, mult: int) -> None:
        name = eqn.primitive.name
        specs = [self.read(env, v) for v in eqn.invars]
        handler = getattr(self, "_h_" + name.replace("-", "_"), None)
        if handler is not None:
            outs = handler(env, eqn, specs, mult)
        elif name in _OPAQUE_CALLS:
            outs = [None] * len(eqn.outvars)  # manual region: census owns it
        elif name in _REDUCE_PRIMS:
            outs = self._reduce(eqn, specs, mult)
        elif name in _IDENTITY:
            outs = [specs[0]]
        elif name in _ELEMENTWISE:
            outs = [self.merge_specs(eqn, list(zip(eqn.invars, specs)), mult)]
        else:
            outs = self._generic(eqn, specs, mult)
        for v, s in zip(eqn.outvars, list(outs) + [None] * len(eqn.outvars)):
            self.write(env, v, s)

    # -- structural handlers -------------------------------------------------
    def _h_transpose(self, env, eqn, specs, mult):
        s = specs[0]
        if s is None:
            return [None]
        perm = eqn.params["permutation"]
        return [tuple(s[p] for p in perm)]

    def _h_reshape(self, env, eqn, specs, mult):
        s = specs[0]
        if s is None or not _spec_axes(s):
            out_rank = len(eqn.outvars[0].aval.shape)
            return [(None,) * out_rank]
        in_shape = [int(d) for d in eqn.invars[0].aval.shape]
        out_shape = [int(d) for d in eqn.outvars[0].aval.shape]
        # map sharded input dims to same-size output dims left-to-right;
        # a sharded dim that merges/splits degrades to replicated (GSPMD
        # usually keeps these local — halo effects are noise at this level)
        parts: typing.List[typing.Optional[str]] = [None] * len(out_shape)
        j = 0
        for i, d in enumerate(in_shape):
            if s[i] is None:
                continue
            while j < len(out_shape) and out_shape[j] != d:
                j += 1
            if j < len(out_shape):
                parts[j] = s[i]
                j += 1
        return [_dedup(parts)]

    def _h_squeeze(self, env, eqn, specs, mult):
        s = specs[0]
        if s is None:
            return [None]
        dims = set(eqn.params["dimensions"])
        return [tuple(p for d, p in enumerate(s) if d not in dims)]

    def _h_expand_dims(self, env, eqn, specs, mult):
        s = specs[0]
        if s is None:
            return [None]
        dims = sorted(eqn.params["dimensions"])
        out = list(s)
        for d in dims:
            out.insert(d, None)
        return [tuple(out)]

    def _h_broadcast_in_dim(self, env, eqn, specs, mult):
        s = specs[0]
        out_shape = eqn.outvars[0].aval.shape
        parts: typing.List[typing.Optional[str]] = [None] * len(out_shape)
        if s is not None:
            in_shape = eqn.invars[0].aval.shape
            for i, od in enumerate(eqn.params["broadcast_dimensions"]):
                if int(in_shape[i]) == int(out_shape[od]):
                    parts[od] = s[i]
        return [_dedup(parts)]

    def _h_slice(self, env, eqn, specs, mult):
        return [self._dim_preserving(eqn, specs[0])]

    def _h_dynamic_slice(self, env, eqn, specs, mult):
        return [self._dim_preserving(eqn, specs[0])]

    def _h_pad(self, env, eqn, specs, mult):
        return [self._dim_preserving(eqn, specs[0])]

    def _h_rev(self, env, eqn, specs, mult):
        s = specs[0]
        if s is None:
            return [None]
        dims = set(eqn.params["dimensions"])
        return [tuple(None if d in dims else p for d, p in enumerate(s))]

    def _dim_preserving(self, eqn, s: Spec) -> Spec:
        """Keep sharding only on dims whose size is unchanged."""
        if s is None:
            return None
        in_shape = eqn.invars[0].aval.shape
        out_shape = eqn.outvars[0].aval.shape
        if len(in_shape) != len(out_shape):
            return None
        return tuple(p if int(a) == int(b) else None
                     for p, a, b in zip(s, in_shape, out_shape))

    def _h_dynamic_update_slice(self, env, eqn, specs, mult):
        return [specs[0]]

    def _h_scatter(self, env, eqn, specs, mult):
        return [specs[0]]

    def _h_scatter_add(self, env, eqn, specs, mult):
        """The embedding-gradient shape: data-sharded updates scatter-added
        into a replicated table — every shard holds a partial table grad,
        so GSPMD must all-reduce the OPERAND-sized result over each axis
        the updates/indices carry but the table does not."""
        out_axes = _spec_axes(specs[0])
        charged: typing.Set[str] = set()
        for i, s in enumerate(specs[1:], start=1):
            for axis in _spec_axes(s):
                if axis not in out_axes and axis not in charged:
                    charged.add(axis)
                    # alternative: gather the sharded updates and scatter
                    # locally (cheaper when the table dwarfs the updates)
                    alt = (ChargeOption("all_gather",
                                        _nbytes(eqn.invars[i].aval),
                                        _spec_axes(s)),)
                    self.charge("psum", axis, _nbytes(eqn.outvars[0].aval),
                                out_axes, mult, eqn, alternatives=alt)
        return [specs[0]]

    def _h_concatenate(self, env, eqn, specs, mult):
        known = [s for s in specs if s is not None]
        if len(known) != len(specs) or len(set(known)) != 1:
            return [None]
        dim = int(eqn.params["dimension"])
        s = list(known[0])
        if dim < len(s):
            s[dim] = None  # the concatenated dim's sharding does not survive
        return [tuple(s)]

    def _h_gather(self, env, eqn, specs, mult):
        """Embedding-lookup shape: a gather from a replicated/unknown table
        with KNOWN index sharding carries the index spec onto the output's
        gather-batch dims (offset dims — operand slices — replicate)."""
        idx_spec = specs[1] if len(specs) > 1 else None
        out_rank = len(eqn.outvars[0].aval.shape)
        if idx_spec is None:
            return [None]
        dn = eqn.params.get("dimension_numbers")
        offset = set(getattr(dn, "offset_dims", ()) or ())
        # index dims feeding the output, in order (drop the trailing
        # index-vector dim)
        idx_parts = list(idx_spec[:-1]) if len(idx_spec) else []
        parts: typing.List[typing.Optional[str]] = []
        k = 0
        for d in range(out_rank):
            if d in offset:
                parts.append(None)
            else:
                parts.append(idx_parts[k] if k < len(idx_parts) else None)
                k += 1
        return [_dedup(parts)]

    def _h_sharding_constraint(self, env, eqn, specs, mult):
        """A constraint pins dims it names; dims it leaves open KEEP the
        propagated sharding.  The annotation was baked at trace time under
        the LOCAL mesh (axes of size 1 are omitted by spec_for), so on the
        intended/candidate mesh it systematically under-specifies —
        overwriting would erase the very seeds the analysis planted."""
        spec = getattr(eqn.params.get("sharding"), "spec", None)
        rank = len(eqn.outvars[0].aval.shape)
        incoming = specs[0] if specs and specs[0] is not None \
            else (None,) * rank
        if spec is None:
            return [specs[0]]
        parts: typing.List[typing.Optional[str]] = []
        for d in range(rank):
            part = spec[d] if d < len(spec) else None
            axes = [a for a in (part if isinstance(part, tuple) else (part,))
                    if a is not None]
            parts.append(axes[0] if axes else incoming[d])
        return [_dedup(parts)]

    # -- compute handlers ----------------------------------------------------
    def _reduce(self, eqn, specs, mult):
        s = specs[0]
        if s is None:
            return [None] * len(eqn.outvars)
        dims = set(int(d) for d in (eqn.params.get("axes") or ()))
        out = tuple(p for d, p in enumerate(s) if d not in dims)
        out_aval = eqn.outvars[0].aval
        for d in sorted(dims):
            if d < len(s) and s[d] is not None:
                # cross-shard reduction: partial results per shard must be
                # combined — an implicit all-reduce of the OUTPUT (max/min
                # move the same payload as sums)
                self.charge("psum", s[d], _nbytes(out_aval),
                            _spec_axes(out), mult, eqn)
        return [out] * len(eqn.outvars)

    def _h_dot_general(self, env, eqn, specs, mult):
        ls, rs = specs[0], specs[1]
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        l_rank = len(eqn.invars[0].aval.shape)
        r_rank = len(eqn.invars[1].aval.shape)
        l = ls if ls is not None else (None,) * l_rank
        r = rs if rs is not None else (None,) * r_rank
        parts: typing.List[typing.Optional[str]] = []
        for i, (dl, dr) in enumerate(zip(lb, rb)):
            parts.append(self._merge_dim(
                l[dl], r[dr], eqn, dl, eqn.invars[1].aval, rs, mult))
        lfree = [d for d in range(l_rank) if d not in lc and d not in lb]
        rfree = [d for d in range(r_rank) if d not in rc and d not in rb]
        parts += [l[d] for d in lfree]
        parts += [r[d] for d in rfree]
        out = _dedup(parts)
        out_axes = _spec_axes(out)
        contracted: typing.List[str] = []
        for d in lc:
            if l[d] is not None and l[d] not in contracted:
                contracted.append(l[d])
        for d in rc:
            if r[d] is not None and r[d] not in contracted:
                contracted.append(r[d])
        out_aval = eqn.outvars[0].aval
        for axis in contracted:
            if axis in out_axes:
                continue  # the axis re-shards an output dim: local reuse
            # contraction over a sharded dim: GSPMD either all-reduces the
            # partial-sum output (reduce-scatter when the consumer wants
            # it sharded) or ALL-GATHERS the smaller operand over that
            # axis and contracts locally — offer both, census picks the
            # cheaper, exactly like the partitioner's strategy choice
            # (weight-stationary grad all-reduce vs gathering a weight
            # that is tiny next to a batch-sized activation)
            alts = []
            for i in (0, 1):
                s = (l, r)[i]
                if axis in _spec_axes(s):
                    alts.append(ChargeOption(
                        "all_gather", _nbytes(eqn.invars[i].aval),
                        _spec_axes(s)))
            self.charge("psum", axis, _nbytes(out_aval), out_axes, mult,
                        eqn, alternatives=alts)
        return [out]

    def _h_cumsum(self, env, eqn, specs, mult):
        return [None]

    def _h_cumlogsumexp(self, env, eqn, specs, mult):
        return [None]

    def _h_cummax(self, env, eqn, specs, mult):
        return [None]

    def _h_iota(self, env, eqn, specs, mult):
        return [(None,) * len(eqn.outvars[0].aval.shape)]

    # -- control flow --------------------------------------------------------
    def _h_pjit(self, env, eqn, specs, mult):
        sub = eqn.params.get("jaxpr")
        return self._call(sub, eqn, specs, mult)

    def _h_remat(self, env, eqn, specs, mult):
        return self._call(eqn.params.get("jaxpr"), eqn, specs, mult)

    def _h_checkpoint(self, env, eqn, specs, mult):
        return self._call(eqn.params.get("jaxpr"), eqn, specs, mult)

    def _h_custom_jvp_call(self, env, eqn, specs, mult):
        return self._call(eqn.params.get("call_jaxpr"), eqn, specs, mult)

    def _h_custom_vjp_call(self, env, eqn, specs, mult):
        return self._call(eqn.params.get("call_jaxpr"), eqn, specs, mult)

    def _h_custom_vjp_call_jaxpr(self, env, eqn, specs, mult):
        return self._call(eqn.params.get("fun_jaxpr"), eqn, specs, mult)

    def _call(self, sub, eqn, specs, mult):
        if sub is None:
            return self._generic(eqn, specs, mult)
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        if len(inner.invars) != len(specs):
            return self._generic(eqn, specs, mult)
        return self.run(sub, specs, mult)

    def _h_scan(self, env, eqn, specs, mult):
        sub = eqn.params.get("jaxpr")
        if sub is None:
            return [None] * len(eqn.outvars)
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        length = max(1, int(eqn.params.get("length", 1) or 1))
        body_in: typing.List[Spec] = []
        for i, s in enumerate(specs):
            if i < n_consts + n_carry or s is None:
                body_in.append(s)
            else:
                body_in.append(s[1:])  # xs lose the scan dim
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        if len(inner.invars) != len(body_in):
            return [None] * len(eqn.outvars)
        body_out = self.run(sub, body_in, mult * length)
        outs: typing.List[Spec] = []
        for i, s in enumerate(body_out):
            if i < n_carry or s is None:
                outs.append(s)
            else:
                outs.append((None,) + s)  # ys gain the scan dim
        return outs

    def _h_while(self, env, eqn, specs, mult):
        body = eqn.params.get("body_jaxpr")
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        if body is None:
            return [None] * len(eqn.outvars)
        body_in = specs[cn:]
        inner = body.jaxpr if hasattr(body, "jaxpr") else body
        if len(inner.invars) != len(body_in):
            return [None] * len(eqn.outvars)
        # trip count is dynamic: body charged once (documented undercount;
        # none of the audited steps loop collectives inside while bodies)
        outs = self.run(body, body_in, mult)
        del bn  # body outvars ARE the carry, matching eqn.outvars 1:1
        return outs if len(outs) == len(eqn.outvars) else \
            [None] * len(eqn.outvars)

    def _h_cond(self, env, eqn, specs, mult):
        branches = eqn.params.get("branches") or ()
        op_specs = specs[1:]
        best: typing.Optional[typing.List[Spec]] = None
        best_records: typing.List[ImplicitRecord] = []
        best_conflicts: typing.List[Conflict] = []
        best_cost = -1
        for br in branches:
            sub = _Propagator(self.mask)
            inner = br.jaxpr if hasattr(br, "jaxpr") else br
            if len(inner.invars) != len(op_specs):
                return [None] * len(eqn.outvars)
            outs = sub.run(br, op_specs, mult)
            # branch cost proxy: the lowered (first) option of each charge
            cost = sum(r.options[0].full_bytes * r.count
                       for r in sub.records if r.options)
            if cost > best_cost:
                best_cost = cost
                best, best_records = outs, sub.records
                best_conflicts = sub.conflicts
        if best is None:
            return [None] * len(eqn.outvars)
        # charge the costlier branch only (the other executes instead of,
        # not in addition to, it)
        self.records.extend(best_records)
        self.conflicts.extend(best_conflicts)
        return best

    def _generic(self, eqn, specs, mult):
        # recurse into a single sub-jaxpr call if arities line up; else the
        # outputs are unknown (conservative: never invents sharding)
        from .memory import _sub_jaxprs
        subs = list(_sub_jaxprs(eqn))
        if len(subs) == 1:
            inner = subs[0].jaxpr if hasattr(subs[0], "jaxpr") else subs[0]
            if len(inner.invars) == len(specs):
                outs = self.run(subs[0], specs, mult)
                if len(outs) == len(eqn.outvars):
                    return outs
        return [None] * len(eqn.outvars)


# -- entry points ------------------------------------------------------------

def _mask_of(imesh) -> typing.FrozenSet[str]:
    return frozenset(a for a, n in dict(imesh.shape).items() if int(n) > 1)


def propagate(st: StepTrace, imesh) -> SpmdResult:
    """Propagate shardings through one traced step under ``imesh``'s
    active axes.  Memoized per (trace, >1-axis mask): the mesh searcher
    prices many same-structure candidates from one walk."""
    mask = _mask_of(imesh)
    if not mask:
        # single-device mesh: no axis can shard anything, so no record or
        # conflict is reachable — skip the whole walk (the 1-chip configs
        # would otherwise pay a full jaxpr traversal per step inside the
        # CI's graftcheck wall budget for a guaranteed-empty census)
        seeded = st.in_axes is not None
        return SpmdResult(st.name, mask, [], [], seeded=seeded,
                          error="" if seeded
                          else "trace carries no per-input axis metadata")
    cache = getattr(st, "_spmd_cache", None)
    if cache is None:
        cache = {}
        st._spmd_cache = cache
    if mask in cache:
        return cache[mask]
    result = _propagate_uncached(st, mask)
    cache[mask] = result
    return result


def _propagate_uncached(st: StepTrace, mask: typing.FrozenSet[str]
                        ) -> SpmdResult:
    from ..parallel.sharding import spec_for
    inner = st.jaxpr.jaxpr if hasattr(st.jaxpr, "jaxpr") else st.jaxpr
    if st.in_axes is None:
        return SpmdResult(st.name, mask, [], [], seeded=False,
                          error="trace carries no per-input axis metadata")
    mesh = _MaskMesh(mask)
    seeds: typing.List[Spec] = []
    for v, names in zip(inner.invars, st.in_axes):
        rank = len(getattr(getattr(v, "aval", None), "shape", ()) or ())
        if names is None:
            seeds.append(None)
            continue
        if len(names) == 0:
            seeds.append((None,) * rank)  # explicitly replicated
            continue
        if len(names) != rank:
            # rank drift (e.g. stacked-pipeline axis metadata against an
            # unstacked decode leaf): seeding a truncated spec would plant
            # a WRONG sharding — unknown is the honest seed
            seeds.append(None)
            continue
        spec = spec_for(tuple(names), mesh)
        parts: typing.List[typing.Optional[str]] = []
        for part in spec:
            axes = [a for a in (part if isinstance(part, tuple) else (part,))
                    if a is not None]
            parts.append(axes[0] if axes else None)
        parts += [None] * (rank - len(parts))
        seeds.append(_dedup(parts[:rank]))
    prop = _Propagator(mask)
    try:
        prop.run(st.jaxpr, seeds)
    except Exception as e:  # pragma: no cover - surfaced as a rule error
        return SpmdResult(st.name, mask, prop.records, prop.conflicts,
                          seeded=True,
                          error=f"{type(e).__name__}: {e}")
    return SpmdResult(st.name, mask, prop.records, prop.conflicts,
                      seeded=True)


def census(result: SpmdResult, imesh, strategy: str = "lowered"
           ) -> typing.Dict[str, typing.Dict[str, typing.Dict[str, int]]]:
    """Concrete implicit census under ``imesh``: ``{family: {axis:
    {count, payload_bytes, bytes}}}``.  ``payload_bytes`` is the
    per-device buffer entering the collective (the figure HLO validation
    compares); ``bytes`` is ring-moved bytes (chunk-factored — what the
    alpha-beta pricing consumes).

    ``strategy``: ``"lowered"`` takes each record's FIRST option — the
    partial-sum all-reduce today's partitioner actually emits, which is
    what the HLO cross-validation pins and the golden ratchets;
    ``"best"`` takes the per-site cheapest option (e.g. all-gather a
    weight that is tiny next to a batch-sized partial-sum output) — the
    tuned-lowering bound the pricing uses (see :func:`implicit_comm`)."""
    from .cost_model import _CHUNK_FACTORS
    if strategy not in ("lowered", "best"):
        raise ValueError(f"unknown census strategy {strategy!r}")
    shape = {a: int(n) for a, n in dict(imesh.shape).items()}
    out: typing.Dict[str, typing.Dict[str, typing.Dict[str, int]]] = {}
    for r in result.records:
        n = shape.get(r.axis, 1)
        if n <= 1:
            continue
        best: typing.Optional[typing.Tuple[float, float, str]] = None
        for o in (r.options if strategy == "best" else r.options[:1]):
            div = 1
            for a in o.shard_axes:
                div *= max(1, shape.get(a, 1))
            payload = o.full_bytes / div
            moved = payload * _CHUNK_FACTORS.get(o.family,
                                                 lambda n: 1.0)(n)
            if best is None or moved < best[0]:
                best = (moved, payload, o.family)
        if best is None:
            continue
        moved, payload, family = best
        slot = out.setdefault(family, {}).setdefault(
            r.axis, {"count": 0, "payload_bytes": 0, "bytes": 0})
        slot["count"] += r.count
        slot["payload_bytes"] += int(payload * r.count)
        slot["bytes"] += int(moved * r.count)
    return out


#: the XLA collective-combiner model: implicit same-axis collectives fuse
#: into one launch per this much payload (the census keeps true per-op
#: counts — those are what the uncombined CPU-lowered HLO shows — but the
#: alpha/launch pricing must not charge a latency per tiny gradient psum
#: that the combiner pass provably batches)
COMBINE_BYTES = 32 * 1024 * 1024


def implicit_comm(result: SpmdResult, imesh):
    """The implicit census as a :class:`cost_model.CommModel` (moved bytes
    per axis) — the term ``StepResources.total_comm`` adds on top of the
    walked manual collectives.  Priced at the ``"best"`` strategy (the
    partitioner's cheaper choice per site) with launch counts
    combiner-fused (:data:`COMBINE_BYTES`) — the tuned-lowering bound the
    mesh-search objective should rank on — while the ratcheted census and
    the HLO validation pin the ``"lowered"`` form (docs/static_analysis.md
    "SPMD propagation")."""
    from .cost_model import CommModel
    c = census(result, imesh, strategy="best")
    model = CommModel({}, {})
    for fam in c.values():
        for axis, slot in fam.items():
            model.bytes_per_axis[axis] = (
                model.bytes_per_axis.get(axis, 0) + int(slot["bytes"]))
            launches = max(1, -(-int(slot["payload_bytes"]) // COMBINE_BYTES))
            model.count_per_axis[axis] = (
                model.count_per_axis.get(axis, 0)
                + min(int(slot["count"]), launches))
    return model


# -- the ratcheted implicit-collective graph rule ----------------------------

def spmd_golden_path(config_name: str) -> str:
    return os.path.join(GOLDENS_DIR, "spmd", config_name + ".json")


def _loc(traces: ConfigTraces, step: str) -> str:
    return f"configs/{traces.config_name}.json[{step}]"


def _step_golden(result: SpmdResult, imesh) -> dict:
    return {"implicit": census(result, imesh),
            "conflicts": len(result.conflicts),
            "seeded": bool(result.seeded)}


def check_implicit_collectives(traces: ConfigTraces,
                               update_goldens: bool = False
                               ) -> typing.List[Finding]:
    """The graph rule: the propagated implicit-collective census vs the
    ratcheted golden (``analysis/goldens/spmd/<config>.json``).  Counts
    must match exactly (a new implicit reshard is a layout change, exactly
    like a census diff); per-axis moved bytes ratchet at
    :data:`RATCHET_RATIO`; sharding conflicts lint as warnings and their
    count may never grow past the recorded one."""
    from .graph_rules import intended_mesh
    findings: typing.List[Finding] = []
    imesh = intended_mesh(traces.cfg)
    actual: typing.Dict[str, dict] = {}
    results: typing.Dict[str, SpmdResult] = {}
    for step, st in sorted(traces.steps.items()):
        try:
            r = propagate(st, imesh)
        except Exception as e:  # a propagation crash must name itself
            findings.append(Finding(
                "implicit-collective", "error", _loc(traces, step),
                f"sharding propagation failed: {type(e).__name__}: {e}"))
            continue
        if r.error:
            findings.append(Finding(
                "implicit-collective", "error", _loc(traces, step),
                f"sharding propagation failed: {r.error}"))
            continue
        if not r.seeded:
            findings.append(Finding(
                "implicit-collective", "warning", _loc(traces, step),
                "step trace carries no per-input sharding seeds "
                "(in_axes) — implicit collectives unaudited for this step"))
            continue
        results[step] = r
        actual[step] = _step_golden(r, imesh)
        for c in r.conflicts[:5]:
            findings.append(Finding(
                "implicit-collective", "warning", _loc(traces, step),
                f"conflicting operand shardings at {c.location} "
                f"({c.prim}: {c.detail}) — GSPMD resolves this by "
                f"resharding, often to full replication"))
        if len(r.conflicts) > 5:
            findings.append(Finding(
                "implicit-collective", "warning", _loc(traces, step),
                f"... and {len(r.conflicts) - 5} more sharding conflicts"))
    path = spmd_golden_path(traces.config_name)
    if update_goldens:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        import jax
        merged = dict(actual)
        if os.path.exists(path):
            with open(path) as f:
                for step, budget in json.load(f).get("steps", {}).items():
                    merged.setdefault(step, budget)
        with open(path, "w") as f:
            json.dump({"config": traces.config_name,
                       "jax": jax.__version__,
                       "intended_mesh": {k: int(v) for k, v in
                                         dict(imesh.shape).items()},
                       "tolerance": {"ratchet": RATCHET_RATIO},
                       "steps": merged}, f, indent=2, sort_keys=True)
            f.write("\n")
        findings.append(Finding(
            "implicit-collective", "info", path,
            f"spmd golden updated ({', '.join(actual) or 'no steps'})"))
        return findings
    if not os.path.exists(path):
        findings.append(Finding(
            "implicit-collective", "error", _loc(traces, "*"),
            f"no spmd golden at {os.path.relpath(path)}; run "
            f"`python tools/graftcheck.py --config configs/"
            f"{traces.config_name}.json --update-goldens`"))
        return findings
    with open(path) as f:
        golden = json.load(f)
    ratchet = float(golden.get("tolerance", {}).get("ratchet", RATCHET_RATIO))
    gsteps = golden.get("steps", {})
    from .cost_model import format_bytes as _fmt
    for step in sorted(set(actual) | set(gsteps)):
        if step not in actual:
            findings.append(Finding(
                "implicit-collective", "warning", _loc(traces, step),
                "step present in spmd golden but not audited this run "
                f"({traces.errors.get(step, 'step skipped')})"))
            continue
        if step not in gsteps:
            findings.append(Finding(
                "implicit-collective", "warning", _loc(traces, step),
                "step audited but not pinned by the spmd golden; record it "
                "with --update-goldens to gate it"))
            continue
        got, want = actual[step], gsteps[step]
        gi, wi = got.get("implicit", {}), want.get("implicit", {})
        for fam in sorted(set(gi) | set(wi)):
            ga, wa = gi.get(fam, {}), wi.get(fam, {})
            for ax in sorted(set(ga) | set(wa)):
                g = ga.get(ax, {"count": 0, "bytes": 0})
                w = wa.get(ax, {"count": 0, "bytes": 0})
                if g["count"] != w.get("count", 0):
                    findings.append(Finding(
                        "implicit-collective", "error", _loc(traces, step),
                        f"implicit {fam} count on mesh axis {ax!r} "
                        f"{g['count']} != golden {w.get('count', 0)} — "
                        f"GSPMD will insert a different collective layout "
                        f"than the one this config was tuned under "
                        f"(an unplanned reshard usually means a "
                        f"sharding-spec drift); if intended, re-record "
                        f"with --update-goldens"))
                elif g["bytes"] > max(w.get("bytes", 0), 1024) * ratchet:
                    findings.append(Finding(
                        "implicit-collective", "error", _loc(traces, step),
                        f"implicit {fam} payload on mesh axis {ax!r} grew "
                        f"{_fmt(w.get('bytes', 0))} -> {_fmt(g['bytes'])} "
                        f"past the x{ratchet} ratchet; if intended, "
                        f"re-record with --update-goldens"))
                elif (w.get("bytes", 0) > 1024
                        and g["bytes"] * ratchet < w["bytes"]):
                    findings.append(Finding(
                        "implicit-collective", "info", _loc(traces, step),
                        f"implicit {fam} payload on {ax!r} improved "
                        f"{_fmt(w['bytes'])} -> {_fmt(g['bytes'])}; "
                        f"re-record with --update-goldens to ratchet"))
        if got.get("conflicts", 0) > want.get("conflicts", 0):
            findings.append(Finding(
                "implicit-collective", "error", _loc(traces, step),
                f"sharding conflicts grew {want.get('conflicts', 0)} -> "
                f"{got['conflicts']} — a new eqn mixes incompatible "
                f"operand shardings (see the conflict warnings above); if "
                f"intended, re-record with --update-goldens"))
        elif got.get("conflicts", 0) < want.get("conflicts", 0):
            findings.append(Finding(
                "implicit-collective", "info", _loc(traces, step),
                f"sharding conflicts improved {want.get('conflicts', 0)} "
                f"-> {got['conflicts']}; re-record with --update-goldens"))
    return findings


# -- HLO cross-validation (the honesty check) --------------------------------

def hlo_compilable(cfg) -> typing.Tuple[bool, str]:
    """Whether the config's train step can be lowered+compiled with its
    real shardings on THIS host (CPU devices): pure-GSPMD structure only
    (the sequence/pipeline axes lower through shard_map, which the compat
    shims stage but cannot faithfully compile), and enough local devices
    to build the intended mesh."""
    import jax
    if int(cfg.sequence_parallel) > 1 or int(cfg.pipeline_parallel) > 1:
        return False, "sequence/pipeline structure lowers through " \
                      "shard_map — not GSPMD-compilable here"
    n = max(1, int(cfg.tpu_size))
    if n > len(jax.devices()):
        return False, (f"intended mesh needs {n} devices; "
                       f"{len(jax.devices())} available")
    return True, ""


def compile_train_hlo(cfg) -> str:
    """Lower + compile the REAL train step under the intended shardings on
    local (CPU) devices and return the optimized, partitioned HLO text —
    the collectives in it are the ones GSPMD actually inserted."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from ..nd import NT
    from ..optim import Optimizer
    from ..parallel import make_mesh
    from ..parallel.sharding import spec_for
    from ..train.state import Trainer, TrainState
    from .trace import _micro_sds, abstract_batch, abstract_params
    n = max(1, int(cfg.tpu_size))
    mesh = make_mesh(cfg, devices=jax.devices()[:n], quiet=True)
    batch = abstract_batch(cfg)
    trainer = Trainer(cfg, mesh)
    micro = _micro_sds(batch, trainer.n_micro)
    params, axes = abstract_params(cfg, micro)
    trainer.axes = axes
    trainer.optimizer = Optimizer(cfg, axes)
    opt_state = jax.eval_shape(trainer.optimizer.init, params)
    slot_axes = trainer.optimizer.slot_axis_names()

    def shard(sds, names):
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=NamedSharding(mesh, spec_for(tuple(names), mesh)))

    state = TrainState(
        {k: shard(v, axes.get(k, ())) for k, v in params.items()},
        {name: {k: shard(v, slot_axes.get(name, {}).get(k, ()))
                for k, v in slots.items()}
         for name, slots in dict(opt_state).items()},
        jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh, PartitionSpec())))
    sbatch = {k: NT(shard(t.x, t.names), t.names) for k, t in batch.items()}
    rng = jax.eval_shape(lambda: jax.random.key(0))
    rng = jax.ShapeDtypeStruct(
        rng.shape, rng.dtype,
        sharding=NamedSharding(mesh, PartitionSpec()))
    step = trainer._make_step()
    with mesh:
        compiled = step.trace(state, sbatch, rng,
                              *trainer.step_extra_args()).lower().compile()
    return compiled.as_text()


_HLO_OP_RE = re.compile(
    r"=\s+(?P<shape>\(?[a-z0-9_]+\[[^=]*?)\s+"
    r"(?P<op>" + "|".join(HLO_COLLECTIVE_OPS) + r")(?:-start)?[.\d]*\(")
_HLO_SHAPE_RE = re.compile(r"([a-z][a-z0-9_]*)\[([0-9,]*)\]")

_HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def hlo_collectives(text: str) -> typing.Dict[str, typing.Dict[str, int]]:
    """Parse ``{op: {count, bytes}}`` out of partitioned HLO text; bytes
    sum the (per-device) result shapes, tuple results included, so XLA's
    all-reduce combiner merging N ops into one keeps bytes invariant."""
    out: typing.Dict[str, typing.Dict[str, int]] = {}
    for m in _HLO_OP_RE.finditer(text):
        op = m.group("op")
        nbytes = 0
        for dt, dims in _HLO_SHAPE_RE.findall(m.group("shape")):
            n = 1
            for d in dims.split(","):
                if d.strip():
                    n *= int(d)
            nbytes += n * _HLO_DTYPE_BYTES.get(dt, 4)
        slot = out.setdefault(op, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += nbytes
    return out


def compare_hlo(predicted: typing.Dict[str, typing.Dict[str, typing.Dict[
        str, int]]], hlo: typing.Dict[str, typing.Dict[str, int]]) -> dict:
    """Tolerance verdict between a predicted implicit census and the
    lowered-HLO collective sheet.  Totals are the comparison basis — XLA
    combines/splits ops freely but conserves payload bytes; counts get a
    loose bar, bytes the :data:`HLO_TOLERANCE` ratio with
    :data:`HLO_BYTES_SLACK` absolute slack."""
    pred_count = sum(slot["count"] for fam in predicted.values()
                     for slot in fam.values())
    pred_bytes = sum(slot["payload_bytes"] for fam in predicted.values()
                     for slot in fam.values())
    hlo_count = sum(v["count"] for v in hlo.values())
    hlo_bytes = sum(v["bytes"] for v in hlo.values())
    reasons: typing.List[str] = []
    if (pred_count > 0) != (hlo_count > 0):
        reasons.append(
            f"presence mismatch: predicted {pred_count} implicit "
            f"collective(s), lowered HLO has {hlo_count}")
    if abs(pred_count - hlo_count) > max(HLO_COUNT_SLACK,
                                         0.5 * max(pred_count, hlo_count)):
        reasons.append(f"count mismatch: predicted {pred_count} vs "
                       f"{hlo_count} in HLO")
    lo = min(pred_bytes, hlo_bytes) + HLO_BYTES_SLACK
    hi = max(pred_bytes, hlo_bytes)
    if hi > lo * HLO_TOLERANCE:
        reasons.append(
            f"payload mismatch: predicted {pred_bytes} B vs {hlo_bytes} B "
            f"in HLO (beyond x{HLO_TOLERANCE} + {HLO_BYTES_SLACK} B slack)")
    return {"ok": not reasons,
            "predicted": {"count": pred_count, "payload_bytes": pred_bytes},
            "hlo": {"count": hlo_count, "bytes": hlo_bytes,
                    "ops": hlo},
            "tolerance": {"bytes_ratio": HLO_TOLERANCE,
                          "bytes_slack": HLO_BYTES_SLACK,
                          "count_slack": HLO_COUNT_SLACK},
            "reasons": reasons}


def validate_hlo(traces: ConfigTraces) -> dict:
    """The honesty check: predicted implicit census (train step, intended
    mesh) vs the collectives in the actually-compiled partitioned HLO.
    Returns ``{"skipped": reason}`` when the config cannot compile here
    (shard_map structure / not enough local devices / no train trace)."""
    from .graph_rules import intended_mesh
    cfg = traces.cfg
    ok, reason = hlo_compilable(cfg)
    if not ok:
        return {"config": traces.config_name, "skipped": reason}
    st = traces.steps.get("train")
    if st is None:
        return {"config": traces.config_name,
                "skipped": traces.errors.get("train", "train not traced")}
    imesh = intended_mesh(cfg)
    result = propagate(st, imesh)
    if not result.seeded or result.error:
        return {"config": traces.config_name,
                "skipped": result.error or "no sharding seeds"}
    predicted = census(result, imesh)
    try:
        text = compile_train_hlo(cfg)
    except Exception as e:
        return {"config": traces.config_name,
                "skipped": f"compile failed: {type(e).__name__}: {e}"}
    verdict = compare_hlo(predicted, hlo_collectives(text))
    verdict["config"] = traces.config_name
    verdict["predicted_census"] = predicted
    return verdict
