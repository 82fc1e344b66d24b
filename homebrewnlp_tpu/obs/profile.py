"""graftprof: device-time attribution from jax.profiler Chrome traces.

The ``--profile`` window (main.py) and bench.py's per-workload probe both
make ``jax.profiler`` write a Chrome-trace JSON
(``<dir>/plugins/profile/<session>/<host>.trace.json.gz``).  The reference
framework stopped there — a human eyeballed the TF profiler dump.  This
module turns the capture into machine-checkable numbers:

- **category bucketing**: device events (HLO op executions) are classified
  as MXU dots, collectives by kind, vector/elementwise fusions,
  copies/data movement, or infeed/outfeed, purely from the HLO op name —
  no sidecar needed.
- **scope attribution**: the model build mirrors the ``nd`` scope stack
  into ``jax.named_scope`` (nd.push_scope), so every compiled HLO
  instruction's ``metadata.op_name`` carries the layer path
  (``jit(step)/jit(main)/jvp(body)/@d0_.../dot_general``).  The kept AOT
  step executable dumps an op→op_name sidecar
  (:data:`OP_MAP_FILENAME`) next to the trace at ``stop_trace`` time, and
  the parser joins trace events against it — per-layer device time
  without a TPU-side dependency.
- **the train step's scope grammar**: :func:`step_scope` turns one
  ``op_name`` into ``(pass, block, layer)`` — which layer of which block,
  and whether the instruction runs in the chain's forward, the reversible
  backward's replay, the remat recompute or the backward proper.  On a TPU
  the profiler's own ``.xplane.pb`` carries each instruction's ``op_name``
  (:func:`xplane_op_names`), so the layer x pass table needs no sidecar
  there; the benchmark's per-scope metrics (``benchmark/scope_time.py``)
  read the same two functions.
- **an ms_per_step decomposition** into ``mxu + hbm + comm + idle`` that
  sums to the device wall window, reconciled against graftcost's static
  alpha-beta / roofline estimates (``analysis/cost_model.py``) as
  per-component ``prediction_error`` fields.

Everything below the loaders is pure over plain dicts (the committed
miniature trace fixture in tests/data/ exercises it without jax), and the
summary round-trips through JSON so bench baselines and the ``/metrics``
exporter consume the same shape.

Timing convention: Chrome trace ``ts``/``dur`` are microseconds.  Within
one lane (pid, tid) events nest by containment (a CPU ``call`` thunk
encloses the ops it calls); attribution uses SELF time (duration minus
directly nested children) so nothing double-counts.  Lanes run
concurrently, so busy time is the interval UNION of top-level events
across lanes, idle is the device wall window minus that union, and the
category decomposition splits the union proportionally to per-category
self-time.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
import re
import typing

#: sidecar filename written next to the trace session (write_op_map)
OP_MAP_FILENAME = "graftprof_op_map.json"

#: categories every device event lands in (order = table/render order)
CATEGORIES = ("mxu", "collective", "vector", "copy", "infeed", "unknown")

#: decomposition buckets and which categories feed them; "idle" is
#: wall - busy and has no category of its own
DECOMP_BUCKETS: typing.Dict[str, typing.Tuple[str, ...]] = {
    "mxu": ("mxu",),
    "comm": ("collective", "infeed"),
    "hbm": ("vector", "copy", "unknown"),
}

_COLLECTIVE_PREFIXES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "send", "recv",
    "send-done", "recv-done", "partition-id", "replica-id",
)
#: async collective halves (``all-reduce-start``/``-done``) report under
#: their family; stripped before the _COLLECTIVE_PREFIXES match
_ASYNC_HALF_RE = re.compile(r"-(start|done|update)$")
_MXU_PREFIXES = ("dot", "convolution", "conv", "cublas", "gemm")
_COPY_PREFIXES = ("copy", "bitcast", "reshape", "transpose", "slice",
                  "dynamic-slice", "dynamic-update-slice", "concatenate",
                  "pad", "gather", "scatter", "broadcast", "iota",
                  "copy-start", "copy-done")
_INFEED_PREFIXES = ("infeed", "outfeed", "host-transfer")
_VECTOR_PREFIXES = (
    "fusion", "add", "subtract", "multiply", "divide", "tanh", "exp",
    "log", "rsqrt", "sqrt", "power", "maximum", "minimum", "compare",
    "select", "and", "or", "not", "xor", "negate", "abs", "sign",
    "floor", "ceil", "round", "clamp", "convert", "reduce",
    "reduce-window", "map", "rng", "sort", "reverse", "tuple",
    "get-tuple-element", "constant", "parameter", "cbrt", "logistic",
    "erf", "atan2", "rem", "shift", "popcnt", "clz", "is-finite",
    "real", "imag", "complex", "expm1", "log1p", "cos", "sin", "tan",
    "stochastic-convert", "bitcast-convert", "domain", "optimization"
)
_CONTROL_PREFIXES = ("call", "while", "conditional", "fused-computation",
                     "async-start", "async-done", "async-update")


def _base_op(name: str) -> str:
    """``all-reduce.12.clone`` -> ``all-reduce`` (strip numeric/.clone/
    .remat suffixes; keep the leading HLO opcode or fusion name)."""
    n = name.strip().lstrip("%").lower()
    n = re.sub(r"(\.(clone|remat|\d+))+$", "", n)
    return n


def categorize(name: str) -> str:
    """Category for one device event from its HLO op name alone."""
    base = _base_op(name)
    coll = _ASYNC_HALF_RE.sub("", base)
    for p in _COLLECTIVE_PREFIXES:
        if coll == p or coll.startswith(p + "."):
            return "collective"
    for p in _INFEED_PREFIXES:
        if base.startswith(p):
            return "infeed"
    for p in _MXU_PREFIXES:
        if base == p or base.startswith(p + "-") or base.startswith(p + "_"):
            return "mxu"
    if "fusion" in base:
        # named fusions ("input_multiply_dot_fusion"): a contained matmul
        # makes the whole fused loop MXU work ("convert" must NOT hit the
        # "conv" token, so match whole _/- separated tokens)
        toks = re.split(r"[^a-z0-9]+", base)
        if any(t in ("dot", "conv", "convolution", "gemm", "matmul")
               for t in toks):
            return "mxu"
        return "vector"
    for p in _COPY_PREFIXES:
        if base == p or base.startswith(p + "-") or base.startswith(p + "_"):
            return "copy"
    for p in _VECTOR_PREFIXES:
        if base == p or base.startswith(p + "-") or base.startswith(p + "_"):
            return "vector"
    if base.startswith("custom-call"):
        # opaque kernels (pallas) — compute, almost always matmul-class
        return "mxu"
    for p in _CONTROL_PREFIXES:
        if base == p or base.startswith(p + "-"):
            # control ops carry ~zero SELF time (their children hold the
            # real work); classify as vector so they don't read as unknown
            return "vector"
    return "unknown"


def collective_kind(name: str) -> typing.Optional[str]:
    """The collective family (``all-reduce``...) or None; async halves
    (``all-reduce-start.1``) report under their family."""
    base = _ASYNC_HALF_RE.sub("", _base_op(name))
    for p in _COLLECTIVE_PREFIXES:
        if base == p or base.startswith(p + "."):
            return p
    return None


# -- scope extraction from HLO metadata op_name -------------------------------

#: jax transform wrappers that may enclose a named_scope component in
#: ``metadata.op_name`` (``transpose(jvp(body))`` -> ``body``)
_WRAPPERS = ("jvp", "transpose", "vmap", "pmap", "remat", "checkpoint",
             "custom_jvp", "custom_vjp", "jit", "pjit", "xmap",
             "shard_map", "scan", "while", "cond", "custom_vjp_call",
             "rematted_computation")
_WRAP_RE = re.compile(r"^(%s)\((.*)\)$" % "|".join(_WRAPPERS))
_JIT_HEAD_RE = re.compile(r"^(jit|pjit)\(.*\)$")


#: path components that jax.checkpoint itself writes into ``op_name`` (the
#: lowering's ``checkpoint`` and the transpose's ``rematted_computation``):
#: machinery, never a model scope.  :func:`step_scope` reads the pass from
#: them in the raw string; the scope path drops them
_REMAT_COMPONENTS = ("checkpoint", "rematted_computation")


def _collapse_repeat(parts: typing.Tuple[str, ...]
                     ) -> typing.Tuple[str, ...]:
    """Collapse a repeated leading run: ``gpt/body/gpt/body/d0_0`` ->
    ``gpt/body/d0_0``.  Per-block sub-builds re-enter their full preset
    scope path (models/ctx.py::_PresetScope) while the outer build's
    jax name-stack entries are still open, so compiled metadata carries
    the prefix twice; the parameter path is the single-run form.  Under
    ``jax.checkpoint`` the prefix comes once more behind the lowering's own
    component (``gpt/body/checkpoint/gpt/body/d0_0``): those components
    go first, so that every pass of one block resolves to one path."""
    parts = tuple(p for p in parts if p not in _REMAT_COMPONENTS)
    changed = True
    while changed and parts:
        changed = False
        for i in range(1, len(parts) // 2 + 1):
            if parts[:i] == parts[i:2 * i]:
                parts = parts[i:]
                changed = True
                break
    return parts


def _op_name_parts(op_name: str) -> typing.List[str]:
    """Components of one ``op_name`` behind the leading ``jit(...)``
    machinery.  XLA joins the names of merged instructions with ``;``: the
    first one stands for the instruction."""
    parts = [p for p in op_name.split(";")[0].split("/") if p]
    while parts and _JIT_HEAD_RE.match(parts[0]):
        parts.pop(0)
    return parts


def scope_of_op_name(op_name: str) -> typing.Tuple[str, ...]:
    """Model-scope components of one HLO ``metadata.op_name``.

    Drops the leading ``jit(...)`` machinery and the trailing primitive
    name, and unwraps transform decorations, so forward and backward ops
    of one layer attribute to the SAME scope path::

        jit(step)/jit(main)/transpose(jvp(body))/layer0/ffn/dot_general
        -> ("body", "layer0", "ffn")
    """
    out: typing.List[str] = []
    for p in _op_name_parts(op_name):
        m = _WRAP_RE.match(p)
        while m:
            p = m.group(2)
            m = _WRAP_RE.match(p) if p else None
        if p:
            out.append(p)
    return _collapse_repeat(tuple(out[:-1]))  # last component = primitive


# -- the train step's scope grammar: op_name -> (pass, block, layer) ----------

#: which run of the model an instruction belongs to (order = table order)
PASSES = ("forward", "replay", "remat", "backward", "optimizer", "other")
#: layers that the benchmark's metrics and the operator's table name; an nd
#: layer scope outside :data:`_LAYER_OF_TOKEN` reports under its own name
LAYERS = ("norm", "group_linear", "map", "body", "input", "output", "loss",
          "optimizer", "other")
OTHER = "other"

_BLOCK_RE = re.compile(r"^d\d+_\d+$")
_BLOCK_SCOPE_RE = re.compile(r"^block_\d*$")
#: nd gives every layer scope a use counter: ``norm_``, ``norm_1``
_COUNTER_RE = re.compile(r"_\d*$")
_LAYER_OF_TOKEN = {"norm": "norm", "rms_norm": "norm",
                   "bottleneck_group_linear": "group_linear",
                   "attention": "map", "activation": "map"}
_TRANSFORM_RE = re.compile(r"^(jvp|transpose)\(")


def _pass_of_parts(parts: typing.Sequence[str]) -> str:
    if not parts:
        return OTHER
    if parts[0] == "optimizer":
        return "optimizer"
    outer = _TRANSFORM_RE.match(parts[0])
    if outer is None:
        return OTHER
    if outer.group(1) == "jvp":
        return "forward"
    if "rematted_computation" in parts:
        return "remat"
    for at, p in enumerate(parts[1:], 2):
        inner = _TRANSFORM_RE.match(p)
        if inner is not None:
            # under the plain `checkpoint` strategy the body is one jvp of
            # its own, and jax.checkpoint names its transposed half
            # `checkpoint/...`: no forward runs there but the recompute
            # (`rematted_computation`, above)
            replay = (inner.group(1) == "jvp"
                      and "checkpoint" not in parts[at:])
            return "replay" if replay else "backward"
    return "backward"


def _layer_of_scope(scope: typing.Sequence[str]
                    ) -> typing.Tuple[typing.Optional[str], str]:
    if not scope:
        return None, OTHER
    if scope[0] == "optimizer":
        return None, "optimizer"
    at = next((i for i, p in enumerate(scope) if _BLOCK_RE.match(p)), None)
    if at is None:
        top = scope[1] if len(scope) > 1 else ""
        return None, top if top in ("input", "output", "loss", "body") \
            else OTHER
    inside = [p for p in scope[at + 1:] if not _BLOCK_SCOPE_RE.match(p)]
    if not inside or not _COUNTER_RE.search(inside[0]):
        # no nd layer scope: the block's own instructions
        return scope[at], "map"
    base = _COUNTER_RE.sub("", inside[0])
    return scope[at], _LAYER_OF_TOKEN.get(base, base)


@functools.lru_cache(maxsize=1 << 16)  # a step has a few thousand names
def step_scope(op_name: str
               ) -> typing.Tuple[str, typing.Optional[str], str]:
    """``(pass_, block, layer)`` of one instruction of the compiled train
    step, from its ``metadata.op_name`` alone.

    ``layer``: the nd layer scope under ``d<i>_<j>/block_`` with its use
    counter dropped and folded by :data:`_LAYER_OF_TOKEN` (``norm_1`` ->
    ``norm``, ``bottleneck_group_linear_`` -> ``group_linear``,
    ``attention_`` / ``activation_`` -> ``map``, ``rms_norm_`` -> ``norm``;
    any other layer scope keeps its own name: ``kda``, ``mla``,
    ``routed_moe``, ``gated_feed_forward``); ``optimizer``; ``input`` / ``output`` / ``loss``;
    ``body`` for the reversible chain's own glue between blocks (residual
    sums, the cotangent squash); ``other`` for step-level glue with no
    model scope (gradient norm, clipping, argument copies).  Whatever sits
    directly under ``block_`` with no layer scope of its own is ``map``:
    only a fused block (``fused_mixer_block``) emits instructions there,
    the kernel (``jit(_fwd_pallas)`` / ``jit(_bwd_pallas)``) and the layout
    glue around it.  The kernel holds that block's norms too; they run
    inside one custom call and cannot be split out, so a fused block's
    ``map`` is the whole block.

    ``block``: ``d<i>_<j>`` or None.

    ``pass_`` comes from the transform wrappers of the raw string, which
    :func:`scope_of_op_name` strips.  The step is ``jax.grad`` of ``gpt``, so
    the first component is ``jvp(gpt)`` (the chain's forward: ``forward``),
    ``transpose(jvp(gpt))`` (everything the backward runs) or
    ``optimizer``.  Inside the backward, ``ops/reversible.py::chain_bwd``
    calls ``jax.vjp`` on each block again, and the next wrapped component
    tells its two halves apart: ``jvp(...)`` is that vjp's primal, the
    inverse's forward (``replay``); ``transpose(...)`` its transposed
    instructions (``backward``), which is also where a ``custom_vjp``'s
    backward rule lands (``transpose(transpose(jvp(gpt)))/.../
    jit(_bwd_pallas)``).  Under ``jax.checkpoint`` the transposed half
    holds both the recompute, which JAX marks ``rematted_computation``
    (``remat``), and the transposed instructions (``backward``); with the
    body under the plain ``checkpoint`` strategy those read
    ``transpose(jvp(gpt))/body/jvp(gpt)/body/checkpoint/gpt/...``, an inner
    ``jvp`` that replays nothing.  Counted
    on the optimized HLO of both block layouts (tests/graftprof_test.py):
    XLA drops the first block's replay under remat (nothing reads the
    reconstructed input) and merges the last block's with the forward it
    repeats, so a chain of n blocks shows n - 2 or n - 1 replays."""
    if "(" not in op_name:
        # argument labels ("state.params['gpt/...']") are no scope path
        return OTHER, None, OTHER
    block, layer = _layer_of_scope(scope_of_op_name(op_name))
    return _pass_of_parts(_op_name_parts(op_name)), block, layer


def layer_pass_seconds(rows: typing.Iterable[
        typing.Tuple[typing.Optional[str], float]]) -> typing.Dict[str, float]:
    """``{"<layer>/<pass>": seconds}`` over ``(op_name or None, seconds)``
    rows, by :func:`step_scope`; a row without a name is ``other/other``.
    Empty when no row resolved to a scope."""
    total: typing.Dict[str, float] = {}
    for op_name, seconds in rows:
        pass_, _, layer = step_scope(op_name or "")
        key = f"{layer}/{pass_}"
        total[key] = total.get(key, 0.0) + seconds
    return total if set(total) - {f"{OTHER}/{OTHER}"} else {}


def layer_pass_table(table: typing.Dict[str, float], n_steps: int = 1
                     ) -> typing.List[str]:
    """Text lines of a :func:`layer_pass_seconds` table in ms a step:
    one row a layer (largest first), one column a pass, sums on both.
    No table, no lines."""
    if not table:
        return []
    steps = max(1, n_steps)
    cells = {tuple(k.rsplit("/", 1)): v * 1e3 / steps
             for k, v in table.items()}
    by_layer: typing.Dict[str, float] = {}
    for (layer, _), v in cells.items():
        by_layer[layer] = by_layer.get(layer, 0.0) + v
    lines = [f"{'layer (ms/step)':<16}"
             + "".join(f"{p:>10}" for p in PASSES) + f"{'sum':>10}"]
    for layer in sorted(by_layer, key=lambda k: -by_layer[k]):
        lines.append(f"{layer[:16]:<16}" + "".join(
            f"{cells.get((layer, p), 0.0):>10.3f}" for p in PASSES)
            + f"{by_layer[layer]:>10.3f}")
    lines.append(f"{'sum':<16}" + "".join(
        f"{sum(v for (_, q), v in cells.items() if q == p):>10.3f}"
        for p in PASSES) + f"{sum(by_layer.values()):>10.3f}")
    return lines


# -- HLO op map (instruction -> metadata op_name) -----------------------------

_HLO_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*metadata=\{[^}]*?"
    r"op_name=\"([^\"]+)\"")


def op_map_from_hlo_text(text: str) -> typing.Dict[str, str]:
    """``{instruction_name: metadata op_name}`` parsed from optimized HLO
    text (``compiled.as_text()``) — covers instructions inside fused/
    called computations too, since every line carrying metadata is read."""
    out: typing.Dict[str, str] = {}
    for line in text.splitlines():
        m = _HLO_INSTR_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_module_name(text: str) -> str:
    m = _HLO_MODULE_RE.match(text.splitlines()[0] if text else "")
    return m.group(1) if m else ""


class OpMap:
    """Per-module instruction -> op_name lookup with suffix fallback
    (the runtime clones instructions: trace names like ``tanh.5.clone``
    must still hit the ``tanh.5`` map entry)."""

    def __init__(self, modules: typing.Dict[str, typing.Dict[str, str]]):
        self.modules = modules

    @classmethod
    def from_hlo_text(cls, text: str) -> "OpMap":
        return cls({hlo_module_name(text) or "unknown":
                    op_map_from_hlo_text(text)})

    def lookup(self, module: str, op: str) -> typing.Optional[str]:
        ops = self.modules.get(module)
        if ops is None:
            return None
        hit = ops.get(op)
        if hit is not None:
            return hit
        base = re.sub(r"(\.clone)+$", "", op)
        return ops.get(base)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump({"modules": self.modules}, f)
        return path

    @classmethod
    def load(cls, path: str) -> "OpMap":
        with open(path) as f:
            doc = json.load(f)
        return cls(doc.get("modules", {}))


def write_op_map(compiled, profile_dir: str) -> typing.Optional[str]:
    """Dump the compiled step executable's op map next to the newest trace
    session under ``profile_dir`` (or into ``profile_dir`` itself when no
    session exists yet).  Returns the sidecar path, or None when the
    executable can't render its HLO (exotic backends)."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    session = _newest_session_dir(profile_dir)
    outdir = session if session else profile_dir
    os.makedirs(outdir, exist_ok=True)
    return OpMap.from_hlo_text(text).save(
        os.path.join(outdir, OP_MAP_FILENAME))


def write_op_map_for(trainer, profile_dir: str) -> typing.Optional[str]:
    """The train-loop entry point: sidecar from the trainer's kept AOT
    executable when one exists (telemetry or ``--profile`` pre-compile),
    silently nothing otherwise — category bucketing still works without
    it, only per-scope attribution degrades."""
    compiled = getattr(trainer, "_compiled", None)
    if compiled is None:
        return None
    return write_op_map(compiled, profile_dir)


# -- op_name from the profiler's own file -------------------------------------
#
# On the TPU the profiler writes ``<session>/<host>.xplane.pb``.  Every
# device event points at an event-metadata entry whose name is the whole HLO
# instruction and whose ``tf_op`` stat is that instruction's
# ``metadata.op_name`` followed by ``:`` (looked at by hand, PR 25, in
# traces of executables compiled in the run and loaded from the persistent
# cache alike).  ``jax.profiler.ProfileData`` shows an event's own stats
# only, so the few message fields needed are read off the protobuf wire
# format here: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (map entries: value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1,
# .str_value = 5, .ref_value = 7 (tsl/profiler/protobuf/xplane.proto).

OP_NAME_STAT = "tf_op"
DEVICE_PLANE_PREFIX = "/device:TPU:"


def _wire_fields(buf) -> typing.Iterator[typing.Tuple[int, typing.Any]]:
    """(field number, value) of one serialized message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            if not byte & 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
            continue
        if kind == 2:
            size = varint()
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane")
        yield key >> 3, buf[i:i + size]
        i += size


def _map_values(entries) -> typing.Iterator[typing.Dict[int, list]]:
    """The value message of every map entry, as {field: [values]}."""
    for entry in entries:
        for field, value in _wire_fields(entry):
            if field == 2:
                message: typing.Dict[int, list] = {}
                for f, v in _wire_fields(value):
                    message.setdefault(f, []).append(v)
                yield message


def xplane_op_names(path: str) -> typing.Dict[str, str]:
    """``{event name: op_name}`` over the device planes of one
    ``.xplane.pb``: the name is the event's whole HLO instruction, as
    ``ProfileData`` gives it, the value its ``metadata.op_name``.
    Instructions without one (parameters, async copies that XLA itself
    put in) are left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: typing.Dict[str, str] = {}
    for field, plane in _wire_fields(space):
        if field != 1:
            continue
        parts: typing.Dict[int, list] = {}
        for f, v in _wire_fields(plane):
            if f in (2, 4, 5):
                parts.setdefault(f, []).append(v)
        name = bytes(parts.get(2, [b""])[0]).decode()
        if not name.startswith(DEVICE_PLANE_PREFIX):
            continue
        stat_names = {m[1][0]: bytes(m.get(2, [b""])[0]).decode()
                      for m in _map_values(parts.get(5, [])) if 1 in m}
        for meta in _map_values(parts.get(4, [])):
            for stat in meta.get(5, []):
                got = dict(_wire_fields(stat))
                if stat_names.get(got.get(1)) != OP_NAME_STAT:
                    continue
                value = (bytes(got[5]).decode() if 5 in got
                         else stat_names.get(got.get(7), ""))
                op_name = value.rsplit(":", 1)[0]
                if op_name:
                    out.setdefault(bytes(meta[2][0]).decode(), op_name)
    return out


def find_xplane_file(profile_dir: str) -> typing.Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    session = _newest_session_dir(profile_dir)
    hits = sorted(glob.glob(os.path.join(session, "*.xplane.pb"))) \
        if session else []
    return hits[-1] if hits else None


def xplane_layer_pass_seconds(path: str) -> typing.Dict[str, float]:
    """The layer x pass table of every ``XLA Ops`` event of one
    ``.xplane.pb`` (a TPU capture; a CPU one names no instruction and
    gives ``{}``): durations summed flat, a fusion under its own
    instruction's scope."""
    op_names = xplane_op_names(path)
    if not op_names:
        return {}
    from jax.profiler import ProfileData
    return layer_pass_seconds(
        (op_names.get(event.name), event.duration_ns / 1e9)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines if line.name == "XLA Ops"
        for event in line.events)


# -- trace loading ------------------------------------------------------------

def _newest_session_dir(profile_dir: str) -> typing.Optional[str]:
    sessions = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*")))
    return sessions[-1] if sessions else None


def find_trace_file(path: str) -> typing.Optional[str]:
    """Resolve a profiler output path to one Chrome-trace JSON file: a
    direct ``*.trace.json(.gz)`` file, a session dir, or the profiler
    root dir (newest session wins).  None when the plugin directory is
    absent — the caller skips cleanly (some toolchains never write it)."""
    if os.path.isfile(path):
        return path
    if not os.path.isdir(path):
        return None
    for d in (path, _newest_session_dir(path)):
        if d is None:
            continue
        hits = sorted(glob.glob(os.path.join(d, "*.trace.json.gz"))
                      + glob.glob(os.path.join(d, "*.trace.json")))
        if hits:
            return hits[0]
    return None


def load_trace_events(path: str) -> typing.List[dict]:
    """Raw event dicts from a ``.trace.json(.gz)`` file (or a bare list /
    ``{"traceEvents": [...]}`` document)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    if isinstance(doc, list):
        return doc
    return doc.get("traceEvents", [])


def sidecar_op_map(path: str) -> typing.Optional[OpMap]:
    """The op-map sidecar next to a resolved trace file, if present."""
    candidate = os.path.join(os.path.dirname(os.path.abspath(path)),
                             OP_MAP_FILENAME)
    if not os.path.exists(candidate):
        return None
    try:
        return OpMap.load(candidate)
    except Exception:
        return None


# -- event selection + self-time ----------------------------------------------

def _process_names(events: typing.Iterable[dict]) -> typing.Dict[int, str]:
    out: typing.Dict[int, str] = {}
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and isinstance(e.get("args"), dict)):
            out[e.get("pid")] = str(e["args"].get("name", ""))
    return out


def _is_device_pid(pname: str) -> bool:
    p = pname.lower()
    return "/device:" in p or "tpu core" in p or "tpu:" in p


@dataclasses.dataclass
class DeviceEvent:
    name: str
    ts: float  # microseconds
    dur: float
    lane: typing.Tuple[int, int]  # (pid, tid)
    module: str  # hlo_module when known
    op: str  # hlo_op when known, else name
    self_us: float = 0.0


def device_events(events: typing.List[dict]
                  ) -> typing.Tuple[typing.List[DeviceEvent], int]:
    """(device events, malformed count).  A device event is an ``X`` event
    carrying an ``hlo_op`` arg (XLA:CPU thunk runtime — they interleave
    with Python events on host threads) or any ``X`` event on a device
    process (``/device:TPU:N`` in the converted TPU trace).  Garbage —
    missing/negative timing, non-dict args where one is needed — is
    counted, not raised: a truncated capture should degrade, not die."""
    pnames = _process_names(events)
    out: typing.List[DeviceEvent] = []
    bad = 0
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        args = e.get("args")
        args = args if isinstance(args, dict) else {}
        on_device_pid = _is_device_pid(pnames.get(e.get("pid"), ""))
        if "hlo_op" not in args and not on_device_pid:
            continue
        name, ts, dur = e.get("name"), e.get("ts"), e.get("dur")
        if (not isinstance(name, str)
                or not isinstance(ts, (int, float))
                or not isinstance(dur, (int, float)) or dur < 0 or ts < 0):
            bad += 1
            continue
        out.append(DeviceEvent(
            name=name, ts=float(ts), dur=float(dur),
            lane=(e.get("pid"), e.get("tid")),
            module=str(args.get("hlo_module", "")),
            op=str(args.get("hlo_op", name))))
    return out, bad


def compute_self_times(events: typing.List[DeviceEvent]) -> None:
    """Fill ``self_us`` per event: duration minus directly nested children
    on the same lane (CPU ``call`` thunks enclose their callees; without
    this the enclosed time would count twice)."""
    by_lane: typing.Dict[tuple, typing.List[DeviceEvent]] = {}
    for e in events:
        by_lane.setdefault(e.lane, []).append(e)
    eps = 1e-3  # us; trace timestamps are rounded to ns
    for lane in by_lane.values():
        lane.sort(key=lambda e: (e.ts, -e.dur))
        stack: typing.List[typing.Tuple[DeviceEvent, typing.List[float]]] = []
        for e in lane:
            while stack and e.ts >= stack[-1][0].ts + stack[-1][0].dur - eps:
                parent, kids = stack.pop()
                parent.self_us = max(0.0, parent.dur - sum(kids))
            if stack:
                stack[-1][1].append(e.dur)
            stack.append((e, []))
        while stack:
            parent, kids = stack.pop()
            parent.self_us = max(0.0, parent.dur - sum(kids))


def _interval_union_us(events: typing.List[DeviceEvent]) -> float:
    """Union length of top-level busy intervals across all lanes."""
    ivs = sorted((e.ts, e.ts + e.dur) for e in events)
    total = 0.0
    cur_s = cur_e = None
    for s, t in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, t
        else:
            cur_e = max(cur_e, t)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


# -- the summary --------------------------------------------------------------

UNATTRIBUTED = "(unattributed)"
#: ops whose metadata IS known but carries no model scope — step-level
#: glue (loss reduction tails, arg copies).  Attributed, unlike map misses.
TOPLEVEL = "(toplevel)"


@dataclasses.dataclass
class ProfileSummary:
    """One parsed capture.  All times seconds unless suffixed ``_ms``."""
    wall_s: float
    busy_s: float
    n_events: int
    n_malformed: int
    n_lanes: int
    n_steps: typing.Optional[int]
    categories_s: typing.Dict[str, float]
    collectives_s: typing.Dict[str, float]
    scopes_s: typing.Dict[str, float]
    top_ops: typing.List[dict]
    attributed_category_frac: float
    attributed_scope_frac: float
    decomposition_ms_per_step: typing.Dict[str, float]
    fractions: typing.Dict[str, float]
    #: full per-(scope, op) self seconds — flamegraph source; trimmed to
    #: top_ops in the JSON form
    op_rows: typing.List[dict] = dataclasses.field(default_factory=list)
    #: ``{"<layer>/<pass>": seconds}`` by :func:`step_scope`: from the
    #: ``.xplane.pb`` on a TPU, from the op-map sidecar elsewhere
    layer_pass_s: typing.Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def comm_fraction(self) -> float:
        return self.fractions.get("comm", 0.0)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("op_rows")
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ProfileSummary":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw.setdefault("op_rows", [])
        return cls(**kw)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path

    @classmethod
    def load(cls, path: str) -> "ProfileSummary":
        with open(path) as f:
            return cls.from_json(json.load(f))


def summarize_events(raw_events: typing.List[dict],
                     op_map: typing.Optional[OpMap] = None,
                     n_steps: typing.Optional[int] = None,
                     top_k: int = 20) -> ProfileSummary:
    """The pure core: raw Chrome-trace dicts -> :class:`ProfileSummary`."""
    events, bad = device_events(raw_events)
    compute_self_times(events)
    wall_us = busy_us = 0.0
    if events:
        t0 = min(e.ts for e in events)
        t1 = max(e.ts + e.dur for e in events)
        wall_us = t1 - t0
        busy_us = _interval_union_us(events)
    cats = {c: 0.0 for c in CATEGORIES}
    colls: typing.Dict[str, float] = {}
    per_key: typing.Dict[typing.Tuple[typing.Tuple[str, ...], str, str],
                         float] = {}
    scope_us: typing.Dict[typing.Tuple[str, ...], float] = {}
    total_self = 0.0
    named_rows: typing.List[typing.Tuple[typing.Optional[str], float]] = []
    for e in events:
        cat = categorize(e.op)
        cats[cat] += e.self_us
        total_self += e.self_us
        kind = collective_kind(e.op)
        if kind is not None:
            colls[kind] = colls.get(kind, 0.0) + e.self_us
        scope: typing.Tuple[str, ...] = (UNATTRIBUTED,)
        op_name = None
        if op_map is not None:
            op_name = op_map.lookup(e.module, e.op)
        named_rows.append((op_name, e.self_us * 1e-6))
        if op_name:
            # argument-label metadata ("state.params['gpt/...']",
            # "batch['token_x']") is not a scope path: step-level glue
            scope = ((TOPLEVEL,) if "jit(" not in op_name
                     else scope_of_op_name(op_name) or (TOPLEVEL,))
        key = (scope, _base_op(e.op), cat)
        per_key[key] = per_key.get(key, 0.0) + e.self_us
        scope_us[scope] = scope_us.get(scope, 0.0) + e.self_us
    us = 1e-6
    attributed_cat = ((total_self - cats["unknown"]) / total_self
                      if total_self else 0.0)
    attributed_scope = ((total_self - scope_us.get((UNATTRIBUTED,), 0.0))
                        / total_self if total_self else 0.0)
    # decomposition: split the busy union across buckets proportional to
    # per-category self time (lanes overlap, so self sums can exceed the
    # union); idle = wall - busy.  Sums to the wall window by construction.
    decomp_us = {b: 0.0 for b in DECOMP_BUCKETS}
    for bucket, members in DECOMP_BUCKETS.items():
        share = sum(cats[c] for c in members)
        if total_self > 0:
            decomp_us[bucket] = busy_us * share / total_self
    decomp_us["idle"] = max(0.0, wall_us - busy_us)
    decomp_us["total"] = wall_us
    steps = max(1, n_steps) if n_steps else None
    decomp_ms = {k: (v / 1e3 / (steps or 1)) for k, v in decomp_us.items()}
    fractions = {k: (decomp_us[k] / wall_us if wall_us else 0.0)
                 for k in ("mxu", "hbm", "comm", "idle")}
    op_rows = sorted(
        ({"scope": "/".join(scope), "op": op, "category": cat,
          "self_s": round(v * us, 9)}
         for (scope, op, cat), v in per_key.items()),
        key=lambda r: -r["self_s"])
    return ProfileSummary(
        wall_s=round(wall_us * us, 9),
        busy_s=round(busy_us * us, 9),
        n_events=len(events),
        n_malformed=bad,
        n_lanes=len({e.lane for e in events}),
        n_steps=n_steps,
        categories_s={k: round(v * us, 9) for k, v in sorted(cats.items())
                      if v > 0.0},
        collectives_s={k: round(v * us, 9) for k, v in sorted(colls.items())},
        scopes_s={"/".join(k): round(v * us, 9) for k, v in
                  sorted(scope_us.items(), key=lambda kv: -kv[1])},
        top_ops=op_rows[:top_k],
        attributed_category_frac=round(attributed_cat, 6),
        attributed_scope_frac=round(attributed_scope, 6),
        decomposition_ms_per_step={k: round(v, 6)
                                   for k, v in decomp_ms.items()},
        fractions={k: round(v, 6) for k, v in fractions.items()},
        op_rows=op_rows,
        layer_pass_s={k: round(v, 9) for k, v in
                      layer_pass_seconds(named_rows).items()})


def summarize_trace(path: str, op_map: typing.Optional[OpMap] = None,
                    n_steps: typing.Optional[int] = None,
                    top_k: int = 20) -> ProfileSummary:
    return summarize_events(load_trace_events(path), op_map=op_map,
                            n_steps=n_steps, top_k=top_k)


def capture_summary(profile_dir: str, n_steps: typing.Optional[int] = None,
                    top_k: int = 20) -> typing.Optional[ProfileSummary]:
    """Summarize the newest capture under a profiler output dir, joining
    the op-map sidecar when one sits next to the trace.  Where the
    capture's ``.xplane.pb`` names its instructions itself (a TPU), the
    layer x pass table is read from it.  None when no
    trace was written (profiler plugin directory absent — the caller
    skips cleanly rather than failing the run)."""
    trace = find_trace_file(profile_dir)
    if trace is None:
        return None
    summary = summarize_trace(trace, op_map=sidecar_op_map(trace),
                              n_steps=n_steps, top_k=top_k)
    xplane = find_xplane_file(profile_dir)
    from_device = xplane_layer_pass_seconds(xplane) if xplane else {}
    if from_device:
        summary.layer_pass_s = {k: round(v, 9)
                                for k, v in from_device.items()}
    return summary


# -- flamegraph + diff + reconcile --------------------------------------------

def collapsed_stacks(summary: ProfileSummary) -> typing.List[str]:
    """Flamegraph collapsed-stack lines (``scope;path;op <microseconds>``)
    — feed to any FlameGraph/speedscope renderer.  Uses the full op rows,
    so call on a summary built from a trace (not one re-loaded from its
    trimmed JSON form)."""
    rows = summary.op_rows or summary.top_ops
    out = []
    for r in sorted(rows, key=lambda r: (r["scope"], r["op"])):
        stack = [p for p in r["scope"].split("/") if p] + [r["op"]]
        out.append("%s %d" % (";".join(stack), round(r["self_s"] * 1e6)))
    return out


def diff_summaries(a: ProfileSummary, b: ProfileSummary) -> dict:
    """Attribution drift between two captures (``--compare``): per-bucket
    fraction deltas, per-scope ms/step deltas, and step-time movement —
    b minus a, so positive = grew in b."""
    steps_a = a.n_steps or 1
    steps_b = b.n_steps or 1
    scope_ms_a = {k: v * 1e3 / steps_a for k, v in a.scopes_s.items()}
    scope_ms_b = {k: v * 1e3 / steps_b for k, v in b.scopes_s.items()}
    scopes = {}
    for k in sorted(set(scope_ms_a) | set(scope_ms_b)):
        d = scope_ms_b.get(k, 0.0) - scope_ms_a.get(k, 0.0)
        scopes[k] = {"a_ms": round(scope_ms_a.get(k, 0.0), 6),
                     "b_ms": round(scope_ms_b.get(k, 0.0), 6),
                     "delta_ms": round(d, 6)}
    return {
        "ms_per_step": {
            "a": a.decomposition_ms_per_step.get("total", 0.0),
            "b": b.decomposition_ms_per_step.get("total", 0.0),
            "delta": round(
                b.decomposition_ms_per_step.get("total", 0.0)
                - a.decomposition_ms_per_step.get("total", 0.0), 6)},
        "fractions_delta": {
            k: round(b.fractions.get(k, 0.0) - a.fractions.get(k, 0.0), 6)
            for k in ("mxu", "hbm", "comm", "idle")},
        "attributed_scope_frac_delta": round(
            b.attributed_scope_frac - a.attributed_scope_frac, 6),
        "scopes_ms": scopes,
    }


def reconcile(summary: ProfileSummary,
              predicted_s: typing.Optional[typing.Dict[str, float]]
              ) -> dict:
    """Measured decomposition vs graftcost's static per-step estimate
    (``analysis/cost_model.py::static_step_times``: ``mxu``/``hbm``/``ici``
    seconds).  Per component: predicted ms, measured ms, and
    ``prediction_error`` = predicted/measured - 1 (positive = the model
    over-predicted).  ``predicted_s=None`` (CPU, unknown device) keeps the
    fields present but null, so the BENCH row shape is stable across
    backends."""
    pairs = {"mxu": "mxu", "hbm": "hbm", "comm": "ici"}
    out: typing.Dict[str, dict] = {}
    for component, pkey in pairs.items():
        measured_ms = summary.decomposition_ms_per_step.get(component, 0.0)
        pred_ms = None
        if predicted_s is not None and predicted_s.get(pkey) is not None:
            pred_ms = float(predicted_s[pkey]) * 1e3
        err = None
        if pred_ms is not None and measured_ms > 0:
            err = round(pred_ms / measured_ms - 1.0, 4)
        out[component] = {
            "predicted_ms": None if pred_ms is None else round(pred_ms, 6),
            "measured_ms": round(measured_ms, 6),
            "prediction_error": err,
        }
    return out


# -- bench attribution-drift baseline -----------------------------------------

#: tolerated absolute drift of any decomposition fraction (and of the
#: scope-attribution coverage) vs the committed per-device baseline
PROFILE_DRIFT_TOL = 0.15


def baseline_entry(profile_row: dict) -> dict:
    """The committed shape for one workload (bench_profile_baseline.json)."""
    return {"fractions": dict(profile_row.get("fractions", {})),
            "attributed_scope_frac":
                profile_row.get("attributed_scope_frac", 0.0)}


def evaluate_profile_baseline(workloads: dict, budgets: dict,
                              tol: float = PROFILE_DRIFT_TOL):
    """Pure attribution-drift gate (unit-testable; same contract as
    ``bench.evaluate_compile_budget``): each workload row's decomposition
    fractions must sit within ``tol`` (absolute) of the committed
    per-device baseline, and scope-attribution coverage must not drop more
    than ``tol`` below it.  Returns (per-workload rows, all_pass);
    workloads without a profile row or baseline entry are skipped —
    absence is not a regression."""
    rows: dict = {}
    ok = True
    for nm, w in sorted(workloads.items()):
        prof = w.get("profile") if isinstance(w, dict) else None
        base = (budgets or {}).get(nm)
        if (not isinstance(prof, dict) or "fractions" not in prof
                or not isinstance(base, dict)):
            continue
        drift = {k: round(prof["fractions"].get(k, 0.0)
                          - base.get("fractions", {}).get(k, 0.0), 4)
                 for k in ("mxu", "hbm", "comm", "idle")}
        cov_drop = round(base.get("attributed_scope_frac", 0.0)
                         - prof.get("attributed_scope_frac", 0.0), 4)
        passed = bool(max(abs(v) for v in drift.values()) <= tol
                      and cov_drop <= tol)
        rows[nm] = {"fraction_drift": drift,
                    "coverage_drop": cov_drop,
                    "tol": tol, "pass": passed}
        ok = ok and passed
    return rows, ok
