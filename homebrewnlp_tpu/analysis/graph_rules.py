"""Graph auditors: rule passes over abstractly-traced step jaxprs.

Six rules, each pinning an invariant that historically only failed at TPU
runtime (slow step, OOM, or silently wrong layout):

- ``collective-census``: count data-moving collectives (+ sharding
  constraints, half->f32 upcasts and quantized int8/fp8 ops) per step and
  diff against the config's golden budget file.  An accidental all-gather
  from a PartitionSpec mismatch — or a new upcast in the hot path —
  shows up as a census diff.
- ``dtype-promotion``: no f64/complex128 values anywhere in a step unless the
  config itself declares an f64 dtype policy.
- ``quant-dtype``: int8/fp8 compute only inside the config's declared
  ``quant_blocks`` scope (ops/quant.py) — a quantized op without the knob,
  or a declared scope whose train step has no quantized dot (silent
  high-precision fallback), is an error.
- ``donation``: every TrainState buffer entering the train step must be
  donated (``donate_argnums``) — a dropped donation doubles peak HBM.
- ``sharding-spec``: every mesh axis named by the sharding rule table or by
  an in-graph sharding annotation must exist on the mesh (``spec_for``
  silently replicates unknown axes — exactly the failure this pins); large
  parameters left fully replicated on the config's intended pod mesh are
  flagged.
- ``constant-bloat``: closed-over array constants above a size threshold are
  baked into the program (recompile hazard + wasted HBM per executable).

Golden budgets live in ``homebrewnlp_tpu/analysis/goldens/census/`` — one
JSON per config, regenerated with ``python tools/graftcheck.py
--update-goldens`` (see docs/static_analysis.md).
"""
from __future__ import annotations

import json
import os
import typing

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..parallel.mesh import MESH_AXES, axis_sizes
from ..parallel.sharding import RULES, spec_for
from .findings import Finding
from .trace import (COLLECTIVE_PRIMS, ConfigTraces, eqn_location, iter_eqns,
                    iter_closed_jaxprs)

GOLDENS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens")

# constant-bloat thresholds (bytes): above ERROR the constant is certainly a
# closure bug; WARN..ERROR is worth a look (tables etc.)
CONST_WARN_BYTES = 64 * 1024
CONST_ERROR_BYTES = 1024 * 1024

# sharding-spec: parameters at least this large (elements) should not be
# fully replicated when the config's intended mesh has a >1 model axis
REPLICATED_PARAM_ELEMS = 1 << 23  # 8M elements (32 MB at f32)

_F64 = (jnp.float64, jnp.complex128)

#: quantized-compute dtypes the quant-dtype rule audits (ops/quant.py):
#: int8 plus every fp8 flavor this toolchain knows.  Keys are np.dtype
#: instances — an aval carries np.dtype, which compares equal to the jnp
#: scalar type but does NOT hash equal, so a scalar-type-keyed dict would
#: silently miss every hit.  Maps np.dtype -> census family ("int8"/"fp8").
_QUANT_DTYPES: typing.Dict[typing.Any, str] = {np.dtype(jnp.int8): "int8"}
for _fp8 in ("float8_e4m3fn", "float8_e5m2", "float8_e4m3b11_fnuz",
             "float8_e4m3fnuz", "float8_e5m2fnuz"):
    if hasattr(jnp, _fp8):
        _QUANT_DTYPES[np.dtype(getattr(jnp, _fp8))] = "fp8"


def _quant_family(dt) -> typing.Optional[str]:
    if dt is None:
        return None
    try:
        return _QUANT_DTYPES.get(np.dtype(dt))
    except TypeError:
        return None


def census_of(step_trace) -> typing.Dict[str, typing.Any]:
    """Static per-call-site counts of collectives, upcasts and quantized
    ops for one step.  The ``quant`` sub-dict (``<family>_dot`` quantized
    dot_generals, ``<family>_cast`` quantize conversions) is present only
    when nonzero, so pre-quant goldens stay byte-stable; quant-enabled
    configs pin their counts like any other census key."""
    collectives: typing.Dict[str, int] = {}
    upcasts = 0
    n_eqns = 0
    quant: typing.Dict[str, int] = {}
    for eqn in iter_eqns(step_trace.jaxpr):
        n_eqns += 1
        name = COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if name is not None:
            collectives[name] = collectives.get(name, 0) + 1
        elif eqn.primitive.name == "convert_element_type":
            new = eqn.params.get("new_dtype")
            old = getattr(getattr(eqn.invars[0], "aval", None), "dtype", None)
            if (old is not None and new == jnp.float32
                    and old in (jnp.bfloat16, jnp.float16)):
                upcasts += 1
            fam = _quant_family(new)
            if fam is not None:
                quant[f"{fam}_cast"] = quant.get(f"{fam}_cast", 0) + 1
        elif eqn.primitive.name == "dot_general":
            for v in eqn.invars:
                fam = _quant_family(
                    getattr(getattr(v, "aval", None), "dtype", None))
                if fam is not None:
                    quant[f"{fam}_dot"] = quant.get(f"{fam}_dot", 0) + 1
                    break
    out = {"collectives": dict(sorted(collectives.items())),
           "half_to_f32_upcasts": upcasts,
           "n_eqns": n_eqns}
    if quant:
        out["quant"] = dict(sorted(quant.items()))
    return out


def golden_path(config_name: str) -> str:
    return os.path.join(GOLDENS_DIR, "census", config_name + ".json")


def _loc(traces: ConfigTraces, step: str) -> str:
    return f"configs/{traces.config_name}.json[{step}]"


def check_collective_census(traces: ConfigTraces,
                            update_goldens: bool = False
                            ) -> typing.List[Finding]:
    findings: typing.List[Finding] = []
    actual = {name: census_of(st) for name, st in sorted(traces.steps.items())}
    path = golden_path(traces.config_name)
    if update_goldens:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        import jax
        # merge over any existing golden: steps pinned earlier but not
        # traced this run (e.g. --steps train, or a toolchain that cannot
        # trace one step) keep their budget instead of being erased
        merged = dict(actual)
        if os.path.exists(path):
            with open(path) as f:
                for step, budget in json.load(f).get("steps", {}).items():
                    merged.setdefault(step, budget)
        with open(path, "w") as f:
            json.dump({"config": traces.config_name,
                       "mesh": {k: int(v) for k, v in traces.mesh.shape.items()},
                       "jax": jax.__version__,
                       "steps": merged}, f, indent=2, sort_keys=True)
            f.write("\n")
        findings.append(Finding(
            "collective-census", "info", path,
            f"golden updated ({', '.join(actual) or 'no steps'}"
            + (f"; kept {', '.join(sorted(set(merged) - set(actual)))}"
               if set(merged) - set(actual) else "") + ")"))
        return findings
    if not os.path.exists(path):
        findings.append(Finding(
            "collective-census", "error", _loc(traces, "*"),
            f"no golden budget at {os.path.relpath(path)}; run "
            f"`python tools/graftcheck.py --config configs/"
            f"{traces.config_name}.json --update-goldens`"))
        return findings
    with open(path) as f:
        golden = json.load(f)
    gsteps = golden.get("steps", {})
    for step in sorted(set(actual) | set(gsteps)):
        if step not in actual:
            findings.append(Finding(
                "collective-census", "warning", _loc(traces, step),
                "step present in golden but not traced this run "
                f"(trace errors: {traces.errors.get(step, 'step skipped')})"))
            continue
        if step not in gsteps:
            # a step outside the golden's recorded set (e.g. --steps eval
            # when the budget pins train+decode) is unpinned, not wrong
            findings.append(Finding(
                "collective-census", "warning", _loc(traces, step),
                "step traced but not pinned by the golden budget; record it "
                "with --update-goldens to gate it"))
            continue
        got, want = actual[step], gsteps[step]
        for key in sorted(set(got["collectives"]) | set(want["collectives"])):
            g = got["collectives"].get(key, 0)
            w = want["collectives"].get(key, 0)
            if g != w:
                findings.append(Finding(
                    "collective-census", "error", _loc(traces, step),
                    f"{key} count {g} != golden {w} — an unplanned "
                    f"collective usually means a sharding-spec mismatch; "
                    f"if intended, re-record with --update-goldens"))
        if got["half_to_f32_upcasts"] != want.get("half_to_f32_upcasts", 0):
            findings.append(Finding(
                "collective-census", "error", _loc(traces, step),
                f"half->f32 upcast count {got['half_to_f32_upcasts']} != "
                f"golden {want.get('half_to_f32_upcasts', 0)} — check the "
                f"hot path for unintended promotions; if intended, "
                f"re-record with --update-goldens"))
        gq, wq = got.get("quant", {}), want.get("quant", {})
        for key in sorted(set(gq) | set(wq)):
            if gq.get(key, 0) != wq.get(key, 0):
                findings.append(Finding(
                    "collective-census", "error", _loc(traces, step),
                    f"quantized-op count {key} {gq.get(key, 0)} != golden "
                    f"{wq.get(key, 0)} — the quant scope changed shape "
                    f"(ops/quant.py); if intended, re-record with "
                    f"--update-goldens"))
    return findings


def check_dtype_promotion(traces: ConfigTraces) -> typing.List[Finding]:
    cfg = traces.cfg
    declared_f64 = any(
        getattr(cfg, a) == jnp.float64
        for a in ("storage_dtype", "slice_dtype", "calculation_dtype",
                  "optimizer_slice_dtype", "optimizer_calculation_dtype"))
    if declared_f64:
        return []
    findings: typing.List[Finding] = []
    for step, st in sorted(traces.steps.items()):
        hits: typing.List[str] = []
        for eqn in iter_eqns(st.jaxpr):
            for v in eqn.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt in _F64:
                    hits.append(f"{eqn.primitive.name} -> {dt} at "
                                f"{eqn_location(eqn)}")
                    break
            if len(hits) >= 5:
                break
        for h in hits:
            findings.append(Finding(
                "dtype-promotion", "error", _loc(traces, step),
                f"f64 value in the graph ({h}); no config dtype declares "
                f"float64 — check for Python floats promoted via x64 or an "
                f"explicit astype"))
    return findings


def check_donation(traces: ConfigTraces) -> typing.List[Finding]:
    findings: typing.List[Finding] = []
    st = traces.steps.get("train")
    if st is not None and st.state_info is not None:
        import jax
        leaves = jax.tree_util.tree_leaves_with_path(st.state_info)
        missing = [jax.tree_util.keystr(path) for path, info in leaves
                   if not getattr(info, "donated", False)]
        shown = missing[:10]
        for name in shown:
            findings.append(Finding(
                "donation", "error", _loc(traces, "train"),
                f"train-state buffer {name} is not donated — the step keeps "
                f"a second copy live (check donate_argnums on the jitted "
                f"step, train/state.py)"))
        if len(missing) > len(shown):
            findings.append(Finding(
                "donation", "error", _loc(traces, "train"),
                f"... and {len(missing) - len(shown)} more non-donated "
                f"train-state buffers"))
    findings.extend(_check_serve_donation(traces))
    return findings


def _check_serve_donation(traces: ConfigTraces) -> typing.List[Finding]:
    """Serving twin of the train-state donation audit: the batch engine's
    decode/prefill executables carry the pooled KV caches, token pool,
    per-lane positions and rng as step state — abstractly trace the EXACT
    jitted functions the engine compiles (serve/engine.py::jit_executables)
    and require their pooled arguments donated.  Without donation the
    decode loop copies the whole KV pool every step on device (the
    ROADMAP continuous-batching residual this rule ratchets)."""
    from .trace import decode_traceable
    cfg = traces.cfg
    if not decode_traceable(cfg) or not traces.param_shapes:
        return []
    from ..serve import engine
    if not engine.use_batch_engine(cfg):
        # the serialized path allocates per-call caches — there is no pool
        # to donate; auditing the engine trace here would cost a full
        # decode-graph trace per config for a code path the config never
        # runs (the contract itself is pinned by the graftcheck tests on
        # an engine-enabled config)
        return []
    import jax
    findings: typing.List[Finding] = []
    params = traces.param_shapes
    if cfg.pipeline_parallel > 1:
        from ..models import pipeline_params_stacked, unstack_pipeline_params
        if pipeline_params_stacked(cfg, params):
            params = jax.eval_shape(
                lambda p: unstack_pipeline_params(cfg, p), params)
    if getattr(cfg, "serve_aot_cache_dir", ""):
        # the engine deliberately compiles WITHOUT donation when it
        # persists AOT executables (serialize_executable cannot round-trip
        # input-output aliasing on this toolchain — serve/engine.py) —
        # the audit below checks the donating contract the non-AOT path
        # uses, so surface the tradeoff instead of green-lighting it
        findings.append(Finding(
            "donation", "warning", _loc(traces, "serve"),
            "serve_aot_cache_dir is set: the batch engine compiles its "
            "executables WITHOUT pool donation (AOT serialization cannot "
            "round-trip input-output aliasing) — on device every decode "
            "step copies the whole KV pool; unset the cache dir on "
            "memory-bound deployments or re-verify donation once the "
            "toolchain serializes aliased executables"))
    rows = max(1, cfg.sequence_length // cfg.token_patch_size)
    # the pool geometry the engine actually runs (use_batch_engine gated
    # above, so serve_max_batch > 1 here)
    n_lanes = int(cfg.serve_max_batch)
    try:
        dec_jit, pre_jit, chk_jit = engine.jit_executables(cfg, rows,
                                                           n_lanes)
        dec_abs, pre_abs, chk_abs = engine.abstract_exec_args(cfg, params,
                                                              rows, n_lanes)
        audits = (("decode", dec_jit.trace(*dec_abs),
                   engine.DECODE_DONATE_ARGNUMS,
                   engine.DECODE_DONATE_ARG_NAMES),
                  ("prefill", pre_jit.trace(*pre_abs),
                   engine.PREFILL_DONATE_ARGNUMS,
                   engine.PREFILL_DONATE_ARG_NAMES))
        if chk_jit is not None and chk_abs is not None:
            # serve_prefill_chunk_tokens > 0: the chunk executable
            # carries the same pooled state — audit it too (knob off
            # keeps the audit, and the census goldens, byte-stable)
            audits += (("prefill_chunk", chk_jit.trace(*chk_abs),
                        engine.PREFILL_CHUNK_DONATE_ARGNUMS,
                        engine.PREFILL_CHUNK_DONATE_ARG_NAMES),)
    except Exception as e:
        return findings + [Finding(
            "donation", "warning", _loc(traces, "serve"),
            f"serving executables failed to trace for the donation audit: "
            f"{type(e).__name__}: {e}")]
    for step, traced, want, arg_names in audits:
        infos = traced.args_info[0]
        for idx in want:
            if idx >= len(infos):
                continue
            leaves = jax.tree_util.tree_leaves_with_path(infos[idx])
            missing = [jax.tree_util.keystr(p) for p, info in leaves
                       if not getattr(info, "donated", False)]
            if missing:
                findings.append(Finding(
                    "donation", "error", _loc(traces, f"serve_{step}"),
                    f"batch-engine {step} does not donate its "
                    f"{arg_names.get(idx, f'arg {idx}')} "
                    f"({len(missing)} buffer(s), e.g. {missing[0]}) — the "
                    f"device copies the whole pool every step; check "
                    f"donate_argnums in serve/engine.py::jit_executables"))
    return findings


class _IntendedMesh:
    """Duck-typed stand-in for spec_for's mesh argument carrying the axis
    sizes of the config's INTENDED pod (tpu_size), not the local CPU mesh."""

    def __init__(self, shape: typing.Dict[str, int]):
        self.shape = shape
        self.axis_names = tuple(shape)


def intended_mesh(cfg: Config) -> _IntendedMesh:
    try:
        sizes = axis_sizes(cfg, max(cfg.tpu_size, 1))
    except ValueError:
        sizes = {a: 1 for a in MESH_AXES}
    return _IntendedMesh(dict(sizes))


def check_sharding_specs(traces: ConfigTraces) -> typing.List[Finding]:
    findings: typing.List[Finding] = []
    known = set(MESH_AXES)
    # 1. the rule table itself: an unknown mesh axis is SILENTLY treated as
    # replicated by spec_for — the classic mis-shard
    for logical, mesh_axis in sorted(RULES.items()):
        if mesh_axis not in known:
            findings.append(Finding(
                "sharding-spec", "error", "homebrewnlp_tpu/parallel/sharding.py",
                f"RULES maps logical axis {logical!r} to unknown mesh axis "
                f"{mesh_axis!r} (known: {sorted(known)}) — spec_for silently "
                f"replicates it"))
    # 2. in-graph sharding annotations must only name real mesh axes
    for step, st in sorted(traces.steps.items()):
        seen_bad: typing.Set[str] = set()
        for eqn in iter_eqns(st.jaxpr):
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            if spec is None:
                continue
            for part in spec:
                axes = part if isinstance(part, tuple) else (part,)
                for ax in axes:
                    if ax is not None and ax not in known and ax not in seen_bad:
                        seen_bad.add(ax)
                        findings.append(Finding(
                            "sharding-spec", "error", _loc(traces, step),
                            f"sharding annotation names unknown mesh axis "
                            f"{ax!r} at {eqn_location(eqn)}"))
    # 3. large params fully replicated on the intended pod mesh
    imesh = intended_mesh(traces.cfg)
    if any(v > 1 for v in imesh.shape.values()):
        for name, sds in sorted(traces.param_shapes.items()):
            elems = int(np.prod(sds.shape)) if sds.shape else 1
            if elems < REPLICATED_PARAM_ELEMS:
                continue
            spec = spec_for(traces.param_axes.get(name, ()), imesh)
            if not any(p is not None for p in spec):
                findings.append(Finding(
                    "sharding-spec", "warning", _loc(traces, "params"),
                    f"parameter {name} ({elems} elements, axes "
                    f"{traces.param_axes.get(name, ())}) is fully replicated "
                    f"on the intended {dict(imesh.shape)} mesh — consider a "
                    f"sharding rule for one of its axes"))
    return findings


def check_constant_bloat(traces: ConfigTraces) -> typing.List[Finding]:
    findings: typing.List[Finding] = []
    for step, st in sorted(traces.steps.items()):
        for cj in iter_closed_jaxprs(st.jaxpr):
            for c in getattr(cj, "consts", ()):
                size = getattr(c, "size", 0)
                itemsize = getattr(getattr(c, "dtype", None), "itemsize", 1)
                nbytes = int(size) * int(itemsize)
                if nbytes < CONST_WARN_BYTES:
                    continue
                sev = "error" if nbytes >= CONST_ERROR_BYTES else "warning"
                findings.append(Finding(
                    "constant-bloat", sev, _loc(traces, step),
                    f"closed-over constant {getattr(c, 'shape', ())} "
                    f"{getattr(c, 'dtype', '?')} ({nbytes} bytes) is baked "
                    f"into the program — pass it as an argument (recompile "
                    f"hazard + per-executable HBM copy)"))
    return findings


def check_quant_dtype(traces: ConfigTraces) -> typing.List[Finding]:
    """Quantized-compute allowlist (ops/quant.py, docs/static_analysis.md):
    the config's ``quant_blocks`` knob is the ONLY sanctioned source of
    int8/fp8 compute.

    - A quantized op (int8/fp8 ``dot_general`` or quantize cast) in a step
      of a config that declares NO quant scope is an error — low-precision
      math must never leak in implicitly (an accidental integer-promotion
      dot has silently destroyed model quality before it showed in loss).
    - A declared quant scope whose traced TRAIN step contains no quantized
      ``dot_general`` is an error — the scope silently fell back to the
      high-precision path (pattern typo, fused-kernel bypass, or a dtype
      gate eating the knob), i.e. the run would report quantized speedups
      it is not taking.
    """
    cfg = traces.cfg
    declared = bool(getattr(cfg, "quant_blocks", ()))
    findings: typing.List[Finding] = []
    for step, st in sorted(traces.steps.items()):
        quant = census_of(st).get("quant", {})
        dots = sum(v for k, v in quant.items() if k.endswith("_dot"))
        if not declared and quant:
            findings.append(Finding(
                "quant-dtype", "error", _loc(traces, step),
                f"quantized ops in the graph ({quant}) but the config "
                f"declares no quant scope (quant_blocks is empty) — int8/"
                f"fp8 compute is only sanctioned through ops/quant.py "
                f"behind the quant_blocks knob"))
        if declared and step == "train" and dots == 0:
            findings.append(Finding(
                "quant-dtype", "error", _loc(traces, step),
                f"quant_blocks={list(cfg.quant_blocks)} is declared but the "
                f"traced train step contains no quantized dot_general — the "
                f"scope silently fell back to the high-precision path "
                f"(check the substrings against the layer scopes, and that "
                f"no fused kernel bypasses linear())"))
    return findings


def check_trace_errors(traces: ConfigTraces) -> typing.List[Finding]:
    """Trace failures are findings too: a step that does not trace on the
    one supported toolchain is an error."""
    return [Finding("trace", "error", _loc(traces, step),
                    f"step failed to trace: {err}")
            for step, err in sorted(traces.errors.items())]


def _config_tpu_size(name: str) -> typing.Optional[int]:
    """tpu_size from the raw config JSON (no Config construction, no jax) —
    None when the file is absent/unreadable.  The fallback default MUST
    match config.py's ``_DEFAULTS`` tpu_size."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "configs", name + ".json")
    try:
        with open(path) as f:
            return int(json.load(f).get("tpu_size", 32))
    except (OSError, ValueError, TypeError):
        return None


def check_golden_coverage(config_names: typing.Sequence[str]
                          ) -> typing.List[Finding]:
    """Tree-wide gate (run under --all-configs): every bundled config must
    have a census golden, a resources golden AND an spmd
    (implicit-collective) golden — and, when it declares a multi-device
    topology (tpu_size > 1), a mesh golden too — and no golden may outlive
    its config.  Previously a brand-new config silently skipped the census
    until someone traced it by hand — coverage is now an invariant, not a
    convention."""
    from .cost_model import resources_golden_path
    from .mesh_search import mesh_golden_path
    from .spmd import spmd_golden_path
    findings: typing.List[Finding] = []
    names = set(config_names)
    for kind, path_fn in (("census", golden_path),
                          ("resources", resources_golden_path),
                          ("spmd", spmd_golden_path),
                          ("mesh", mesh_golden_path)):
        have = set()
        d = os.path.dirname(path_fn("_"))
        if os.path.isdir(d):
            have = {os.path.splitext(f)[0] for f in os.listdir(d)
                    if f.endswith(".json")}
        missing = names - have
        if kind == "mesh":
            # only multi-device configs factor a mesh; a config whose raw
            # JSON cannot be read (e.g. a hypothetical name probed by
            # tests) is not held to the multi-device requirement
            missing = {n for n in missing
                       if (_config_tpu_size(n) or 1) > 1}
        for name in sorted(missing):
            findings.append(Finding(
                "golden-coverage", "error", f"configs/{name}.json",
                f"config has no {kind} golden — it would silently skip the "
                f"{kind} gate; run `python tools/graftcheck.py --config "
                f"configs/{name}.json --update-goldens`"))
        for name in sorted(have - names):
            findings.append(Finding(
                "golden-coverage", "warning", os.path.relpath(path_fn(name)),
                f"orphan {kind} golden: no configs/{name}.json — delete it "
                f"or restore the config"))
    # tree-wide (not per-config) goldens from the concurrency audit: the
    # sync rules error out themselves when theirs are missing, but only if
    # they run — this gate makes a deleted golden fail even rule-filtered
    # runs that skip them
    from .concurrency import (sync_lock_order_golden_path,
                              sync_shared_state_golden_path)
    for kind, path in (("sync shared-state", sync_shared_state_golden_path()),
                       ("sync lock-order", sync_lock_order_golden_path())):
        if not os.path.exists(path):
            findings.append(Finding(
                "golden-coverage", "error", os.path.relpath(path),
                f"missing {kind} golden — the concurrency audit would "
                f"refuse to ratchet; run `python tools/graftsync.py "
                f"--update-goldens`"))
    return findings


def run_graph_rules(traces: ConfigTraces, update_goldens: bool = False,
                    rules: typing.Optional[typing.Sequence[str]] = None
                    ) -> typing.List[Finding]:
    from .cost_model import check_resource_budget
    from .mesh_search import check_mesh_rank
    from .spmd import check_implicit_collectives
    table = {
        "collective-census": lambda t: check_collective_census(t, update_goldens),
        "dtype-promotion": check_dtype_promotion,
        "quant-dtype": check_quant_dtype,
        "donation": check_donation,
        "sharding-spec": check_sharding_specs,
        "constant-bloat": check_constant_bloat,
        "resource-budget": lambda t: check_resource_budget(t, update_goldens),
        "implicit-collective":
            lambda t: check_implicit_collectives(t, update_goldens),
        "mesh-rank": lambda t: check_mesh_rank(t, update_goldens),
    }
    findings = check_trace_errors(traces)
    for name, fn in table.items():
        if rules is None or name in rules:
            findings.extend(fn(traces))
    return findings
