"""graftcheck static-analysis subsystem: trace harness, graph rules (census
goldens, donation, sharding specs, constant bloat), AST lint (axis literals,
f64 requests, RNG/time, PartitionSpec axes, .x ratchet), NT scope-named
errors, and the CLI."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from homebrewnlp_tpu import nd
from homebrewnlp_tpu.analysis import (ast_rules, graph_rules, trace as
                                      atrace)
from homebrewnlp_tpu.analysis.findings import Finding, worst_severity
from homebrewnlp_tpu.config import Config

from .backend import tiny_config


def _load_config(name):
    raw = json.load(open(os.path.join(REPO, "configs", name)))
    raw.pop("_comment", None)
    return Config(raw)


# -- NT scope-path errors (ISSUE satellite) ---------------------------------

def test_nt_rank_mismatch_names_scope():
    nd.push_scope("gpt")
    nd.push_scope("body")
    try:
        with pytest.raises(ValueError, match=r"gpt/body"):
            nd.NT(jnp.zeros((2, 3)), ("batch",))
    finally:
        nd.pop_scope()
        nd.pop_scope()
    # outside any scope the message stays shape-only
    with pytest.raises(ValueError) as e:
        nd.NT(jnp.zeros((2, 3)), ("batch",))
    assert "scope" not in str(e.value)


def test_model_build_error_names_layer_scope():
    """A rank mismatch raised while building a real model names the
    enclosing block scope, making analyzer findings actionable."""
    from homebrewnlp_tpu.models import build
    from homebrewnlp_tpu.models.ctx import Ctx
    from homebrewnlp_tpu.models.registry import LAYER_FUNCTIONS
    from .backend import text_batch
    cfg = tiny_config()
    batch = text_batch(cfg)
    orig = LAYER_FUNCTIONS["feed_forward"]

    def broken(args):
        out = orig(args)
        return nd.NT(out.x, out.names[:-1])  # drop a name -> rank mismatch

    LAYER_FUNCTIONS["feed_forward"] = broken
    try:
        with pytest.raises(ValueError, match=r"scope '.*body.*'"):
            build(Ctx(cfg, params=None, seed=0, train=False), batch)
    finally:
        LAYER_FUNCTIONS["feed_forward"] = orig


def test_axis_registry_has_canonical_names():
    known = nd.known_axes()
    for name in ("batch", "sequence", "heads", "features_per_head", "vocab",
                 "pipe_stage"):
        assert name in known, name


# -- trace harness ----------------------------------------------------------

def test_trace_tiny_config_train_and_decode(eight_devices):
    cfg = tiny_config()
    traces = atrace.trace_config(cfg, "tiny", steps=("train", "eval",
                                                     "decode"))
    assert not traces.errors, traces.errors
    assert set(traces.steps) == {"train", "eval", "decode"}
    assert traces.param_shapes and traces.param_axes
    # abstract params: no leaf is a concrete array
    for v in traces.param_shapes.values():
        assert isinstance(v, jax.ShapeDtypeStruct)
    census = graph_rules.census_of(traces.steps["train"])
    assert census["n_eqns"] > 0
    # clean tree: donation + dtype + sharding + const rules all quiet
    # (golden-backed rules excluded: the ad-hoc "tiny" config has none)
    findings = [f for f in graph_rules.run_graph_rules(traces)
                if f.rule not in ("collective-census", "resource-budget",
                                  "implicit-collective", "mesh-rank")]
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, [f.render() for f in errors]


def test_composed_dryrun_census_matches_golden(eight_devices):
    """The DP/SP/PP/TP composed config (ring attention nested in 1F1B
    pipeline stages) traces and its collective census matches the committed
    golden — the ppermute budget only changes deliberately."""
    cfg = _load_config("8dev_composed_dryrun.json")
    traces = atrace.trace_config(cfg, "8dev_composed_dryrun",
                                 steps=("train", "decode"))
    assert not traces.errors, traces.errors
    findings = graph_rules.check_collective_census(traces)
    assert not findings, [f.render() for f in findings]
    census = graph_rules.census_of(traces.steps["train"])
    # the composed graph must actually move data around the rings: pipeline
    # hops + ring attention rotations
    assert census["collectives"].get("ppermute", 0) >= 8, census


def test_census_diff_detected(eight_devices, monkeypatch, tmp_path):
    """An unplanned collective (census drift vs golden) is an error."""
    cfg = tiny_config()
    traces = atrace.trace_config(cfg, "tinycensus", steps=("train",))
    monkeypatch.setattr(graph_rules, "GOLDENS_DIR", str(tmp_path))
    # record, verify clean, then tamper the golden budget
    graph_rules.check_collective_census(traces, update_goldens=True)
    assert graph_rules.check_collective_census(traces) == []
    path = graph_rules.golden_path("tinycensus")
    golden = json.load(open(path))
    train = golden["steps"]["train"]
    train["collectives"]["all_gather"] = \
        train["collectives"].get("all_gather", 0) + 2
    json.dump(golden, open(path, "w"))
    findings = graph_rules.check_collective_census(traces)
    assert any(f.severity == "error" and "all_gather" in f.message
               for f in findings), [f.render() for f in findings]


# -- graph rules: seeded defects --------------------------------------------

def test_injected_bad_partitionspec_rule_is_caught(eight_devices,
                                                   monkeypatch):
    """Regression (ISSUE acceptance): a mesh-unknown axis in the sharding
    rule table — which spec_for silently replicates — fails the validator."""
    from homebrewnlp_tpu.parallel import sharding as shmod
    cfg = tiny_config()
    traces = atrace.trace_config(cfg, "tiny", steps=())
    bad = dict(shmod.RULES)
    bad["batch"] = "dataa"  # graftcheck: disable=partitionspec-axis
    monkeypatch.setattr(graph_rules, "RULES", bad)
    findings = graph_rules.check_sharding_specs(traces)
    assert any(f.severity == "error" and "dataa" in f.message
               for f in findings), [f.render() for f in findings]
    # clean table passes
    monkeypatch.setattr(graph_rules, "RULES", dict(shmod.RULES))
    assert not [f for f in graph_rules.check_sharding_specs(traces)
                if f.severity == "error"]


def test_dropped_donation_is_caught(eight_devices):
    """A train step jitted WITHOUT donate_argnums fails the donation audit;
    the real step (donating) passes."""
    from homebrewnlp_tpu.train.state import TrainState
    cfg = tiny_config()
    traces = atrace.trace_config(cfg, "tiny", steps=("train",))
    assert graph_rules.check_donation(traces) == []

    params = traces.param_shapes
    state = TrainState(params, {}, jax.ShapeDtypeStruct((), jnp.int32))

    def fake_step(state, rng):
        return state

    traced = jax.jit(fake_step).trace(state, jax.random.key(0))
    st = atrace.StepTrace("train", traced.jaxpr, traces.mesh,
                          traced.args_info, traced.args_info[0][0])
    bad = atrace.ConfigTraces("tiny", cfg, traces.mesh, {"train": st},
                              traces.param_axes, params, {})
    findings = graph_rules.check_donation(bad)
    assert findings and all(f.severity == "error" for f in findings)
    assert "donate" in findings[0].message


def test_serve_donation_audit_passes_on_batch_engine_config(eight_devices):
    """The donation rule's serving extension: a KV-cache-eligible config
    running the continuous-batching engine traces the engine's EXACT
    jitted decode/prefill executables and finds the pooled state donated
    (the ROADMAP cache-donation residual, now ratcheted)."""
    from .backend import mixer_config
    cfg = mixer_config(depth=1, sequence_length=12, heads=2,
                       features_per_head=16, vocab_size=32,
                       train_batch_size=1, serve_max_batch=2)
    traces = atrace.trace_config(cfg, "engine_tiny", steps=("train",))
    assert graph_rules.check_donation(traces) == []


def test_serve_donation_dropped_is_caught(eight_devices, monkeypatch):
    """Seeded regression: stripping donate_argnums from the engine's
    executables must fail the donation audit naming the pooled buffers."""
    from homebrewnlp_tpu.serve import engine
    from .backend import mixer_config
    cfg = mixer_config(depth=1, sequence_length=12, heads=2,
                       features_per_head=16, vocab_size=32,
                       train_batch_size=1, serve_max_batch=2)
    traces = atrace.trace_config(cfg, "engine_tiny", steps=("train",))
    orig = engine.jit_executables

    def undonated(cfg, rows, n_lanes, first_token_cb=None):
        import functools
        dec = functools.partial(engine.decode_body, cfg, rows, n_lanes,
                                first_token_cb)
        pre = functools.partial(engine.prefill_body, cfg, rows)
        return jax.jit(dec), jax.jit(pre), None

    monkeypatch.setattr(engine, "jit_executables", undonated)
    findings = graph_rules.check_donation(traces)
    assert findings and all(f.severity == "error" for f in findings)
    assert any("pooled KV caches" in f.message for f in findings)
    assert any("serve_decode" in f.location for f in findings)
    assert any("serve_prefill" in f.location for f in findings)
    monkeypatch.setattr(engine, "jit_executables", orig)
    # serialized-path configs (serve_max_batch=1) skip the engine audit
    cfg1 = mixer_config(depth=1, sequence_length=12, heads=2,
                        features_per_head=16, vocab_size=32,
                        train_batch_size=1, serve_max_batch=1)
    t1 = atrace.trace_config(cfg1, "serialized_tiny", steps=("train",))
    assert graph_rules._check_serve_donation(t1) == []


def test_serve_donation_warns_on_aot_no_donate_tradeoff(eight_devices,
                                                       tmp_path):
    """serve_aot_cache_dir engines compile undonated (the serialization
    tradeoff) — the audit must surface that as a warning, never a silent
    green."""
    from .backend import mixer_config
    cfg = mixer_config(depth=1, sequence_length=12, heads=2,
                       features_per_head=16, vocab_size=32,
                       train_batch_size=1, serve_max_batch=2,
                       serve_aot_cache_dir=str(tmp_path))
    traces = atrace.trace_config(cfg, "engine_aot", steps=("train",))
    findings = graph_rules.check_donation(traces)
    warns = [f for f in findings if f.severity == "warning"]
    assert any("WITHOUT pool donation" in f.message for f in warns)
    assert not [f for f in findings if f.severity == "error"]


def test_constant_bloat_detected(eight_devices):
    big = jnp.asarray(np.ones((512, 1024), np.float32))  # 2 MB closure

    def f(x):
        return x @ big

    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 512), jnp.float32))
    cfg = tiny_config()
    mesh = traces_mesh = None
    st = atrace.StepTrace("train", jaxpr, traces_mesh)
    traces = atrace.ConfigTraces("tiny", cfg, mesh, {"train": st}, {}, {}, {})
    findings = graph_rules.check_constant_bloat(traces)
    assert any(f.severity == "error" for f in findings), findings


def test_f64_in_graph_detected(eight_devices):
    """The jaxpr-level dtype audit flags real f64 avals (as produced when
    x64 is enabled)."""
    import dataclasses

    def f(x):
        return x + 1

    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
    # forge an f64 aval on the output eqn (x64 cannot be toggled in-process)
    eqn = jaxpr.jaxpr.eqns[-1]
    var = eqn.outvars[0]
    var.aval = var.aval.update(dtype=jnp.dtype("float64"))
    cfg = tiny_config()
    st = atrace.StepTrace("train", jaxpr, None)
    traces = atrace.ConfigTraces("tiny", cfg, None, {"train": st}, {}, {}, {})
    findings = graph_rules.check_dtype_promotion(traces)
    assert findings and findings[0].severity == "error"
    assert "f64" in findings[0].message


# -- quant-dtype allowlist (ISSUE 6) ----------------------------------------

def _quant_mixer_traces(**overrides):
    from .backend import mixer_config
    cfg = mixer_config(quant_blocks=["bottleneck_group_linear"], **overrides)
    traces = atrace.trace_config(cfg, "tinyquant", steps=("train",))
    assert not traces.errors, traces.errors
    return traces


def test_quant_census_counts_and_rule_clean(eight_devices):
    """A declared quant scope shows int8 dots + casts in the census and the
    quant-dtype rule passes; an undeclared config's census carries NO quant
    key (goldens stay byte-stable)."""
    traces = _quant_mixer_traces()
    census = graph_rules.census_of(traces.steps["train"])
    assert census["quant"]["int8_dot"] > 0
    assert census["quant"]["int8_cast"] > 0
    assert graph_rules.check_quant_dtype(traces) == []
    from .backend import mixer_config
    plain = atrace.trace_config(mixer_config(), "tinyplain",
                                steps=("train",))
    assert "quant" not in graph_rules.census_of(plain.steps["train"])
    assert graph_rules.check_quant_dtype(plain) == []


def test_quant_outside_declared_scope_is_error(eight_devices):
    """Seeded regression: int8 ops in a graph whose config declares NO
    quant scope fail the ratchet (the allowlist direction)."""
    import dataclasses
    traces = _quant_mixer_traces()
    undeclared = dataclasses.replace(traces, cfg=tiny_config())
    findings = graph_rules.check_quant_dtype(undeclared)
    assert findings and all(f.severity == "error" for f in findings)
    assert "quant_blocks is empty" in findings[0].message


def test_quant_silent_fallback_is_error(eight_devices):
    """Seeded regression: a declared scope that matches no layer (typo /
    fused-kernel bypass) compiles zero quantized dots — an error, not a
    silently-unquantized 'success'."""
    from .backend import mixer_config
    cfg = mixer_config(quant_blocks=["bottleneck_gruop_linear"])  # typo
    traces = atrace.trace_config(cfg, "tinytypo", steps=("train",))
    assert not traces.errors, traces.errors
    findings = graph_rules.check_quant_dtype(traces)
    assert findings and findings[0].severity == "error"
    assert "silently fell back" in findings[0].message


def test_quant_census_drift_detected(eight_devices, monkeypatch, tmp_path):
    """The quant counts are ratcheted through the census golden: a pinned
    int8_dot figure that stops matching the trace is an error."""
    traces = _quant_mixer_traces()
    monkeypatch.setattr(graph_rules, "GOLDENS_DIR", str(tmp_path))
    graph_rules.check_collective_census(traces, update_goldens=True)
    assert graph_rules.check_collective_census(traces) == []
    path = graph_rules.golden_path("tinyquant")
    golden = json.load(open(path))
    golden["steps"]["train"]["quant"]["int8_dot"] += 2
    json.dump(golden, open(path, "w"))
    findings = graph_rules.check_collective_census(traces)
    assert any(f.severity == "error" and "int8_dot" in f.message
               for f in findings), [f.render() for f in findings]


def test_quant_committed_config_golden_matches(eight_devices):
    """The bundled 32mixer_group_int8 config: census golden (incl. the
    pinned quant counts) matches and the quant-dtype rule is green, on a
    shrunk twin of the real trace path."""
    cfg = _load_config("32mixer_group_int8.json")
    assert cfg.quant_blocks == ["bottleneck_group_linear"]
    traces = atrace.trace_config(cfg, "32mixer_group_int8",
                                 steps=("train",))
    assert not traces.errors, traces.errors
    assert graph_rules.check_quant_dtype(traces) == []
    census = graph_rules.census_of(traces.steps["train"])
    golden = json.load(open(graph_rules.golden_path("32mixer_group_int8")))
    assert census["quant"] == golden["steps"]["train"]["quant"]


# -- AST rules --------------------------------------------------------------

def _mini_tree(tmp_path, models_src="", ops_src=""):
    for rel, src in (("homebrewnlp_tpu/models/m.py", models_src),
                     ("homebrewnlp_tpu/ops/o.py", ops_src),
                     ("homebrewnlp_tpu/infer/__init__.py", ""),
                     ("homebrewnlp_tpu/data/__init__.py", ""),
                     ("homebrewnlp_tpu/optim/__init__.py", ""),
                     ("homebrewnlp_tpu/train/__init__.py", ""),
                     ("tools/__init__.py", "")):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return str(tmp_path)


def test_ast_axis_literal_typo_caught(tmp_path):
    root = _mini_tree(tmp_path, models_src=(
        "from homebrewnlp_tpu.nd import NT\n"
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    a = NT(x, ('batch', 'sequnce'))\n"          # typo -> error
        "    b = a.rename('sequence', '_sequence')\n"    # anonymized ok
        "    return b\n"))
    findings = ast_rules.check_axis_literals(root)
    assert len(findings) == 1 and "sequnce" in findings[0].message
    assert findings[0].location.endswith("m.py:4")


def test_ast_axis_literal_suppression(tmp_path):
    root = _mini_tree(tmp_path, models_src=(
        "from homebrewnlp_tpu.nd import NT\n"
        "def f(x):\n"
        "    return NT(x, ('totally_custom',))"
        "  # graftcheck: disable=axis-literal\n"))
    assert ast_rules.check_axis_literals(root) == []


def test_ast_f64_literal_caught(tmp_path):
    root = _mini_tree(tmp_path, models_src=(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return x.astype(jnp.float64)\n"))
    findings = ast_rules.check_f64_literals(root)
    assert len(findings) == 1 and findings[0].severity == "error"


def test_ast_traced_rng_caught(tmp_path):
    root = _mini_tree(tmp_path, ops_src=(
        "import time\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    t = time.time()\n"
        "    r = np.random.normal()\n"
        "    return x + r + t\n"))
    findings = ast_rules.check_traced_rng(root)
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 2 and "time.time" in msgs and "np.random" in msgs


def test_ast_partitionspec_unknown_axis_caught(tmp_path):
    root = _mini_tree(tmp_path, models_src=(
        "from jax.sharding import PartitionSpec\n"
        "SPEC = PartitionSpec('data', 'modell')\n"))
    findings = ast_rules.check_partitionspec_literals(root)
    assert len(findings) == 1 and "modell" in findings[0].message


def test_ast_x_escape_ratchet(tmp_path, monkeypatch):
    root = _mini_tree(tmp_path, models_src=(
        "def f(t):\n    return t.x + t.x\n"))
    golden = tmp_path / "goldens" / "ast_x_escapes.json"
    monkeypatch.setattr(ast_rules, "x_escape_golden_path",
                        lambda: str(golden))
    ast_rules.check_x_escapes(root, update_goldens=True)
    assert ast_rules.check_x_escapes(root) == []
    # a NEW escape beyond the ratchet is an error
    p = tmp_path / "homebrewnlp_tpu/models/m.py"
    p.write_text(p.read_text() + "\ndef g(t):\n    return t.x\n")
    findings = ast_rules.check_x_escapes(root)
    assert len(findings) == 1 and findings[0].severity == "error"


_HOST_SYNC_TRAIN = (
    "def train(cfg, args):\n"
    "    total = float(cfg.learning_rate)\n"      # outside the loop: free
    "    for u in range(10):\n"
    "        state, metrics = step(state, u)\n"
    "        print(float(metrics['loss']))\n"     # seeded regression
    "        s = int(state.step)\n"
    "        metrics['loss'].block_until_ready()\n"
    "    return total\n")


def test_ast_host_sync_seeded_regression_caught(tmp_path, monkeypatch):
    """ISSUE acceptance: a seeded float(loss) (plus int(step) and
    block_until_ready) inside train()'s step loop fails the host-sync
    ratchet; host code outside the loop does not count."""
    root = _mini_tree(tmp_path)
    (tmp_path / "homebrewnlp_tpu/main.py").write_text(_HOST_SYNC_TRAIN)
    golden = tmp_path / "goldens" / "ast_host_sync.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "host_sync_golden_path",
                        lambda: str(golden))
    assert ast_rules.host_sync_counts(root) == {"homebrewnlp_tpu/main.py": 3}
    findings = ast_rules.check_host_sync(root)
    assert len(findings) == 1 and findings[0].severity == "error"
    assert "device->host" in findings[0].message
    # deliberate syncs ratchet: re-record, then clean; removing one is info
    ast_rules.check_host_sync(root, update_goldens=True)
    assert ast_rules.check_host_sync(root) == []
    (tmp_path / "homebrewnlp_tpu/main.py").write_text(
        _HOST_SYNC_TRAIN.replace("        s = int(state.step)\n", ""))
    improved = ast_rules.check_host_sync(root)
    assert len(improved) == 1 and improved[0].severity == "info"


def test_ast_host_sync_suppression_and_scope(tmp_path, monkeypatch):
    root = _mini_tree(tmp_path)
    (tmp_path / "homebrewnlp_tpu/main.py").write_text(
        "def train(cfg, args):\n"
        "    for u in range(10):\n"
        "        s = int(u)  # graftcheck: disable=host-sync\n"
        "    return s\n"
        "def sample(cfg, args):\n"
        "    for i in range(3):\n"
        "        print(float(i))\n")  # not train(): out of scope
    golden = tmp_path / "goldens" / "ast_host_sync.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "host_sync_golden_path",
                        lambda: str(golden))
    assert ast_rules.host_sync_counts(root) == {}
    assert ast_rules.check_host_sync(root) == []


def test_ast_host_sync_repo_loop_is_clean():
    """The shipped async train loop carries ZERO host syncs — the ratchet
    golden pins the empty count, so any reintroduced device read fails."""
    assert ast_rules.host_sync_counts(REPO) == {}
    assert json.load(open(ast_rules.host_sync_golden_path())) == {}


def test_ast_obs_in_trace_seeded_regression_caught(tmp_path, monkeypatch):
    """ISSUE satellite: a span/registry call inside jit-traced code (models/,
    ops/) fails the obs-in-trace ratchet — every obs import style roots."""
    root = _mini_tree(tmp_path, models_src=(
        "from ..obs.spans import span\n"
        "from homebrewnlp_tpu.obs import REGISTRY as reg\n"
        "def layer(x):\n"
        "    with span('layer'):\n"                  # rooted call 1
        "        reg.counter('bad_total').inc()\n"   # 2 rooted calls:
        "    return x\n"), ops_src=(                 #  .counter() and .inc()
        "import homebrewnlp_tpu.obs.spans as spans\n"
        "def kernel(x):\n"
        "    with spans.span('k'):\n"                # rooted call
        "        return x\n"))
    golden = tmp_path / "goldens" / "ast_obs_in_trace.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "obs_in_trace_golden_path",
                        lambda: str(golden))
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/models/m.py": 3,
                      "homebrewnlp_tpu/ops/o.py": 1}, counts
    findings = ast_rules.check_obs_in_trace(root)
    assert len(findings) == 2
    assert all(f.severity == "error" for f in findings)
    assert "jit-traced" in findings[0].message
    # the ratchet can pin deliberate exceptions, then only go down
    ast_rules.check_obs_in_trace(root, update_goldens=True)
    assert ast_rules.check_obs_in_trace(root) == []


def test_ast_obs_in_trace_package_import_form(tmp_path, monkeypatch):
    """`from .. import obs` (module=None carries no 'obs' component) must
    still root: it is the most natural way to smuggle a registry call in."""
    root = _mini_tree(tmp_path, models_src=(
        "from .. import obs\n"
        "def layer(x):\n"
        "    obs.REGISTRY.counter('bad_total').inc()\n"
        "    return x\n"), ops_src=(
        "from homebrewnlp_tpu import obs as o\n"
        "def kernel(x):\n"
        "    with o.span('k'):\n"
        "        return x\n"))
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/models/m.py": 2,
                      "homebrewnlp_tpu/ops/o.py": 1}, counts


def test_ast_obs_in_trace_bare_dotted_import_precise(tmp_path):
    """A bare `import homebrewnlp_tpu.obs.spans` binds only the top-level
    name: calls through it count ONLY when the chain passes through obs —
    an unrelated `homebrewnlp_tpu.nd.*` call in the same file must not."""
    root = _mini_tree(tmp_path, models_src=(
        "import homebrewnlp_tpu.obs.spans\n"
        "import homebrewnlp_tpu.nd\n"
        "def layer(x):\n"
        "    homebrewnlp_tpu.nd.register_axis('rows')\n"   # NOT obs: clean
        "    with homebrewnlp_tpu.obs.spans.span('bad'):\n"  # obs: counts
        "        return x\n"))
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/models/m.py": 1}, counts


def test_ast_obs_in_trace_suppression_and_host_code_free(tmp_path,
                                                         monkeypatch):
    root = _mini_tree(tmp_path, models_src=(
        "from ..obs.spans import span\n"
        "def layer(x):\n"
        "    with span('ok'):  # graftcheck: disable=obs-in-trace\n"
        "        return x\n"))
    # host-layer code (data/, train/, serve/, main) is OUT of scope: the
    # same import + call in data/ must not count
    p = tmp_path / "homebrewnlp_tpu/data/feedish.py"
    p.write_text("from ..obs.spans import span\n"
                 "def feed(x):\n"
                 "    with span('feed'):\n"
                 "        return x\n")
    golden = tmp_path / "goldens" / "ast_obs_in_trace.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "obs_in_trace_golden_path",
                        lambda: str(golden))
    assert ast_rules.obs_in_trace_counts(root) == {}
    assert ast_rules.check_obs_in_trace(root) == []


def test_ast_obs_in_trace_repo_is_clean():
    """The shipped traced code (models/ops/infer/optim/train-step) carries
    ZERO forbidden obs calls; the committed golden pins the empty count.
    train/state.py is IN scope and imports the allowlisted device_telemetry
    — proof the allowlist admits exactly that module and nothing else."""
    assert ast_rules.obs_in_trace_counts(REPO) == {}
    assert json.load(open(ast_rules.obs_in_trace_golden_path())) == {}
    state_src = open(os.path.join(
        REPO, "homebrewnlp_tpu", "train", "state.py")).read()
    assert "device_telemetry" in state_src  # the allowlist is exercised


def test_ast_obs_in_trace_device_telemetry_allowlist(tmp_path):
    """ISSUE satellite: device_telemetry is the ONE obs module legal in
    traced code — every import style of it passes, while spans/registry use
    in the same files still fires."""
    root = _mini_tree(tmp_path, models_src=(
        "from ..obs import device_telemetry\n"
        "from ..obs.device_telemetry import collect\n"
        "import homebrewnlp_tpu.obs.device_telemetry as dt\n"
        "def layer(g):\n"
        "    ok, nf = device_telemetry.grads_finite(g)\n"   # allowed
        "    c = collect(g, g, {}, 1.0, nf, ok, None)\n"    # allowed
        "    return dt.thin(c, 0, 1)\n"), ops_src=(         # allowed
        "import homebrewnlp_tpu.obs.device_telemetry\n"
        "def kernel(g):\n"
        "    return homebrewnlp_tpu.obs.device_telemetry.grads_finite(g)\n"))
    assert ast_rules.obs_in_trace_counts(root) == {}
    # the allowlist must not leak: spans use NEXT TO a device_telemetry
    # import in the same file still counts
    root = _mini_tree(tmp_path / "mixed", models_src=(
        "from ..obs import device_telemetry\n"
        "from ..obs import spans\n"
        "def layer(g):\n"
        "    with spans.span('bad'):\n"                      # forbidden
        "        return device_telemetry.grads_finite(g)\n"))  # allowed
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/models/m.py": 1}, counts


def test_ast_obs_in_trace_allowlist_cannot_shield_siblings(tmp_path):
    """Review regression: a bare dotted import of the ALLOWLISTED module
    must not whitelist a sibling obs call through the same root — the
    chain filter decides per call site."""
    root = _mini_tree(tmp_path, models_src=(
        "import homebrewnlp_tpu.obs.device_telemetry\n"
        "def layer(g):\n"
        "    homebrewnlp_tpu.obs.spans.span('bad')\n"              # counts
        "    return homebrewnlp_tpu.obs.device_telemetry.thin(g, 0, 1)\n"))
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/models/m.py": 1}, counts


def test_ast_obs_in_trace_train_state_in_scope(tmp_path):
    """train/state.py joined the traced scope: a registry call seeded there
    fails the ratchet (the step function it builds IS traced code)."""
    root = _mini_tree(tmp_path)
    p = tmp_path / "homebrewnlp_tpu/train/state.py"
    p.write_text("from ..obs.registry import REGISTRY\n"
                 "def step_fn(s):\n"
                 "    REGISTRY.counter('bad_total').inc()\n"
                 "    return s\n")
    counts = ast_rules.obs_in_trace_counts(root)
    assert counts == {"homebrewnlp_tpu/train/state.py": 2}, counts


def test_ast_rules_clean_on_repo():
    """The committed tree carries no AST-lint errors (ratchet is current)."""
    findings = ast_rules.run_ast_rules(REPO)
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, "\n".join(f.render() for f in errors)


# -- findings / CLI ---------------------------------------------------------

def test_worst_severity_ordering():
    mk = lambda s: Finding("r", s, "loc", "m")
    assert worst_severity([]) is None
    assert worst_severity([mk("info"), mk("warning")]) == "warning"
    assert worst_severity([mk("warning"), mk("error"), mk("info")]) == "error"


def test_cli_ast_only_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
         "--ast-only"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no findings" in proc.stdout


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
         "--list-rules"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    for rule in ("collective-census", "donation", "axis-literal"):
        assert rule in proc.stdout


@pytest.mark.slow
def test_cli_all_configs_clean():
    """The full CI gate: every bundled config audits clean in one process
    (the ISSUE acceptance bound is 120 s on CPU)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
         "--all-configs"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no findings" in proc.stdout


# -- bare-io ratchet (ISSUE 4) ------------------------------------------------

def test_ast_bare_io_seeded_regression_caught(tmp_path, monkeypatch):
    """ISSUE satellite: unwrapped open()/orbax calls in the train/data hot
    paths fail the bare-io ratchet (golden committed at zero)."""
    root = _mini_tree(tmp_path)
    (tmp_path / "homebrewnlp_tpu/train/ckpt.py").write_text(
        "import orbax.checkpoint as ocp\n"
        "from orbax.checkpoint import CheckpointManager as CM\n"
        "def save(self, step, tree):\n"
        "    mgr = ocp.CheckpointManager('/ckpt')\n"     # bare construction
        "    mgr2 = CM('/ckpt2')\n"                      # aliased ctor
        "    self.manager.save(step, tree)\n"            # bare save
        "    self.manager.wait_until_finished()\n"       # bare barrier
        "    with open('sidecar.json', 'w') as f:\n"     # bare open
        "        f.write('{}')\n")
    (tmp_path / "homebrewnlp_tpu/data/reader.py").write_text(
        "def read(path):\n"
        "    return open(path, 'rb').read()\n")          # bare open
    golden = tmp_path / "goldens" / "ast_bare_io.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "bare_io_golden_path",
                        lambda: str(golden))
    counts = ast_rules.bare_io_counts(root)
    assert counts == {"homebrewnlp_tpu/train/ckpt.py": 5,
                      "homebrewnlp_tpu/data/reader.py": 1}, counts
    findings = ast_rules.check_bare_io(root)
    assert len(findings) == 2
    assert all(f.severity == "error" for f in findings)
    assert "reliability.retry" in findings[0].message


def test_ast_bare_io_suppression_and_exemptions(tmp_path, monkeypatch):
    """Retry-wrapped sites carry the disable comment; fs.py/synthetic.py
    (the I/O layer and fixture generation) are exempt; unrelated .save()
    calls (no manager in the chain) and non-orbax constructors are clean."""
    root = _mini_tree(tmp_path)
    (tmp_path / "homebrewnlp_tpu/train/ckpt.py").write_text(
        "def save(self, step, tree):\n"
        "    self.manager.save(step, tree)  # graftcheck: disable=bare-io\n"
        "    self.writer.save(step)\n"            # not a manager chain
        "    CheckpointManager('/x')\n")          # not an orbax alias
    (tmp_path / "homebrewnlp_tpu/data/fs.py").write_text(
        "def open_stream(path, mode='rb'):\n"
        "    return open(path, mode)\n")
    (tmp_path / "homebrewnlp_tpu/data/synthetic.py").write_text(
        "def write(path):\n"
        "    open(path, 'w').write('x')\n")
    golden = tmp_path / "goldens" / "ast_bare_io.json"
    golden.parent.mkdir(parents=True, exist_ok=True)
    golden.write_text("{}")
    monkeypatch.setattr(ast_rules, "bare_io_golden_path",
                        lambda: str(golden))
    assert ast_rules.bare_io_counts(root) == {}
    assert ast_rules.check_bare_io(root) == []


def test_ast_bare_io_repo_is_clean():
    """The committed golden is ZERO and the tree satisfies it: every hot-
    path I/O call routes through reliability.retry or data/fs.py."""
    assert ast_rules.bare_io_counts(REPO) == {}
    assert json.load(open(ast_rules.bare_io_golden_path())) == {}


# -- census normalization -----------------------------------------------------

def test_collective_prims_cover_both_toolchain_spellings():
    """Census normalization: the typed-shard_map toolchain spellings and the
    legacy ones both land on one census family."""
    P = atrace.COLLECTIVE_PRIMS
    assert P["psum"] == P["psum2"] == P["psum_invariant"] == "psum"
    assert P["all_gather"] == P["all_gather_invariant"] == "all_gather"
    assert P["reduce_scatter"] == P["psum_scatter"] == "reduce_scatter"


# -- golden-coverage gate (ISSUE 7 satellite) --------------------------------

def test_golden_coverage_gate_detects_missing_and_orphans():
    import glob as _glob
    from homebrewnlp_tpu.analysis import check_golden_coverage
    names = [os.path.splitext(os.path.basename(p))[0] for p in
             _glob.glob(os.path.join(REPO, "configs", "*.json"))]
    # the committed tree is fully covered
    assert check_golden_coverage(names) == []
    # a brand-new config without goldens is an ERROR for census, resources
    # AND the spmd (implicit-collective) census
    findings = check_golden_coverage(names + ["brand_new_config"])
    errs = [f for f in findings if f.severity == "error"]
    assert len(errs) == 3 and all("brand_new_config" in f.location
                                  for f in errs)
    kinds = {("census" in f.message and "spmd" not in f.message,
              "resources" in f.message, "spmd" in f.message)
             for f in errs}
    assert kinds == {(True, False, False), (False, True, False),
                     (False, False, True)}
    # a golden whose config was deleted is an orphan warning (census +
    # resources + spmd, plus the mesh golden when the dropped config is
    # multi-device — mesh goldens exist only for tpu_size > 1)
    findings = check_golden_coverage(names[1:])
    orphans = [f for f in findings if f.severity == "warning"]
    raw = json.load(open(os.path.join(REPO, "configs",
                                      names[0] + ".json")))
    want = 4 if raw.get("tpu_size", 32) > 1 else 3
    assert len(orphans) == want and all(names[0] in f.location
                                        for f in orphans)


# -- CLI exit status (ISSUE 7 satellite) -------------------------------------

def test_cli_exit_codes_and_severity_summary(tmp_path):
    """Warnings-only runs exit 0 (1 only under --strict), error runs exit 1,
    and the findings-by-severity summary line prints in every mode."""
    cfg = dict(model_mode="gpt", use_video=False, sequence_length=16,
               features_per_head=16, heads=2, depth=1, vocab_size=64,
               train_batch_size=2, tpu_size=1,
               memory_reduction_strategy="none",
               intermediate_feed_forward_multiplier_multiplier=0.5,
               block_config=[{"layer": ["norm-shift-scale",
                                        "feed_forward-in:relu"]}])
    path = tmp_path / "tmpnew.json"
    path.write_text(json.dumps(cfg))
    base = [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
            "--config", str(path), "--graph-only"]
    # no goldens for a brand-new config -> census error -> exit 1
    proc = subprocess.run(base + ["--rules", "collective-census"],
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "1 error(s)" in proc.stderr and "exit 1" in proc.stderr
    # an eval-only trace is unpinned by the golden -> warnings only -> 0
    warn = [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
            "--config", os.path.join(REPO, "configs", "bpe65k_1chip.json"),
            "--graph-only", "--steps", "eval",
            "--rules", "collective-census"]
    proc = subprocess.run(warn, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 error(s)" in proc.stderr and "exit 0" in proc.stderr
    assert "warning(s)" in proc.stderr
    # --strict promotes those warnings to a failing exit
    proc = subprocess.run(warn + ["--strict"], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "--strict promotes warnings" in proc.stderr


# -- resource-budget through the CLI (ISSUE 7) -------------------------------

def test_cli_golden_coverage_requires_all_configs():
    """Explicitly requesting the tree-wide rule on a single config must
    refuse (exit 2), not silently skip it and report a clean pass."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
         "--config", os.path.join(REPO, "configs", "bpe65k_1chip.json"),
         "--rules", "golden-coverage"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "requires --all-configs" in proc.stderr


def test_cli_resource_budget_rule_selectable():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/graftcheck.py"),
         "--list-rules"], capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    assert "resource-budget" in proc.stdout
    assert "golden-coverage" in proc.stdout
