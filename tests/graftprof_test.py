"""graftprof: device-time attribution from profiler traces (ISSUE 8).

Three layers of coverage:

- pure parser/attribution math over the committed miniature Chrome-trace
  fixture (``tests/data/mini_trace.json`` + op-map sidecar) — category
  bucketing, nested-thunk self time, scope attribution through transform
  wrappers, malformed-event tolerance, the flamegraph golden, the
  ``--compare`` diff, and the predicted-vs-measured reconciliation;
- the live capture path: 5 CPU train steps through the real CLI with the
  profiler armed must produce a summary attributing >=90% of device time
  with named model scopes present (the CI ``profile-smoke`` contract);
- the observability surfaces: ``record_profile`` gauges on /metrics, the
  comm fraction mirrored under /healthz ``utilization``, and the watchdog
  diagnostics dump inlining the latest summary.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re

import pytest

from homebrewnlp_tpu.obs import profile as P

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(DATA, "mini_trace.json")


def fixture_summary(n_steps=2, **kw):
    return P.summarize_trace(FIXTURE, op_map=P.sidecar_op_map(FIXTURE),
                             n_steps=n_steps, **kw)


# -- category bucketing -------------------------------------------------------

@pytest.mark.parametrize("op,cat", [
    ("dot.4", "mxu"),
    ("convolution.2", "mxu"),
    ("input_reduce_dot_fusion.1", "mxu"),
    ("custom-call.3", "mxu"),
    ("all-reduce.12.clone", "collective"),
    ("reduce-scatter", "collective"),
    ("collective-permute.1", "collective"),
    ("all-gather.7", "collective"),
    # async halves (the form modern XLA emits on TPU) are still comm
    ("all-reduce-start.1", "collective"),
    ("all-gather-start", "collective"),
    ("reduce-scatter-done.3", "collective"),
    ("collective-permute-start.2", "collective"),
    # dtype casts are vector work, not MXU ("conv" must not eat "convert")
    ("convert.5", "vector"),
    ("convert_fusion.2", "vector"),
    ("copy.9", "copy"),
    ("dynamic-update-slice.2", "copy"),
    ("infeed", "infeed"),
    ("outfeed.1", "infeed"),
    ("tanh.5.clone", "vector"),
    ("broadcast_multiply_fusion", "vector"),
    ("reduce-window", "vector"),
    ("call.1", "vector"),
    ("while", "vector"),
    ("frobnicate.3", "unknown"),
])
def test_categorize(op, cat):
    assert P.categorize(op) == cat


def test_collective_kind():
    assert P.collective_kind("all-reduce.3.clone") == "all-reduce"
    assert P.collective_kind("all-to-all.1") == "all-to-all"
    assert P.collective_kind("all-reduce-start.2") == "all-reduce"
    assert P.collective_kind("all-gather-done") == "all-gather"
    assert P.collective_kind("dot.4") is None
    assert P.collective_kind("copy-start.1") is None


# -- scope extraction ---------------------------------------------------------

def test_scope_of_op_name_unwraps_transforms():
    assert P.scope_of_op_name(
        "jit(step)/jit(main)/transpose(jvp(body))/layer0/ffn/dot_general"
    ) == ("body", "layer0", "ffn")
    assert P.scope_of_op_name(
        "jit(step)/jit(main)/jvp(gpt)/loss/exp") == ("gpt", "loss")
    # bare step-level glue: no scope components at all
    assert P.scope_of_op_name("jit(step)/jit(main)/add") == ()
    assert P.scope_of_op_name("jit(f)/jit(main)/") == ()


def test_scope_collapses_doubled_preset_prefix():
    # per-block sub-builds re-enter their preset path while the outer
    # build's name-stack entries are still open (models/ctx.py)
    assert P.scope_of_op_name(
        "jit(step_fn)/jit(main)/jvp(gpt)/body/gpt/body/d0_0/block_/mul"
    ) == ("gpt", "body", "d0_0", "block_")


def test_collapse_repeat_pure():
    assert P._collapse_repeat(("a", "b", "a", "b", "c")) == ("a", "b", "c")
    assert P._collapse_repeat(("a", "a")) == ("a",)
    assert P._collapse_repeat(("a", "b", "c")) == ("a", "b", "c")
    assert P._collapse_repeat(()) == ()


def test_collapse_repeat_drops_checkpoint_components():
    # jax.checkpoint's own components sit between the repeats
    assert P._collapse_repeat(
        ("gpt", "body", "checkpoint", "gpt", "body", "d0_0", "block_")
    ) == ("gpt", "body", "d0_0", "block_")
    assert P.scope_of_op_name(
        "jit(step_fn)/transpose(jvp(gpt))/body/"
        "transpose(jvp(transpose(jvp(gpt))))/body/jvp()/checkpoint/"
        "rematted_computation/gpt/body/d0_0/block_/norm_/rsqrt"
    ) == ("gpt", "body", "d0_0", "block_", "norm_")


# -- the train step's scope grammar -------------------------------------------

_BWD = "jit(step_fn)/transpose(jvp(gpt))/body/"
_CKPT = _BWD + "transpose(jvp(transpose(jvp(gpt))))/body/jvp()/checkpoint/"


@pytest.mark.parametrize("op_name,want", [
    # the three runs of one norm under revnet + remat, and its backward
    ("jit(step_fn)/jvp(gpt)/body/gpt/body/d0_0/block_/norm_/rsqrt",
     ("forward", "d0_0", "norm")),
    (_BWD + "jvp(gpt)/body/d0_1/block_/norm_1/rsqrt",
     ("replay", "d0_1", "norm")),
    (_CKPT + "rematted_computation/gpt/body/d0_0/block_/norm_/rsqrt",
     ("remat", "d0_0", "norm")),
    (_CKPT + "gpt/body/d0_0/block_/norm_/mul",
     ("backward", "d0_0", "norm")),
    # einsums keep their layer through the spec component
    ("jit(step_fn)/jvp(gpt)/body/gpt/body/d3_0/block_/"
     "bottleneck_group_linear_/abcd,cde->abce/dot_general",
     ("forward", "d3_0", "group_linear")),
    (_CKPT + "gpt/body/d31_1/block_/attention_1/abc,dcae->dbae/dot_general",
     ("backward", "d31_1", "map")),
    (_BWD + "jvp(gpt)/body/d1_1/block_/activation_/tanh",
     ("replay", "d1_1", "map")),
    # no remat: the replay's vjp is transposed in place
    (_BWD + "transpose(jvp(gpt))/body/d0_0/block_/bottleneck_group_linear_/"
     "abcd,cde->abe/dot_general", ("backward", "d0_0", "group_linear")),
    # a fused block: the kernel and the glue directly under block_
    ("jit(step_fn)/jvp(gpt)/body/gpt/body/d0_1/block_/jit(_fwd_pallas)/"
     "while/body/dot_general", ("forward", "d0_1", "map")),
    (_BWD + "jvp(gpt)/body/d0_1/block_/jit(_fwd_pallas)/while/body/add",
     ("replay", "d0_1", "map")),
    (_BWD + "transpose(transpose(jvp(gpt)))/body/jvp(gpt)/body/d0_1/block_/"
     "jit(_bwd_pallas)/while/body/add", ("backward", "d0_1", "map")),
    (_BWD + "transpose(transpose(jvp(gpt)))/body/jvp(gpt)/body/d0_1/block_/"
     "transpose", ("backward", "d0_1", "map")),
    # outside the blocks
    ("jit(step_fn)/optimizer/mul", ("optimizer", None, "optimizer")),
    ("jit(step_fn)/jvp(gpt)/loss/jit(take_along_axis)/gather",
     ("forward", None, "loss")),
    ("jit(step_fn)/transpose(jvp(gpt))/output/abcd,cdef->abef/dot_general",
     ("backward", None, "output")),
    ("jit(step_fn)/jvp(gpt)/input/gather/embed/gather",
     ("forward", None, "input")),
    (_BWD + "add_any", ("backward", None, "body")),
    ("jit(step_fn)/add", ("other", None, "other")),
    ("state.params['gpt/body/d0_0/block_/norm_/scale']",
     ("other", None, "other")),
    # XLA joins the names of merged instructions: the first one counts
    ("jit(step_fn)/transpose(jvp(gpt))/loss/mul;"
     "jit(step_fn)/jvp(gpt)/loss/sub", ("backward", None, "loss")),
    ("", ("other", None, "other")),
])
def test_step_scope(op_name, want):
    assert P.step_scope(op_name) == want
    assert want[0] in P.PASSES


_TOY_CONFIGS = os.path.join(REPO, "benchmark", "tests", "toy", "configs")
_EINSUM = re.compile(r"/\w+(,\w+)+->\w+/")


@functools.lru_cache(maxsize=None)
def _toy_step_op_names(layout):
    """``op_name`` of every instruction of the optimized train step of one
    of the benchmark's toy configurations (depth 2, two blocks a depth), on
    one device as the cells run it: a partitioned step renames its dots."""
    import jax
    from homebrewnlp_tpu.config import Config
    from homebrewnlp_tpu.parallel import make_mesh
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import random_text_batch
    with open(os.path.join(_TOY_CONFIGS, layout + ".json")) as f:
        raw = json.load(f)
    raw.pop("benchmark")
    cfg = Config(raw)
    tr = Trainer(cfg, make_mesh(cfg, jax.devices()[:1]))
    batch = random_text_batch(cfg)
    tr.step_cost_analysis(tr.init(batch), batch)
    return tuple(P.op_map_from_hlo_text(tr._compiled.as_text()).values())


def _einsum_passes_by_block(op_names):
    found = {}
    for name in op_names:
        pass_, block, _ = P.step_scope(name)
        if block is not None and _EINSUM.search(name):
            found.setdefault(block, set()).add(pass_)
    return found


@pytest.mark.parametrize("layout", ["32big_mixer", "32mixer_group"])
def test_toy_step_blocks_never_resolve_to_other(layout):
    in_block = [n for n in _toy_step_op_names(layout)
                if "(" in n and re.search(r"/d\d+_\d+/", n)]
    assert len(in_block) > 1000
    for name in in_block:
        pass_, block, layer = P.step_scope(name)
        assert block and pass_ != "other" and layer in (
            "norm", "group_linear", "map"), name
    layers = {P.step_scope(n)[2] for n in _toy_step_op_names(layout)}
    assert layers == set(P.LAYERS)


def test_remat_layout_runs_every_block_in_every_pass():
    names = _toy_step_op_names("32big_mixer")
    passes = _einsum_passes_by_block(names)
    assert sorted(passes) == ["d0_0", "d0_1", "d1_0", "d1_1"]
    for block, seen in passes.items():
        assert {"forward", "remat", "backward"} <= seen, (block, seen)
    # XLA drops the first block's replay (nothing reads the reconstructed
    # input) and may merge the last block's with the forward it repeats
    for block in ("d0_1", "d1_0"):
        assert "replay" in passes[block], passes
    # only a fused block emits instructions directly under block_
    assert all(re.search(r"/(attention|activation)_\d*/", n) for n in names
               if P.step_scope(n)[2] == "map")


def test_fused_layout_has_no_remat_and_the_kernel_in_its_passes():
    names = _toy_step_op_names("32mixer_group")
    assert "remat" not in {P.step_scope(n)[0] for n in names}
    passes = _einsum_passes_by_block(names)
    assert sorted(passes) == ["d0_0", "d1_0"]  # d*_1 are fused: no einsum
    for block, seen in passes.items():
        assert seen == {"forward", "replay", "backward"}, (block, seen)
    fwd = {P.step_scope(n) for n in names if "jit(_fwd_pallas)" in n}
    assert {s[0] for s in fwd} == {"forward", "replay"}
    bwd = {P.step_scope(n) for n in names if "jit(_bwd_pallas)" in n}
    assert {s[0] for s in bwd} == {"backward"}
    assert {s[1:] for s in fwd | bwd} == {("d0_1", "map"), ("d1_1", "map")}


# -- HLO op map ---------------------------------------------------------------

HLO_SNIPPET = """\
HloModule jit_step_fn, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  ROOT %mul.3 = f32[8] multiply(%p, %p), metadata={op_name="jit(step_fn)/jit(main)/body/mul" source_file="x.py" source_line=3}
}

ENTRY %main {
  %Arg_0.1 = f32[8] parameter(0), metadata={op_name="x"}
  %dot.7 = f32[8,8] dot(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(step_fn)/jit(main)/body/attn/dot_general"}
  ROOT %out_fusion = f32[8] fusion(%Arg_0.1), calls=%fused_computation, metadata={op_name="jit(step_fn)/jit(main)/body/mul"}
}
"""


def test_op_map_from_hlo_text():
    assert P.hlo_module_name(HLO_SNIPPET) == "jit_step_fn"
    ops = P.op_map_from_hlo_text(HLO_SNIPPET)
    # entry ops, fused-computation internals, and args all carried
    assert ops["dot.7"].endswith("body/attn/dot_general")
    assert ops["mul.3"].endswith("body/mul")
    assert ops["out_fusion"].endswith("body/mul")
    assert ops["Arg_0.1"] == "x"


def test_op_map_lookup_clone_fallback(tmp_path):
    om = P.OpMap.from_hlo_text(HLO_SNIPPET)
    assert om.lookup("jit_step_fn", "dot.7.clone") is not None
    assert om.lookup("jit_step_fn", "dot.7.clone.clone") is not None
    assert om.lookup("jit_step_fn", "nope.1") is None
    assert om.lookup("other_module", "dot.7") is None
    path = om.save(str(tmp_path / "map.json"))
    assert P.OpMap.load(path).lookup("jit_step_fn", "dot.7") \
        == om.lookup("jit_step_fn", "dot.7")


# -- the committed fixture ----------------------------------------------------

def test_fixture_category_seconds():
    s = fixture_summary()
    # hand-computed from the fixture (us): dot 60 mxu; tanh 40 + fusion 20
    # + call self 0 vector; all-reduce 50; copy 30; weird_thing 10 unknown
    assert s.categories_s == {"collective": 5e-05, "copy": 3e-05,
                              "mxu": 6e-05, "unknown": 1e-05,
                              "vector": 6e-05}
    assert s.collectives_s == {"all-reduce": 5e-05}
    assert s.attributed_category_frac == pytest.approx(200 / 210, abs=1e-5)


def test_fixture_self_time_nesting():
    # the call.1 thunk (100us) encloses dot.1 (60) + tanh (40) on its lane:
    # its SELF time must be zero, or the window double-counts
    s = fixture_summary()
    call_rows = [r for r in s.op_rows if r["op"] == "call"]
    assert call_rows and call_rows[0]["self_s"] == 0.0


def test_fixture_scope_attribution():
    s = fixture_summary()
    # transform wrappers unwrap (jvp/transpose -> model), clone suffix
    # falls back, arg-label metadata goes to (toplevel), map misses and
    # the TPU-pid fusion go to (unattributed)
    assert s.scopes_s == {"(toplevel)": 3e-05, "(unattributed)": 3e-05,
                          "model/body": 0.0, "model/body/attn": 0.00011,
                          "model/body/ffn": 4e-05}
    assert s.attributed_scope_frac == pytest.approx(180 / 210, abs=1e-5)


def test_fixture_decomposition_and_idle():
    s = fixture_summary(n_steps=2)
    # wall 210us, busy union 160us (lanes overlap), idle 50us; decomposition
    # splits busy across buckets by self-time share and sums to the wall
    assert s.wall_s == pytest.approx(210e-6)
    assert s.busy_s == pytest.approx(160e-6)
    d = s.decomposition_ms_per_step
    assert d["total"] == pytest.approx(0.105)
    assert d["idle"] == pytest.approx(0.025)
    assert d["mxu"] == pytest.approx(160 * 60 / 210 / 2 * 1e-3, rel=1e-3)
    assert d["comm"] == pytest.approx(160 * 50 / 210 / 2 * 1e-3, rel=1e-3)
    assert (d["mxu"] + d["hbm"] + d["comm"] + d["idle"]
            == pytest.approx(d["total"], rel=1e-4))
    assert sum(s.fractions.values()) == pytest.approx(1.0, abs=1e-4)


def test_fixture_garbage_events_counted_not_fatal():
    s = fixture_summary()
    # missing dur, negative dur, non-numeric ts -> counted; the host-side
    # python event and ph=B marker are silently ignored
    assert s.n_malformed == 3
    assert s.n_events == 7


def test_fixture_tpu_device_pid_detected():
    # fusion.7 carries no hlo_op arg; it counts because pid 9 is a
    # /device: process — the TPU-side trace shape
    s = fixture_summary()
    assert any(r["op"] == "fusion" for r in s.op_rows)
    assert s.n_lanes == 3


def test_summary_json_roundtrip(tmp_path):
    s = fixture_summary()
    path = s.save(str(tmp_path / "summary.json"))
    back = P.ProfileSummary.load(path)
    assert back.to_json() == s.to_json()


def test_no_trace_skips_cleanly(tmp_path):
    assert P.capture_summary(str(tmp_path)) is None
    assert P.find_trace_file(str(tmp_path / "missing")) is None


def test_empty_trace_summary():
    s = P.summarize_events([])
    assert s.n_events == 0 and s.wall_s == 0.0
    assert s.decomposition_ms_per_step["total"] == 0.0


# -- the profiler's own file: op_name from the .xplane.pb ---------------------

_XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops"
    events { metadata_id: 7 offset_ps: 1000 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 9 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 11000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Modules"
    events { metadata_id: 9 offset_ps: 0 duration_ps: 90000000 } }
  event_metadata { key: 7 value { id: 7
    name: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    stats { metadata_id: 3 uint64_value: 12 }
    stats { metadata_id: 2 str_value:
      "jit(step_fn)/transpose(jvp(gpt))/body/jvp(gpt)/body/d0_0/block_/norm_/rsqrt:" } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.2 = bf16[8]{0} copy(%q)"
    stats { metadata_id: 2 ref_value: 4 } } }
  event_metadata { key: 9 value { id: 9 name: "%copy-start = bf16[8]{0} copy-start(%q)" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
  stat_metadata { key: 4 value { id: 4 name: "jit(step_fn)/optimizer/mul:" } }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "x"
    stats { metadata_id: 1 str_value: "jit(f)/y:" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
"""


def _write_xspace(directory, text=_XSPACE):
    from jax.profiler import ProfileData
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(str(directory), "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_xplane_op_names_reads_the_event_metadata(tmp_path):
    names = P.xplane_op_names(_write_xspace(tmp_path))
    # a string stat, a stat by reference; no name, no entry; devices only
    assert names == {
        "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop":
            "jit(step_fn)/transpose(jvp(gpt))/body/jvp(gpt)/body/d0_0/"
            "block_/norm_/rsqrt",
        "%copy.2 = bf16[8]{0} copy(%q)": "jit(step_fn)/optimizer/mul"}
    with pytest.raises(ValueError):
        list(P._wire_fields(bytes([0x0B])))  # a group: not in an xplane


def test_xplane_layer_pass_seconds_sums_the_ops_line_flat(tmp_path):
    table = P.xplane_layer_pass_seconds(_write_xspace(tmp_path))
    assert table == pytest.approx({"norm/replay": 10e-6,
                                   "optimizer/optimizer": 2e-6,
                                   "other/other": 1e-6})
    lines = P.layer_pass_table(table, n_steps=2)
    assert lines[0].split() == ["layer", "(ms/step)", *P.PASSES, "sum"]
    assert [line.split()[0] for line in lines[1:]] == [
        "norm", "optimizer", "other", "sum"]
    assert float(lines[1].split()[2]) == pytest.approx(0.005)
    assert float(lines[-1].split()[-1]) == pytest.approx(0.0065, abs=6e-4)
    # a capture that names no instruction (the CPU's) gives no table
    unnamed = _XSPACE.replace('name: "tf_op"', 'name: "hlo_category"')
    assert P.xplane_layer_pass_seconds(
        _write_xspace(tmp_path / "cpu", unnamed)) == {}
    assert P.layer_pass_seconds([(None, 1.0), ("jit(f)/add", 2.0)]) == {}


def test_capture_summary_takes_the_table_from_the_xplane(tmp_path, capsys):
    session = tmp_path / "plugins" / "profile" / "2026_01_01"
    _write_xspace(session)
    with open(FIXTURE) as f, open(session / "host.trace.json", "w") as out:
        out.write(f.read())
    assert P.find_xplane_file(str(tmp_path)).endswith("host.xplane.pb")
    summary = P.capture_summary(str(tmp_path), n_steps=2)
    assert summary.layer_pass_s == pytest.approx(
        {"norm/replay": 10e-6, "optimizer/optimizer": 2e-6,
         "other/other": 1e-6})
    assert P.ProfileSummary.from_json(
        json.loads(json.dumps(summary.to_json()))).layer_pass_s \
        == summary.layer_pass_s
    # not the sidecar's reading of the Chrome trace beside it
    assert "norm/replay" not in fixture_summary().layer_pass_s
    assert _run_cli(str(tmp_path), "--steps", "2") == 0
    assert "layer (ms/step)" in capsys.readouterr().out
    assert P.find_xplane_file(str(tmp_path / "missing")) is None


# -- flamegraph + compare + CLI -----------------------------------------------

def test_flamegraph_golden():
    s = fixture_summary()
    golden = open(os.path.join(DATA, "mini_trace_flame.txt")).read()
    assert "\n".join(P.collapsed_stacks(s)) + "\n" == golden


def test_diff_summaries_self_is_zero():
    s = fixture_summary()
    d = P.diff_summaries(s, s)
    assert d["ms_per_step"]["delta"] == 0.0
    assert all(v == 0.0 for v in d["fractions_delta"].values())
    assert all(r["delta_ms"] == 0.0 for r in d["scopes_ms"].values())


def test_diff_summaries_detects_growth():
    import dataclasses
    a = fixture_summary()
    b = dataclasses.replace(
        a, scopes_s=dict(a.scopes_s, **{"model/body/attn": 0.00022}),
        decomposition_ms_per_step=dict(a.decomposition_ms_per_step,
                                       total=0.2))
    d = P.diff_summaries(a, b)
    assert d["scopes_ms"]["model/body/attn"]["delta_ms"] > 0
    assert d["ms_per_step"]["delta"] == pytest.approx(0.095)


def _run_cli(*argv):
    from tools import graftprof as cli
    return cli.main(list(argv))


def test_cli_table_and_gates(capsys):
    rc = _run_cli(FIXTURE, "--steps", "2")
    out = capsys.readouterr().out
    assert rc == 0
    assert "model/body/attn" in out
    assert "ms/step" in out and "all-reduce" in out
    # gates: fixture attributes 95.2% by category, 85.7% by scope
    assert _run_cli(FIXTURE, "--min-category-frac", "0.9") == 0
    capsys.readouterr()
    assert _run_cli(FIXTURE, "--min-scope-frac", "0.9") == 1


def test_cli_json_and_depth(capsys):
    rc = _run_cli(FIXTURE, "--steps", "2", "--json")
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["n_steps"] == 2
    assert doc["scopes_s"]["model/body/attn"] == 0.00011
    rc = _run_cli(FIXTURE, "--depth", "1")
    out = capsys.readouterr().out
    assert rc == 0 and "model " in out  # collapsed to depth 1


def test_cli_flame_export(tmp_path, capsys):
    out_path = str(tmp_path / "flame.txt")
    assert _run_cli(FIXTURE, "--flame", out_path) == 0
    golden = open(os.path.join(DATA, "mini_trace_flame.txt")).read()
    assert open(out_path).read() == golden


def test_cli_compare_self(tmp_path, capsys):
    assert _run_cli(FIXTURE, "--compare", FIXTURE, "--json") == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ms_per_step"]["delta"] == 0.0


def test_cli_bench_round_source_and_compare(tmp_path, capsys):
    """--compare between two BENCH_r*.json lines diffs the profile rows."""
    s = fixture_summary()
    prof_row = {
        "n_steps": 2,
        "ms_per_step": s.decomposition_ms_per_step,
        "fractions": s.fractions,
        "attributed_category_frac": s.attributed_category_frac,
        "attributed_scope_frac": s.attributed_scope_frac,
        "scopes_ms": {k: v * 1e3 / 2 for k, v in s.scopes_s.items()},
        "top_ops": s.top_ops[:3],
    }
    a = {"metric": "x", "workloads": {"32big_mixer": {"profile": prof_row}}}
    b = json.loads(json.dumps(a))
    b["workloads"]["32big_mixer"]["profile"]["ms_per_step"] = dict(
        prof_row["ms_per_step"], total=prof_row["ms_per_step"]["total"] + 1.0)
    pa, pb = str(tmp_path / "BENCH_rA.json"), str(tmp_path / "BENCH_rB.json")
    json.dump(a, open(pa, "w"))
    json.dump(b, open(pb, "w"))
    assert _run_cli(pa, "--compare", pb, "--json") == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ms_per_step"]["delta"] == pytest.approx(1.0)


def test_cli_unreadable_source_exits_2(tmp_path, capsys):
    bad = tmp_path / "trunc.trace.json"
    bad.write_text('{"traceEvents": [ {"ph": "X", "na')  # truncated
    assert _run_cli(str(bad)) == 2
    assert _run_cli(str(tmp_path / "missing.json")) == 2


# -- reconciliation math ------------------------------------------------------

def test_reconcile_math():
    s = fixture_summary()
    rec = P.reconcile(s, {"mxu": 1e-3, "hbm": 2e-3, "ici": 5e-4})
    # predicted 1ms vs measured mxu ms
    m = s.decomposition_ms_per_step
    assert rec["mxu"]["predicted_ms"] == 1.0
    assert rec["mxu"]["prediction_error"] == pytest.approx(
        1.0 / m["mxu"] - 1.0, rel=1e-3)
    assert rec["comm"]["predicted_ms"] == 0.5
    assert rec["hbm"]["measured_ms"] == m["hbm"]


def test_reconcile_null_prediction_keeps_shape():
    rec = P.reconcile(fixture_summary(), None)
    assert set(rec) == {"mxu", "hbm", "comm"}
    for r in rec.values():
        assert r["predicted_ms"] is None
        assert r["prediction_error"] is None
        assert r["measured_ms"] >= 0


def test_static_step_times_known_and_unknown_device():
    from homebrewnlp_tpu.analysis.cost_model import (CommModel,
                                                     static_step_times)
    comm = CommModel(bytes_per_axis={"data": 1 << 20},
                     count_per_axis={"data": 4})
    t = static_step_times(1e12, 1e9, comm, {"data": 8}, "v5e")
    assert t is not None
    assert t["mxu"] == pytest.approx(1e12 / 197e12)
    assert t["hbm"] == pytest.approx(1e9 / 819e9)
    assert t["ici"] == pytest.approx(sum(t["ici_per_axis"].values()))
    assert t["ici_per_axis"]["data"] > 0
    assert static_step_times(1e12, 1e9, comm, {"data": 8}, "cpu") is None


def test_roofline_verdict_consistent_with_static_times():
    """_roofline and static_step_times must rank identically — they are
    documented as the same time model."""
    from homebrewnlp_tpu.analysis import cost_model as cm
    comm = cm.CommModel(bytes_per_axis={}, count_per_axis={})

    class _IMesh:
        shape = {"data": 1}
    verdict, kind = cm._roofline(None, 1e15, 1e3, comm, _IMesh(), "v5e")
    t = cm.static_step_times(1e15, 1e3, comm, {"data": 1}, "v5e")
    assert kind == "v5e"
    assert verdict == max(("mxu", "hbm", "ici"), key=lambda k: t[k])


# -- attribution-drift baseline (bench ratchet) -------------------------------

def _profile_row(mxu=0.25, hbm=0.35, comm=0.2, idle=0.2, cov=0.95):
    return {"profile": {"fractions": {"mxu": mxu, "hbm": hbm, "comm": comm,
                                      "idle": idle},
                        "attributed_scope_frac": cov}}


def test_evaluate_profile_baseline_pass_and_drift():
    base = {"w": P.baseline_entry(_profile_row()["profile"])}
    rows, ok = P.evaluate_profile_baseline({"w": _profile_row()}, base)
    assert ok and rows["w"]["pass"]
    # a fraction moving past the tolerance fails
    rows, ok = P.evaluate_profile_baseline(
        {"w": _profile_row(mxu=0.45, hbm=0.15)}, base)
    assert not ok and not rows["w"]["pass"]
    assert rows["w"]["fraction_drift"]["mxu"] == pytest.approx(0.2)
    # coverage dropping past the tolerance fails
    rows, ok = P.evaluate_profile_baseline({"w": _profile_row(cov=0.5)}, base)
    assert not ok and rows["w"]["coverage_drop"] == pytest.approx(0.45)


def test_evaluate_profile_baseline_skips_absent():
    base = {"w": P.baseline_entry(_profile_row()["profile"])}
    # no profile row / error rows / missing baseline: skipped, not failed
    rows, ok = P.evaluate_profile_baseline(
        {"w": {"profile": {"error": "x"}}, "v": _profile_row(),
         "u": {"no_profile": 1}}, base)
    assert ok and rows == {}


def test_baseline_entry_shape():
    e = P.baseline_entry(_profile_row()["profile"])
    assert set(e) == {"fractions", "attributed_scope_frac"}
    assert json.dumps(e)  # committed-file serializable


# -- nd named-scope emission --------------------------------------------------

def test_nd_scope_stacks_stay_balanced():
    from homebrewnlp_tpu import nd
    depth0 = len(nd._SCOPE_STACK)
    for _ in range(3):
        nd.push_scope("a")
        nd.push_scope("@d0_b")  # '@' must not break emission
        assert nd.current_scope() == "a/@d0_b"
        nd.pop_scope()
        nd.pop_scope()
    assert len(nd._SCOPE_STACK) == depth0
    assert len(nd._NAMED_SCOPE_CMS) == depth0
    nd.pop_scope()  # over-pop stays a no-op
    assert len(nd._SCOPE_STACK) == depth0


def test_named_scopes_reach_compiled_hlo_metadata():
    """End to end through the real model build: the compiled train step's
    HLO metadata must carry nd scope paths (this is what graftprof joins
    against)."""
    from tests.backend import text_batch, tiny_config
    from homebrewnlp_tpu.train import Trainer
    cfg = tiny_config()
    tr = Trainer(cfg)
    batch = text_batch(cfg)
    state = tr.init(batch)
    tr.step_cost_analysis(state, batch)
    text = tr._compiled.as_text()
    ops = P.op_map_from_hlo_text(text)
    scopes = {"/".join(P.scope_of_op_name(v)) for v in ops.values()
              if "jit(" in v}
    assert any(s.startswith("gpt/body") for s in scopes), sorted(scopes)[:20]
    assert "optimizer" in scopes, sorted(scopes)[:20]
    # the depth token's '@' was stripped, never silently dropped wholesale
    assert any("d0_" in s for s in scopes), sorted(scopes)[:20]


# -- live capture end to end (the CI profile-smoke contract) ------------------

def test_train_profile_capture_end_to_end(tmp_path):
    from tests.backend import tiny_config
    from homebrewnlp_tpu import main as cli
    cfg = tiny_config(model_path=str(tmp_path / "run"),
                      profile_start=1, profile_steps=3)
    cli.train(cfg, argparse.Namespace(steps=5,
                                      profile=str(tmp_path / "prof"),
                                      workers=None))
    # op-map sidecar written next to the trace session
    trace = P.find_trace_file(str(tmp_path / "prof"))
    assert trace is not None
    assert os.path.exists(os.path.join(os.path.dirname(trace),
                                       P.OP_MAP_FILENAME))
    # persisted summary: named scopes present, >=90% attributed
    doc = json.load(open(tmp_path / "run" / "profile_summary.json"))
    assert doc["n_steps"] == 3
    assert doc["attributed_category_frac"] >= 0.9
    assert doc["attributed_scope_frac"] >= 0.9
    assert any(k.startswith("gpt/") for k in doc["scopes_s"])
    assert "optimizer" in doc["scopes_s"]
    # the layer x pass table (step_scope), here through the sidecar
    table = doc["layer_pass_s"]
    assert table["optimizer/optimizer"] > 0
    assert {k.split("/")[1] for k in table} >= {"forward", "backward"}
    d = doc["decomposition_ms_per_step"]
    assert (d["mxu"] + d["hbm"] + d["comm"] + d["idle"]
            == pytest.approx(d["total"], rel=1e-3))
    # the CLI renders it and passes the CI attribution gate
    from tools import graftprof as cli_mod
    assert cli_mod.main([str(tmp_path / "prof"), "--steps", "3",
                         "--min-category-frac", "0.9"]) == 0


# -- observability surfaces ---------------------------------------------------

def test_record_profile_gauges_and_healthz():
    from homebrewnlp_tpu.obs import Obs
    from homebrewnlp_tpu.obs.registry import MetricsRegistry
    reg = MetricsRegistry()
    obs = Obs(model_path="/tmp/x", watchdog_factor=100.0, registry=reg)
    obs.health.step_completed(1)
    s = fixture_summary()
    obs.record_profile(s)
    text = reg.render()
    assert 'hbnlp_step_time_ms{stat="total"} 0.105' in text
    assert 'hbnlp_step_time_ms{stat="comm"}' in text
    assert 'hbnlp_profile_time_fraction{category="idle"}' in text
    assert 'hbnlp_profile_attributed_fraction{kind="scope"}' in text
    # no telemetry this run: /healthz utilization carries the comm fraction
    snap = obs.health.snapshot()
    assert snap["utilization"]["comm_fraction"] == pytest.approx(
        s.fractions["comm"], abs=1e-5)


def test_record_profile_merges_into_telemetry_utilization():
    from homebrewnlp_tpu.obs import Obs
    from homebrewnlp_tpu.obs.registry import MetricsRegistry

    class _Writer:
        last_rates = {"mfu": 0.5, "tokens_per_sec": 10.0}

        def goodput(self):
            return 0.9

    class _Util:
        flops_per_step = 1e9
    reg = MetricsRegistry()
    obs = Obs(model_path="/tmp/x", watchdog_factor=100.0, registry=reg)
    obs.watch_utilization(_Writer(), _Util())
    obs.record_profile(fixture_summary())
    util = obs.health.snapshot()["utilization"]
    assert util["mfu"] == 0.5
    assert "comm_fraction" in util


def test_dump_diagnostics_inlines_profile_summary(tmp_path):
    from homebrewnlp_tpu.obs.exporter import dump_diagnostics
    fixture_summary().save(str(tmp_path / "profile_summary.json"))
    path = dump_diagnostics(str(tmp_path), reason="test")
    content = open(path).read()
    assert "profile_summary: " in content
    assert '"attributed_scope_frac"' in content
    # and absent file stays absent, not an error
    path2 = dump_diagnostics(str(tmp_path / "other"), reason="test")
    assert not any(l.startswith("profile_summary: ")
                   for l in open(path2).read().splitlines())
