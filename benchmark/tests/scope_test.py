"""The per-scope reduction and its six readers on a small recorded trace
(`data/trace_scopes_small.json`, cut from a chip trace of
`32mixer_group.train`), against a slow count by hand-written rules."""
import json
import os

import pytest

from conftest import HERE

READERS = ("norm_ms", "group_linear_ms", "map_ms", "optimizer_ms",
           "recompute_ms", "scope_attributed_share")
STEPS = 2  # the cut holds one update; two make the division visible


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_scopes_small.json")) as f:
        return json.load(f)


def a_run(recorded, op_names, monkeypatch):
    """What `run.traced_metrics` hands a reader, from the recorded trace,
    with `op_names` standing in for what the run's `.xplane.pb` would say."""
    import scope_time
    import trace_reduce as tr
    start, end = tr.window_of(tr.host_spans(recorded))
    ops = tr.clip(tr.device_ops(recorded)["/device:TPU:0"], start, end)
    monkeypatch.setattr(scope_time, "trace_op_names", lambda: op_names)
    return {"ops": ops, "result": {"steps": STEPS},
            "device": {"busy_s": tr.busy_ns(ops) / 1e9,
                       "window_s": (end - start) / 1e9}}


def read(name, run):
    import run as harness
    return harness.load_module("layer_metrics", name).read(run)


def by_hand(recorded):
    """Nanoseconds by metric, from substrings of each event's `op_name`."""
    names = recorded["op_names"]
    events = recorded["planes"][0]["lines"][0]["events"]
    total = dict.fromkeys(READERS, 0.0)
    for name, _, duration in events:
        op_name = names.get(name, "")
        in_block = "/block_/" in op_name
        if "/block_/norm_" in op_name:
            total["norm_ms"] += duration
        elif "/block_/bottleneck_group_linear_/" in op_name:
            total["group_linear_ms"] += duration
        elif in_block:  # d5_1 is the fused block: kernel, copies, glue
            total["map_ms"] += duration
        if "/optimizer/" in op_name:
            total["optimizer_ms"] += duration
        if op_name.startswith("jit(step_fn)/transpose(jvp(gpt))/body/jvp(gpt)/"):
            total["recompute_ms"] += duration
        if "(gpt)" in op_name or "/optimizer/" in op_name:
            total["scope_attributed_share"] += duration
    return total, sum(d for _, _, d in events)


def test_readers_agree_with_a_count_by_hand(recorded, monkeypatch):
    run = a_run(recorded, recorded["op_names"], monkeypatch)
    want, flat = by_hand(recorded)
    for name in READERS[:-1]:
        assert want[name] > 0
        assert read(name, run) == pytest.approx(want[name] / 1e6 / STEPS), name
    assert read("scope_attributed_share", run) == pytest.approx(
        100.0 * want["scope_attributed_share"] / flat)
    assert 80 < read("scope_attributed_share", run) < 100
    # the kernel's own events are part of the fused block's map time
    import trace_reduce as tr
    kernel = tr.matching(run["ops"], ["_fwd_pallas", "_bwd_pallas"])
    assert len(kernel) == 3
    assert read("map_ms", run) > sum(d for _, _, d in kernel) / 1e6 / STEPS


def test_the_layers_partition_the_flat_sum(recorded, monkeypatch):
    import scope_time
    run = a_run(recorded, recorded["op_names"], monkeypatch)
    table = scope_time.seconds_by_scope(run)
    flat = sum(d for _, _, d in run["ops"]) / 1e9
    assert sum(table.values()) == pytest.approx(flat, rel=1e-12)
    named = sum(read(name, run) for name in READERS[:4]) * STEPS / 1e3
    rest = sum(s for (_, layer), s in table.items() if layer not in (
        "norm", "group_linear", "map", "optimizer"))
    assert named + rest == pytest.approx(flat, rel=1e-12)
    assert {layer for _, layer in table} == {
        "norm", "group_linear", "map", "optimizer", "body", "input",
        "output", "loss", "other"}
    assert {pass_ for pass_, _ in table} == {
        "forward", "replay", "backward", "optimizer", "other"}
    # recompute cuts across the layers
    assert read("recompute_ms", run) * STEPS / 1e3 == pytest.approx(
        sum(s for (pass_, _), s in table.items() if pass_ == "replay"))


@pytest.mark.parametrize("op_names", [
    {}, None, {"%never = f32[] seen()": "jit(step_fn)/optimizer/mul"}],
    ids=["no_name", "no_trace", "no_match"])
def test_nothing_resolved_reads_none(recorded, monkeypatch, op_names):
    run = a_run(recorded, op_names, monkeypatch)
    assert [read(name, run) for name in READERS] == [None] * len(READERS)


def test_a_program_without_the_grammar_reads_none(recorded, monkeypatch):
    """A commit before `step_scope`: the readers report nothing and the
    trace is not opened."""
    import scope_time
    import trace_reduce as tr
    monkeypatch.setattr(scope_time, "program_profile", lambda: None)
    run = {"ops": tr.device_ops(recorded)["/device:TPU:0"],
           "result": {"steps": STEPS}, "device": {"busy_s": 1.0}}
    assert scope_time.trace_op_names(os.path.join(HERE, "nowhere")) is None
    assert [read(name, run) for name in READERS] == [None] * len(READERS)


def test_every_reader_has_its_data_file_and_manifest_entry():
    import scope_time
    from conftest import BENCH, ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in READERS:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert set(spec) <= {"layers", "except_layers", "passes", "what"}
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "tokens_per_s"
        assert entries[name]["workloads"] == ["32big_mixer.train",
                                              "32mixer_group.train"]
    assert scope_time.selected(("replay", "norm"), {"layers": ["norm"]})
    assert not scope_time.selected(("other", "other"),
                                   {"except_layers": ["other"]})
    assert not scope_time.selected(("forward", "norm"),
                                   {"passes": ["replay", "remat"]})
