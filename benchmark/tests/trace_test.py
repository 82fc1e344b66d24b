"""The trace arithmetic on a small recorded trace, against a slow count."""
import json
import os

import pytest

from conftest import HERE


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces(small):
    import trace_reduce as tr
    ops = tr.device_ops(small)["/device:TPU:0"]
    spans = tr.host_spans(small)
    assert len(ops) == 704 and {s[0] for s in spans} >= {"window", "dispatch"}
    start, end = tr.window_of(spans)
    end = min(end, ops[-1][1] + ops[-1][2])
    inside = tr.clip(ops, start, end)
    # slow count: mark every nanosecond bucket of 1 us that an event touches
    step = 1000.0
    marked = set()
    for _, s, d in inside:
        marked.update(range(int(s // step), int((s + d) // step) + 1))
    busy = tr.busy_ns(inside)
    assert 0 < busy <= end - start
    assert abs(busy - len(marked) * step) <= 2 * step * len(tr.union(inside))
    gaps = tr.idle_gaps(inside, spans, start, end)
    assert abs(sum(s for _, s in gaps) * 1e9 - ((end - start) - busy)) < 1.0
    top = tr.top_ops(inside, 5)
    assert top == sorted(top, key=lambda kv: -kv[1]) and " -> " in top[0][0]
    assert sum(s for _, s in tr.top_ops(inside, 10 ** 6)) * 1e9 == pytest.approx(
        sum(d for _, _, d in inside))


def test_only_an_events_own_instruction_matches(small):
    """The recorded trace ends on one forward and one backward kernel call of
    `32mixer_group.train`, each followed by an operation that takes the
    kernel's result as an operand: the copy and the fusion are not the
    kernel, and the kernel's time is what `top_ops` shows for it."""
    import trace_reduce as tr
    ops = tr.device_ops(small)["/device:TPU:0"]
    named = [e for e in ops if "_pallas" in e[0]]
    kernel = tr.matching(ops, ["_fwd_pallas", "_bwd_pallas"])
    assert len(named) == 4 and len(kernel) == 2
    assert [tr.instruction(e[0]) for e in named] == [
        "_fwd_pallas", "copy", "_bwd_pallas", "fusion"]
    rows = sum(s for kind, s in tr.top_ops(ops, 10 ** 6)
               if kind.startswith(("_fwd_pallas ->", "_bwd_pallas ->")))
    assert sum(d for _, _, d in kernel) == pytest.approx(rows * 1e9)
    assert tr.matching(ops, ["pallas"]) == []


def test_union_and_gaps_on_a_made_up_line():
    import trace_reduce as tr
    ops = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0)]
    assert tr.union(ops) == [(0.0, 15.0), (30.0, 35.0)]
    assert tr.busy_ns(ops) == 20.0
    spans = [("window", 0.0, 40.0), ("loss_pull", 14.0, 10.0),
             ("dispatch", 24.0, 7.0)]
    gaps = dict(tr.idle_gaps(ops, spans, 0.0, 40.0))
    assert gaps == {"loss_pull": 15e-9, "(no span)": 5e-9}
    assert tr.clip(ops, 8.0, 32.0) == [("a", 8.0, 2.0), ("b", 8.0, 7.0),
                                       ("c", 30.0, 2.0)]
    assert tr.op_kind("%fusion.12 = (f32[8,2]{1,0}, bf16[3]) fusion(...)") \
        == "fusion -> f32[8,2]"
