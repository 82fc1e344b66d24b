"""The plain reference against the program at toy width, both block layouts
(unfused chain with remat; the fused kernel, interpreted), and the control
and planted faults against the limits the cells are held to."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, CELLS, TOY_MANIFEST

FLOAT32_LIMIT = 1e-4  # same mathematics in float32: rounding order only


def toy_cell(name, **overrides):
    import run
    with open(TOY_MANIFEST) as f:
        cell = run.find_cell(json.load(f), name)
    cell["config"].update(overrides)
    return cell


def program_readings(cell, seed):
    import run
    spans = run.Spans(mirror=False)
    marks = (lambda: __import__("time").perf_counter(),) * 2
    return cell["runner"].run(cell, seed, 0.2, spans, lambda m: None, marks)


@pytest.mark.parametrize("name", CELLS)
def test_float32_program_matches_reference(name):
    import compare
    cell = toy_cell(name, calculation_dtype="float32")
    result = program_readings(cell, seed=11)
    rows = cell["runner"].check(result, cell, 11)
    assert all(value < FLOAT32_LIMIT for _, value, _ in rows), rows
    assert compare.correct(rows)


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail_a_limit(name):
    """The reference in the program's place: sound, it passes every limit;
    with float32 slices held as bfloat16 (the control), or with half of the
    batch left out (a planted fault), it fails one."""
    import compare
    import generate
    import jax.numpy as jnp
    cell = toy_cell(name)
    ref_mod = cell["reference"]
    raw = {k: v for k, v in cell["config"].items() if k != "benchmark"}
    sz = ref_mod.Sizes.from_config(raw)
    batches = [(jnp.asarray(x), jnp.asarray(y)) for x, y in
               generate.token_batches(cell["traffic"], 5,
                                      raw["train_batch_size"],
                                      raw["sequence_length"],
                                      raw["vocab_size"])]
    follow = lambda **kw: ref_mod.follow(sz, 5, batches, 3, 2, **kw)
    ref = follow()
    verdict = lambda got: compare.correct(
        compare.against(compare.readings(got, ref), cell["limits"]))
    assert verdict(follow())
    assert not verdict(follow(lower="bf16_slices"))
    assert not verdict(follow(half_batch=True))
    frozen = dict(ref, change_leaf=np.zeros_like(ref["change_leaf"]))
    assert not verdict(frozen)
