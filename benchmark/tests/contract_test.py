"""`BENCHMARK.json` and the data files against the contract's shape, and the
last line of a run against the keys the driver reads."""
import json
import os
import re

import pytest

from conftest import BENCH, CELLS, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_shape():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names)
    assert any(e["name"] == "setup_s" and e["bound"] <= 0.1
               for e in m["end_to_end"])
    assert all(0.01 <= e["bound"] <= 0.1 for e in m["end_to_end"])
    e2e = {e["name"] for e in m["end_to_end"]}
    layers = set()
    for p in m["per_layer"]:
        assert p["moves"] in e2e and "bound" not in p
        assert set(p["workloads"]) <= {w["name"] for w in m["workloads"]}
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", p["name"] + ".py"))
        layers.add(p["layer"])
    assert layers == {"train step", "kernels", "device", "train loop"}
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "limits", w["name"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))


@pytest.mark.parametrize("entry", manifest()["configs"], ids=lambda c: c["name"])
def test_config_is_the_published_one_but_for_reduced(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        ours = json.load(f)
    meta = ours.pop("benchmark")
    with open(os.path.join(ROOT, meta["repo_config"])) as f:
        published = json.load(f)
    changed = sorted(k for k in set(ours) | set(published)
                     if ours.get(k) != published.get(k))
    assert changed == sorted(entry["reduced"]) == sorted(meta["reduced"])
    assert entry["source"] == meta["source"] and len(entry["source"]) <= 200
    assert ours["depth"] == 32 and ours["slice_dtype"] == "float32"
    assert not any(k.endswith(("_dim", "_rank")) or "features" in k
                   or "heads" in k for k in entry["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_last_line_has_the_keys_the_driver_reads(run_cell, cell):
    rc, line, err = run_cell(cell)
    assert rc == 0, err
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True, err
    assert "compiles_in_window=0" in err
    for name, row in line["checks"].items():
        assert f"check {name}:" in err and row["value"] <= row["limit"]


def test_refuses_to_run_without_a_chip():
    import subprocess
    import sys
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "32big_mixer.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert got.returncode != 0 and got.stdout.strip() == ""
