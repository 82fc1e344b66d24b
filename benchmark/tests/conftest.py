"""benchmark/tests run by hand on the CPU at toy width:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 tests."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TOY_MANIFEST = os.path.join(HERE, "toy", "BENCHMARK.json")
CELLS = ("toy-32big_mixer.train", "toy-32mixer_group.train")


def cpu_for_the_chip(cell):
    """In `run.find_device`'s place: whatever JAX shows, with the peaks of
    the first recorded kind (a toy cell's rates mean nothing)."""
    import jax
    import run
    devices = jax.devices()
    peaks = run.load_json(BENCH, "peaks.json")
    return (devices[0].platform, devices[0].device_kind, len(devices),
            next(iter(peaks.values())))


@pytest.fixture(scope="session")
def run_cell(tmp_path_factory):
    """Drive `run.main` on a toy cell and return (exit code, parsed last
    line, standard error).  The harness's look for a chip is skipped and its
    manifest is the toy one; `fault`, given the program's `Trainer.step`,
    returns what stands in its place for the run: the timed path broken
    underneath, everything of the harness as it is."""
    import contextlib
    import io
    import run
    from homebrewnlp_tpu.train import Trainer
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(tmp_path_factory.mktemp("jax_cache")))

    def go(cell, seed=7, seconds=1.0, trace=0, fault=None):
        out, err = io.StringIO(), io.StringIO()
        with pytest.MonkeyPatch.context() as patch, \
                contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            patch.setattr(run, "MANIFEST", TOY_MANIFEST)
            patch.setattr(run, "find_device", cpu_for_the_chip)
            if fault is not None:
                patch.setattr(Trainer, "step", fault(Trainer.step))
            rc = run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
        lines = out.getvalue().strip().splitlines()
        return rc, json.loads(lines[-1]) if lines else None, err.getvalue()

    return go
