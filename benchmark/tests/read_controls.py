"""Read the controls and the planted fault at a cell's own size.

    python3 benchmark/tests/read_controls.py <cell> <seed> [<seed> ...]

For every seed the plain reference follows the cell's first updates once
sound, then once per control of the reference's `LOWER` (the nearest
precision below the stated one, put in the program's place) and once with
half of the batch left out; each is read against the sound run by
`compare.readings` and judged as a run judges its own, by `compare.against`
and `compare.correct` under `limits/<cell>.json`: one JSON line a case, with
the readings, the harness's verdict `correct` and the numbers `over` their
limit.  This is how the upper readings of `limits/<cell>.json` were taken on
the chip (PERF.md); it is no part of a benchmark run.  A step that returns
its state unchanged needs no run: its `change_*` read 1 by construction.
"""
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), os.path.dirname(HERE)]


def main(argv) -> int:
    import run
    run.lift_compile_cache_cap()
    import jax.numpy as jnp
    import compare
    import generate
    from homebrewnlp_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    cell = run.find_cell(run.load_json(run.ROOT, "BENCHMARK.json"), argv[0])
    ref_mod, conf = cell["reference"], cell["config"]["benchmark"]
    raw = {k: v for k, v in cell["config"].items() if k != "benchmark"}
    sz = ref_mod.Sizes.from_config(raw)
    for seed in map(int, argv[1:]):
        batches = [(jnp.asarray(x), jnp.asarray(y)) for x, y in
                   generate.token_batches(cell["traffic"], seed,
                                          raw["train_batch_size"],
                                          raw["sequence_length"],
                                          raw["vocab_size"])]

        def follow(**kw):
            gc.collect()
            return ref_mod.follow(sz, seed, batches, conf["followed_steps"],
                                  conf["reference_rows"], **kw)

        sound = follow()
        cases = [(name, dict(lower=name)) for name in ref_mod.LOWER]
        cases.append(("half_batch", dict(half_batch=True)))
        for name, kw in cases:
            got = follow(**kw)
            read = compare.readings(got, sound)
            rows = compare.against(read, cell["limits"])
            print(json.dumps({"cell": argv[0], "seed": seed, "case": name,
                              "correct": compare.correct(rows),
                              "over": [n for n, v, lim in rows if not v <= lim],
                              "seconds": got["seconds"], **read}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
