"""On-chip A/B probe for config knobs: run a workload config with knob
overrides through the same harness as bench.bench_workload (median-of-5x10
step windows, host-pull timing) and print one JSON line per variant.

Usage:
  python tools/ab_probe.py --config 32mixer_group --batch 64 \
      --variant fused_mixer_block=true --variant fused_mixer_block=false
  python tools/ab_probe.py --config 32ctx_mixer --batch 8 \
      --variant blocked_causal_map=0 --variant blocked_causal_map=2

Each --variant is a comma-separated knob list (name=value; values parse as
JSON, falling back to string).  This is the single probe harness — the
round-5 fused-group and blocked-map measurements in docs/perf/README.md
used its per-knob predecessors with identical timing methodology.
"""
import argparse
import json
import sys
import time

import jax

sys.path.insert(0, ".")


def _parse_variant(spec: str) -> dict:
    knobs = {}
    for part in spec.split(","):
        name, _, value = part.partition("=")
        try:
            knobs[name] = json.loads(value)
        except json.JSONDecodeError:
            knobs[name] = value
    return knobs


def run(config: str, batch: int, knobs: dict) -> dict:
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils import one_chip_config, random_text_batch

    cfg = one_chip_config(config, train_batch_size=batch, **knobs)
    trainer = Trainer(cfg)
    batch_d = random_text_batch(cfg)
    state = trainer.init(batch_d)
    rng = jax.random.key(1)
    step_i = 0

    def run_steps(n, state):
        nonlocal step_i
        metrics = None
        for _ in range(n):
            state, metrics = trainer.step(state, batch_d,
                                          jax.random.fold_in(rng, step_i))
            step_i += 1
        return state, metrics

    state, metrics = run_steps(3, state)
    loss3 = float(metrics["loss"])
    windows = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, metrics = run_steps(10, state)
        float(metrics["loss"])
        windows.append(time.perf_counter() - t0)
    dt = sorted(windows)[2]
    tokens = cfg.train_batch_size * cfg.sequence_length * 10
    return {"config": config, **knobs,
            "ms_per_step": round(dt / 10 * 1e3, 1),
            "tok_s": round(tokens / dt, 0), "loss_after_3": round(loss3, 4),
            "loss_after_53": round(float(metrics["loss"]), 4),
            "windows_step_ms": [round(w / 10 * 1e3, 1) for w in windows]}


def main() -> None:
    from homebrewnlp_tpu.utils import enable_compilation_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--variant", action="append", required=True,
                    help="comma-separated knob=value list; one run each")
    args = ap.parse_args()
    enable_compilation_cache()
    for spec in args.variant:
        print(json.dumps(run(args.config, args.batch, _parse_variant(spec))),
              flush=True)


if __name__ == "__main__":
    main()
