"""Training child for the fleet-lockstep chaos drill (elastic_test.py and
the CI ``chaos-multihost`` job).

Runs as ``python tests/elastic_child.py --model-path DIR --steps N
[--fault-plan PLAN]``: a tiny synthetic-data training under checkpointing,
exactly what ``tools/supervise.py`` launches per host.  A
``peer:die@stepK`` plan makes the child observe a (simulated) peer death at
global step K — checkpoint cut, exit ``EXIT_PEER_LOST`` (87) — and the
resumed relaunch disarms the rule behind its restore point, so the fleet
generation after the lockstep relaunch completes with a loss sequence
bit-identical to an uninterrupted run."""
import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model-path", required=True)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--fault-plan", default="")
    p.add_argument("--obs-spans", action="store_true",
                   help="record host spans (the fleet-obs drill merges the "
                        "per-rank traces; fleet postings themselves key off "
                        "the supervisor-injected HBNLP_FLEET_DIR)")
    args = p.parse_args()
    import jax
    jax.config.update("jax_platforms", "cpu")
    from tests.backend import tiny_config
    from homebrewnlp_tpu import main as cli
    cfg = tiny_config(model_path=args.model_path, use_checkpointing=True,
                      steps_per_checkpoint=2, fault_plan=args.fault_plan,
                      grace_deadline_s=60.0, obs_spans=args.obs_spans)
    cli.train(cfg, argparse.Namespace(steps=args.steps, profile="",
                                      workers=None))


if __name__ == "__main__":
    main()
