"""Hyperparameter grid-sweep launcher.

Port of /root/reference/scripts/run_experiments.py: meshgrid over list-valued
config entries (:62-75), one JSON config + run name per grid point (:78-93),
then launch each run (:99-125).  The reference hardcodes preemptible-TPU
creation through ``gcloud compute tpus create`` inside ``screen``; here the
launch command is a template (``--launch-cmd``) so the same sweep runs
locally, under tmux, or against any cloud CLI.  The local default runs the
grid points one after another (one process per chip); a cloud template that
creates a machine per run starts them all at once.

Usage:
  python tools/run_experiments.py --base configs/32ctx_mixer.json \
      --grid learning_rate=0.01,0.003 --grid depth=8,16 \
      --out-dir sweeps/lr_depth [--execute] \
      [--launch-cmd 'python main.py --model {config} --run_mode train']
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess

LOCAL_TEMPLATE = "python3 main.py --model {config} --run_mode train"
GCLOUD_TEMPLATE = (
    "gcloud compute tpus create {name} --zone europe-west4-a --range {cidr} "
    "--accelerator-type v3-8 --version tpu-vm-tf-2.x --preemptible && "
    "python3 main.py --model {config} --tpu {name} --run_mode train; "
    "gcloud compute tpus delete {name} --zone europe-west4-a --quiet"
)


def parse_value(v: str):
    try:
        return json.loads(v)
    except json.JSONDecodeError:
        return v


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", required=True, help="base JSON config")
    ap.add_argument("--grid", action="append", default=[],
                    help="key=v1,v2,... (repeatable); meshgrid over all")
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--launch-cmd", default=LOCAL_TEMPLATE,
                    help="command per run; {config}/{name}/{cidr} substituted."
                         " The local default runs the grid points one after"
                         " another (they share this host's chips); any other"
                         " command is started for all points at once."
                         f" gcloud recipe: {GCLOUD_TEMPLATE!r}")
    ap.add_argument("--cidr-base", default="10.48", help="first two CIDR "
                    "octets for TPU ranges (reference :78-93)")
    ap.add_argument("--execute", action="store_true",
                    help="actually launch (default: just write configs)")
    args = ap.parse_args()

    with open(args.base) as f:
        base = json.load(f)
    keys, value_lists = [], []
    for g in args.grid:
        key, vals = g.split("=", 1)
        keys.append(key)
        value_lists.append([parse_value(v) for v in vals.split(",")])

    os.makedirs(args.out_dir, exist_ok=True)
    procs = []
    for run_idx, combo in enumerate(itertools.product(*value_lists)):
        cfg = dict(base)
        name_parts = []
        for k, v in zip(keys, combo):
            cfg[k] = v
            name_parts.append(f"{k}={v}")
        name = "-".join(name_parts).replace("/", "_") or f"run{run_idx}"
        cfg["model_path"] = os.path.join(args.out_dir, name)
        cfg_path = os.path.join(args.out_dir, f"{name}.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f, indent=2)
        cidr = f"{args.cidr_base}.{run_idx}.0/29"
        cmd = args.launch_cmd.format(config=cfg_path, name=f"sweep-{run_idx}",
                                     cidr=cidr)
        print(("LAUNCH " if args.execute else "would launch ") + cmd)
        if not args.execute:
            continue
        if args.launch_cmd == LOCAL_TEMPLATE:
            # a chip belongs to one process at a time: a second local run
            # started beside the first fails or hangs at device init
            subprocess.run(cmd, shell=True)
        else:
            procs.append(subprocess.Popen(cmd, shell=True))
    for p in procs:
        p.wait()


if __name__ == "__main__":
    main()
