#!/usr/bin/env python
"""graftprof CLI: device-time attribution from jax.profiler traces.

Renders the per-scope / per-category attribution of a profiler capture
(docs/observability.md "Profile attribution"), exports flamegraph
collapsed stacks, diffs two captures (``--compare``), and reconciles the
measured decomposition against graftcost's static estimate (``--config``).

Sources (positional argument, auto-detected):

- a profiler output directory (``--profile`` dir / bench tempdir) — the
  newest ``plugins/profile/<session>/*.trace.json.gz`` is parsed, joined
  with the ``graftprof_op_map.json`` sidecar when present; the layer x
  pass table (``obs.profile.step_scope``) comes from the session's
  ``.xplane.pb`` where that names its instructions itself (a TPU);
- a ``*.trace.json[.gz]`` file directly;
- a saved ``profile_summary.json`` (main.py writes one per ``--profile``
  run);
- a committed ``BENCH_r*.json`` line — the per-workload ``profile``
  sub-dict is adapted (pick the row with ``--workload``), so two BENCH
  rounds diff directly: ``graftprof.py BENCH_r06.json --compare
  BENCH_r07.json``.

Examples::

    python tools/graftprof.py /tmp/run/prof --steps 3
    python tools/graftprof.py /tmp/run/prof --flame /tmp/flame.txt
    python tools/graftprof.py BENCH_r06.json --compare BENCH_r07.json
    python tools/graftprof.py /tmp/run/prof --config configs/32big_mixer.json \
        --device v5e

Exit codes: 0 ok; 1 an ``--min-*`` attribution gate failed; 2 usage /
unreadable source.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import typing

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from homebrewnlp_tpu.obs import profile as P  # noqa: E402


def _summary_from_bench_row(row: dict, workload: str) -> P.ProfileSummary:
    """Adapt a BENCH workload ``profile`` sub-dict to a ProfileSummary —
    enough shape for tables and ``--compare`` (bench rows carry per-step
    figures; scopes re-inflate to window seconds)."""
    if not isinstance(row, dict) or "fractions" not in row:
        raise ValueError(
            f"workload {workload!r} carries no usable profile sub-dict "
            f"(got {sorted(row) if isinstance(row, dict) else type(row)})")
    steps = int(row.get("n_steps") or 1)
    decomp = dict(row.get("ms_per_step", {}))
    wall_ms = decomp.get("total", 0.0) * steps
    idle_ms = decomp.get("idle", 0.0) * steps
    return P.ProfileSummary(
        wall_s=wall_ms / 1e3,
        busy_s=(wall_ms - idle_ms) / 1e3,
        n_events=0, n_malformed=0, n_lanes=0, n_steps=steps,
        categories_s={}, collectives_s=dict(row.get("collectives_s", {})),
        scopes_s={k: v * steps / 1e3
                  for k, v in row.get("scopes_ms", {}).items()},
        top_ops=list(row.get("top_ops", [])),
        attributed_category_frac=row.get("attributed_category_frac", 0.0),
        attributed_scope_frac=row.get("attributed_scope_frac", 0.0),
        decomposition_ms_per_step=decomp,
        fractions=dict(row.get("fractions", {})))


def load_source(path: str, steps: typing.Optional[int],
                workload: str) -> P.ProfileSummary:
    """Resolve any supported source to a ProfileSummary (module doc)."""
    if os.path.isdir(path):
        s = P.capture_summary(path, n_steps=steps)
        if s is None:
            raise FileNotFoundError(
                f"no *.trace.json(.gz) under {path} (profiler plugin "
                f"directory absent)")
        return s
    if path.endswith((".trace.json", ".trace.json.gz", ".gz")):
        return P.summarize_trace(path, op_map=P.sidecar_op_map(path),
                                 n_steps=steps)
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "workloads" in doc:  # a BENCH line
        return _summary_from_bench_row(
            doc["workloads"].get(workload, {}).get("profile", {}), workload)
    if isinstance(doc, dict) and "traceEvents" in doc or isinstance(doc, list):
        events = doc if isinstance(doc, list) else doc["traceEvents"]
        return P.summarize_events(events, op_map=P.sidecar_op_map(path),
                                  n_steps=steps)
    if isinstance(doc, dict) and "wall_s" in doc:  # profile_summary.json
        return P.ProfileSummary.from_json(doc)
    raise ValueError(f"unrecognized source format: {path}")


def _collapse_depth(scopes_s: typing.Dict[str, float], depth: int
                    ) -> typing.Dict[str, float]:
    if depth <= 0:
        return dict(scopes_s)
    out: typing.Dict[str, float] = {}
    for k, v in scopes_s.items():
        key = "/".join(k.split("/")[:depth])
        out[key] = out.get(key, 0.0) + v
    return out


def render_summary(s: P.ProfileSummary, top: int, depth: int) -> str:
    lines = []
    steps = max(1, s.n_steps or 1)
    d = s.decomposition_ms_per_step
    lines.append(
        f"device window: {s.wall_s * 1e3:.3f} ms over {steps} step(s), "
        f"{s.n_events} events on {s.n_lanes} lane(s)"
        + (f", {s.n_malformed} malformed skipped" if s.n_malformed else ""))
    lines.append(
        f"ms/step: {d.get('total', 0.0):9.3f} = "
        f"mxu {d.get('mxu', 0.0):.3f} + hbm {d.get('hbm', 0.0):.3f} + "
        f"comm {d.get('comm', 0.0):.3f} + idle {d.get('idle', 0.0):.3f}")
    lines.append(
        f"attributed: category {s.attributed_category_frac:6.1%}   "
        f"scope {s.attributed_scope_frac:6.1%}")
    if s.categories_s:
        lines.append("")
        # lane-ms: SELF-time summed across concurrent device lanes
        # (thread-time), so totals can exceed the wall-clock ms/step above
        lines.append(f"{'category':<12} {'lane-ms/step':>12} {'share':>7}")
        busy = sum(s.categories_s.values()) or 1.0
        for cat, v in sorted(s.categories_s.items(), key=lambda kv: -kv[1]):
            lines.append(f"{cat:<12} {v * 1e3 / steps:>12.3f} "
                         f"{v / busy:>7.1%}")
    if s.collectives_s:
        lines.append("")
        lines.append(f"{'collective':<20} {'lane-ms/step':>12}")
        for kind, v in sorted(s.collectives_s.items(), key=lambda kv: -kv[1]):
            lines.append(f"{kind:<20} {v * 1e3 / steps:>12.3f}")
    scopes = _collapse_depth(s.scopes_s, depth)
    if scopes:
        total = sum(scopes.values()) or 1.0
        lines.append("")
        lines.append(f"{'scope':<56} {'lane-ms/step':>12} {'share':>7}")
        for k, v in sorted(scopes.items(), key=lambda kv: -kv[1])[:top]:
            lines.append(f"{k[:56]:<56} {v * 1e3 / steps:>12.3f} "
                         f"{v / total:>7.1%}")
    table = P.layer_pass_table(s.layer_pass_s, steps)
    if table:
        lines += ["", *table]
    if s.top_ops:
        lines.append("")
        lines.append(f"{'op':<28} {'category':<11} "
                     f"{'scope':<40} {'lane-ms':>9}")
        for r in s.top_ops[:top]:
            lines.append(f"{r['op'][:28]:<28} {r['category']:<11} "
                         f"{r['scope'][:40]:<40} "
                         f"{r['self_s'] * 1e3 / steps:>9.3f}")
    return "\n".join(lines)


def render_diff(diff: dict, top: int) -> str:
    lines = []
    ms = diff["ms_per_step"]
    lines.append(f"ms/step: {ms['a']:.3f} -> {ms['b']:.3f} "
                 f"({ms['delta']:+.3f})")
    fd = diff["fractions_delta"]
    lines.append("fraction drift: " + "  ".join(
        f"{k} {fd[k]:+.3f}" for k in ("mxu", "hbm", "comm", "idle")))
    lines.append(f"scope coverage drift: "
                 f"{diff['attributed_scope_frac_delta']:+.3f}")
    rows = sorted(diff["scopes_ms"].items(),
                  key=lambda kv: -abs(kv[1]["delta_ms"]))[:top]
    if rows:
        lines.append("")
        lines.append(f"{'scope':<56} {'a ms':>9} {'b ms':>9} {'delta':>9}")
        for k, r in rows:
            lines.append(f"{k[:56]:<56} {r['a_ms']:>9.3f} {r['b_ms']:>9.3f} "
                         f"{r['delta_ms']:>+9.3f}")
    return "\n".join(lines)


def _reconcile_for_config(summary: P.ProfileSummary, config_path: str,
                          device: str) -> dict:
    from homebrewnlp_tpu.analysis import cost_model, trace_config
    from homebrewnlp_tpu.analysis.graph_rules import intended_mesh
    from homebrewnlp_tpu.utils import load_config
    cfg = load_config(config_path)
    name = os.path.splitext(os.path.basename(config_path))[0]
    traces = trace_config(cfg, name, steps=("train",))
    if "train" in traces.errors:
        raise RuntimeError(f"trace failed: {traces.errors['train']}")
    res = cost_model.config_resources(traces)["train"]
    kind = device or cfg.target_device or cost_model.DEFAULT_VERDICT_DEVICE
    pred = cost_model.step_static_times(res, dict(intended_mesh(cfg).shape),
                                        kind)
    out = P.reconcile(summary, pred)
    return {"device": kind, "verdict": res.verdict, "components": out}


def render_reconcile(rec: dict) -> str:
    lines = [f"graftcost reconciliation on {rec['device']} "
             f"(static verdict: {rec['verdict']})",
             f"{'component':<10} {'predicted ms':>13} {'measured ms':>12} "
             f"{'error':>8}"]
    for comp, r in rec["components"].items():
        pred = ("-" if r["predicted_ms"] is None
                else f"{r['predicted_ms']:.3f}")
        err = ("-" if r["prediction_error"] is None
               else f"{r['prediction_error']:+.1%}")
        lines.append(f"{comp:<10} {pred:>13} {r['measured_ms']:>12.3f} "
                     f"{err:>8}")
    return "\n".join(lines)


def main(argv: typing.Optional[typing.Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        description="device-time attribution from jax.profiler traces")
    p.add_argument("trace", help="profiler dir / trace file / "
                   "profile_summary.json / BENCH_r*.json")
    p.add_argument("--steps", type=int, default=None,
                   help="steps captured in the window (per-step figures)")
    p.add_argument("--workload", default="32big_mixer",
                   help="workload row to read from a BENCH json source")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--depth", type=int, default=0,
                   help="collapse scope paths to this depth (0 = full)")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--flame", default="",
                   help="write flamegraph collapsed stacks to this path")
    p.add_argument("--compare", default="",
                   help="second source: print attribution drift (b - a)")
    p.add_argument("--config", default="",
                   help="config JSON: reconcile vs the graftcost estimate")
    p.add_argument("--device", default="",
                   help="device kind for --config (default: target_device "
                        "or the graftcost verdict default)")
    p.add_argument("--min-category-frac", type=float, default=None,
                   help="exit 1 when category attribution is below this")
    p.add_argument("--min-scope-frac", type=float, default=None,
                   help="exit 1 when scope attribution is below this")
    args = p.parse_args(argv)

    try:
        summary = load_source(args.trace, args.steps, args.workload)
    except Exception as e:
        print(f"graftprof: cannot load {args.trace}: {e}", file=sys.stderr)
        return 2

    if args.compare:
        try:
            other = load_source(args.compare, args.steps, args.workload)
        except Exception as e:
            print(f"graftprof: cannot load {args.compare}: {e}",
                  file=sys.stderr)
            return 2
        diff = P.diff_summaries(summary, other)
        print(json.dumps(diff, indent=1, sort_keys=True) if args.as_json
              else render_diff(diff, args.top))
        return 0

    rec = None
    if args.config:
        try:
            rec = _reconcile_for_config(summary, args.config, args.device)
        except Exception as e:
            print(f"graftprof: reconciliation failed: {e}", file=sys.stderr)
            return 2

    if args.flame:
        with open(args.flame, "w") as f:
            f.write("\n".join(P.collapsed_stacks(summary)) + "\n")
        print(f"flamegraph collapsed stacks -> {args.flame}",
              file=sys.stderr)

    if args.as_json:
        doc = summary.to_json()
        if rec is not None:
            doc["reconcile"] = rec
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(render_summary(summary, args.top, args.depth))
        if rec is not None:
            print()
            print(render_reconcile(rec))

    failed = []
    if (args.min_category_frac is not None
            and summary.attributed_category_frac < args.min_category_frac):
        failed.append(f"category attribution "
                      f"{summary.attributed_category_frac:.1%} < "
                      f"{args.min_category_frac:.1%}")
    if (args.min_scope_frac is not None
            and summary.attributed_scope_frac < args.min_scope_frac):
        failed.append(f"scope attribution "
                      f"{summary.attributed_scope_frac:.1%} < "
                      f"{args.min_scope_frac:.1%}")
    for msg in failed:
        print(f"graftprof: GATE FAILED: {msg}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
