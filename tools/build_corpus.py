"""Reproduce the in-image real-text corpus used by the 32ctx acceptance run
(configs/32ctx_accept_10k.json): walks deterministic source/doc roots inside
the image (natural-language-rich .py/.rst/.md/.txt), concatenates up to a
byte budget, splits into N parts, and shards them with text2tfrecord.

Usage:
  python tools/build_corpus.py --out-dir datasets [--limit-mb 80] [--parts 8]

Produces datasets/corpus/part_* and datasets/corpus_tf/shardbytes*.tfrecord.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

ROOTS = ["/usr/lib/python3.11", "/opt/venv/lib/python3.12/site-packages"]
EXTS = (".py", ".rst", ".md", ".txt")
SKIP_DIRS = {"__pycache__", "tests", "test"}


def assemble(out_path: str, limit: int) -> int:
    roots = [r for r in ROOTS if os.path.isdir(r)]
    if not roots:
        raise SystemExit(f"none of the corpus roots exist: {ROOTS}")
    n = 0
    with open(out_path, "w", encoding="utf-8", errors="replace") as out:
        for root in roots:
            # lazy walk: sorting IN PLACE keeps the dirs[:] pruning effective
            # (sorted(os.walk(...)) would drain the generator before pruning)
            # and makes the traversal order machine-independent
            for dirpath, dirs, files in os.walk(root):
                dirs[:] = sorted(d for d in dirs if d not in SKIP_DIRS)
                for f in sorted(files):
                    if not f.endswith(EXTS):
                        continue
                    try:
                        text = open(os.path.join(dirpath, f), encoding="utf-8",
                                    errors="replace").read()
                    except OSError:
                        continue
                    out.write(text + "\n\n")
                    n += len(text)
                    if n > limit:
                        return n
    if n == 0:
        raise SystemExit("corpus roots contained no matching text files")
    return n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="datasets")
    ap.add_argument("--limit-mb", type=int, default=80)
    ap.add_argument("--parts", type=int, default=8)
    args = ap.parse_args()
    corpus_dir = os.path.join(args.out_dir, "corpus")
    os.makedirs(corpus_dir, exist_ok=True)
    corpus = os.path.join(corpus_dir, "corpus.txt")
    n = assemble(corpus, args.limit_mb * 1024 * 1024)
    print(f"assembled {n} bytes -> {corpus}")
    for p in os.listdir(corpus_dir):  # stale parts from a previous --parts
        if p.startswith("part_"):
            os.remove(os.path.join(corpus_dir, p))
    subprocess.run(["split", "-n", str(args.parts), corpus,
                    os.path.join(corpus_dir, "part_")], check=True)
    parts = sorted(os.path.join(corpus_dir, p) for p in os.listdir(corpus_dir)
                   if p.startswith("part_"))
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "text2tfrecord.py")
    subprocess.run([sys.executable, tool, "--input", *parts, "--output-dir",
                    os.path.join(args.out_dir, "corpus_tf"),
                    "--files-per-shard", "1", "--procs", str(args.parts)],
                   check=True)


if __name__ == "__main__":
    main()
