"""Shared set-up of the benchmark's CPU tests: a bench directory with the
real readers and the tiny fixture cell, and the repository on the path."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny.sessions"


def bench_dir(tmp: Path) -> Path:
    """The repository's bench/ plus the tiny fixture's files."""
    d = tmp / "bench"
    shutil.copytree(ROOT / "bench", d, ignore=shutil.ignore_patterns(
        "__pycache__"))
    for sub in ("configs", "traffic", "cells"):
        for f in (DATA / sub).iterdir():
            shutil.copy(f, d / sub / f.name)
    return d


def benchmark_with_tiny() -> dict:
    """BENCHMARK.json with the tiny cell added as a session cell."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": TINY, "config": "tiny",
                            "traffic": "tiny-sessions", "chips": 1,
                            "why": "CPU test fixture"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY)
    return bm
