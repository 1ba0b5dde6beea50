"""Roofline table from the dry-run artifacts (EXPERIMENTS.md section
Roofline).  Reads results/dryrun/*.json (produced by launch/dryrun.py) and
prints the per-(arch x shape x mesh) three-term breakdown."""
from __future__ import annotations

import glob
import json
import os

from benchmarks.common import emit
from repro.jax_cache import configure_compile_cache

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results", "dryrun")


def run(results_dir: str = RESULTS) -> list:
    rows = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        tag = f"{rec['arch']}/{rec['shape']}/{rec['mesh']}"
        if rec.get("status") == "skipped":
            emit(f"roofline/{tag}", 0.0, "SKIPPED: " + rec["reason"][:60])
            continue
        if rec.get("status") != "ok":
            emit(f"roofline/{tag}", 0.0, "ERROR: " + rec.get("error", "")[:80])
            continue
        r = rec["roofline"]
        mem = rec.get("memory", {})
        emit(f"roofline/{tag}", r["bound_s"] * 1e6,
             f"dom={r['dominant']} comp={r['compute_s']:.4f}s "
             f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s "
             f"useful={r['useful_ratio']:.3f} "
             f"peak={mem.get('peak_gib', float('nan')):.1f}GiB")
        rows.append(rec)
    if not rows:
        emit("roofline/missing", 0.0,
             "run: python -m repro.launch.dryrun --all --out results/dryrun")
    return rows


if __name__ == "__main__":
    configure_compile_cache()
    run()
