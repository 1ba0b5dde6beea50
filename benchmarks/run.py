"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only table7,...] [--full]

Emits ``name,us_per_call,derived`` CSV rows.  GNN tables run the FPGA-
constant cost-model simulation at full Table VI scale (the paper's own
latency IS its Table IV model + measured densities + Alg. 8 scheduling up
to load-balance noise); kernel timings are interpret-mode trends -- wall-
clock MFU is not claimable in this CPU container (see EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (bench_engine, bench_serving, fig13_runtime_overhead,
                        roofline, table4_perf_model, table7_k2p,
                        table8_pruning, table9_compiler, table10_accelerators)
from repro.jax_cache import configure_compile_cache

SUITES = {
    "engine": lambda full: (bench_engine.run_formats(),
                            bench_engine.run_gat()),
    "serving": lambda full: bench_serving.run(fast=not full),
    "table4": lambda full: table4_perf_model.run(fast=not full),
    "table7": lambda full: table7_k2p.run(),
    "table8": lambda full: table8_pruning.run(),
    "table9": lambda full: table9_compiler.run(),
    "fig13": lambda full: fig13_runtime_overhead.run(),
    "table10": lambda full: table10_accelerators.run(),
    "roofline": lambda full: roofline.run(),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            SUITES[name](args.full)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    configure_compile_cache()
    main()
