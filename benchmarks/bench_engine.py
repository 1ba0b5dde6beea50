"""Executor checks on the CPU: format-aware planning and GAT attention.

* ``--formats`` -- density sweep of format-aware planning (row-CSR vs the
  block path) through ``FusedModelExecutor``, with dense-oracle parity;
  writes ``format_rows`` and the measured crossover into
  ``BENCH_engine.json``.
* ``--gat`` -- the GAT model through ``FusedModelExecutor`` against an
  independent float64 NumPy oracle, plus the per-head plan evidence.

Timings here are CPU wall clocks for the format sweep only; speed claims
come from the chip benchmark (``bench/``).

  PYTHONPATH=src python -m benchmarks.run --only engine
  PYTHONPATH=src python -m benchmarks.bench_engine --formats --smoke
  PYTHONPATH=src python -m benchmarks.bench_engine --gat --smoke
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import compiler, runtime
from repro.core.ir import Activation, AggOp, KernelType
from repro.core.perf_model import Format, Primitive, TPUCostModel
from repro.data import graphs as graph_data
from repro.jax_cache import configure_compile_cache
from repro.models import gnn as gnn_models

_OUT = pathlib.Path(__file__).resolve().parents[1] / "BENCH_engine.json"


def _merge_json(update: dict) -> None:
    """Merge ``update`` into BENCH_engine.json, preserving other sections
    (the format sweep and the GAT row write independently)."""
    data = json.loads(_OUT.read_text()) if _OUT.exists() else {}
    data.update(update)
    _OUT.write_text(json.dumps(data, indent=2) + "\n")


def _time_paired(fns, repeats: int) -> list:
    """Best-of-N wall clocks, INTERLEAVED across the candidates.

    Best-of-N is the standard low-noise latency estimator (the minimum is
    the run least perturbed by the OS scheduler); interleaving the
    candidates inside each round additionally cancels slow drift in shared
    container load, which sequential per-engine loops would alias into a
    fake speedup/regression.
    """
    for fn in fns:
        fn()                              # warm compile/dispatch caches
    best = [float("inf")] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return best


def _er_bundle(model: str, n: int, density: float, *, f_in: int = 64,
               hidden: int = 16, n_classes: int = 7, seed: int = 0):
    """Compile ``model`` over a synthetic ER graph at ``density`` (the
    density sweep axis the datasets cannot provide)."""
    rng = np.random.default_rng(seed)
    a = (rng.random((n, n)) < density).astype(np.float32)
    np.fill_diagonal(a, 0.0)
    a_gcn, a_mean = graph_data.normalize_adjacency(a)
    h0 = (rng.normal(size=(n, f_in))
          * (rng.random((n, f_in)) < 0.5)).astype(np.float32)
    spec = gnn_models.make_model_spec(model, f_in, hidden, n_classes)
    meta = compiler.GraphMeta("ER", n, int(a.sum()), f_in)
    tensors = {"A": jnp.asarray(a_gcn), "A_mean": jnp.asarray(a_mean),
               "H0": jnp.asarray(h0)}
    cm = compiler.compile_model(spec, meta, n_cc=7, tensors=tensors,
                                align=16, on_chip_bytes=256 * 1024)
    for name, w in gnn_models.init_weights(cm, seed=seed).items():
        tensors[name] = jnp.asarray(w)
    return cm, tensors


def _dense_oracle(compiled, tensors):
    """Plain jnp.dot walk with the executor's epilogue semantics."""
    env = dict(tensors)
    for k in compiled.graph.topo_order():
        if k.kernel_type == KernelType.AGGREGATE:
            x = env["A" if k.agg_op == AggOp.SUM else "A_mean"]
        else:
            x = env[k.lhs]
        y = env[k.rhs]
        out = jnp.dot(x, y, preferred_element_type=jnp.float32).astype(
            jnp.promote_types(x.dtype, y.dtype))
        if k.epilogue_add is not None:
            out = out + env[k.epilogue_add] * k.epilogue_scale
        if k.activation_enabled:
            if k.activation == Activation.RELU:
                out = jax.nn.relu(out)
            elif k.activation == Activation.PRELU:
                out = jnp.where(out >= 0, out, 0.25 * out)
        env[k.out] = out
    return env[compiled.graph.kernels[-1].out]


def run_formats(*, smoke: bool = False, write_json: bool = True) -> list:
    """Density sweep for format-aware planning (DESIGN.md section 13).

    GraphSAGE aggregates the RAW feature matrix (f_in columns), so its two
    Aggregate kernels carry enough arithmetic for the row-CSR-vs-block
    decision to bite in both directions across the sweep: row-CSR wins at
    the sparse end and the planner falls back to the block path (fill
    guard and transform cost) at the dense end.  Both executors run the
    SAME program shape; only the format decision differs.  ``csr_rmax``
    is deliberately small: the padded row format's conversion AND gather
    costs scale with rmax, so a tight row budget is what makes the sparse
    end pay -- the fill guard then vetoes CSR exactly where the budget no
    longer fits, which is the crossover this sweep measures.
    """
    model, f_in, rmax = "sage", 128, 16
    if smoke:
        n, densities, repeats = 512, (0.004,), 3
    else:
        n, densities, repeats = 1024, (0.001, 0.002, 0.005, 0.01, 0.02), 5
    mk = dict(model=TPUCostModel(), collect_report=False)
    fmt_eng = runtime.FusedModelExecutor(format_aware=True, csr_rmax=rmax,
                                         **mk)
    blk_eng = runtime.FusedModelExecutor(format_aware=False, **mk)
    probe = runtime.FusedModelExecutor(format_aware=True, csr_rmax=rmax,
                                       keep_codes=True, **mk)
    rows = []
    for density in densities:
        cm, tensors = _er_bundle(model, n, density, f_in=f_in, seed=0)
        last = cm.graph.kernels[-1].out
        fmt_s, blk_s = _time_paired(
            [lambda: fmt_eng.run(cm, tensors)[0][last],
             lambda: blk_eng.run(cm, tensors)[0][last]], repeats)
        env, _ = probe.run(cm, tensors)
        oracle = np.asarray(_dense_oracle(cm, tensors))
        parity = bool(np.allclose(np.asarray(env[last]), oracle,
                                  atol=3e-4, rtol=3e-4))
        fmts = {name: int(np.asarray(f))
                for name, f in probe.planned_formats.items()}
        speedup = blk_s / fmt_s if fmt_s > 0 else float("inf")
        rows.append({
            "model": model, "n": n, "f_in": f_in, "csr_rmax": rmax,
            "density": density,
            "formats": fmts,
            "csr_kernels": sum(f == int(Format.CSR) for f in fmts.values()),
            "format_aware_s": fmt_s, "block_only_s": blk_s,
            "speedup": speedup, "parity_ok": parity,
        })
        emit(f"engine.formats.{model}.d{density}", fmt_s * 1e6,
             f"block={blk_s*1e6:.0f}us speedup={speedup:.2f}x "
             f"csr_kernels={rows[-1]['csr_kernels']} parity={parity}")
    wins = [r["density"] for r in rows if r["speedup"] > 1.0
            and r["csr_kernels"] > 0]
    crossover = max(wins) if wins else None
    if write_json:
        _merge_json({
            "format_rows": rows,
            "format_crossover_density": crossover,
        })
    emit("engine.formats.crossover", 0.0,
         f"row-CSR wins up to density {crossover}")
    return rows


def _gat_oracle(compiled, tensors):
    """Independent float64 NumPy forward pass for GAT: dense matmuls plus
    an explicit masked edge-softmax (the ``_dense_oracle`` twin for models
    with ATTENTION kernels, which that walk cannot execute)."""
    env = {name: np.asarray(v, np.float64) for name, v in tensors.items()}
    for k in compiled.graph.topo_order():
        if k.kernel_type == KernelType.ATTENTION:
            z = env[k.rhs]
            s = z @ env[k.att_src] + (z @ env[k.att_dst]).T
            s = np.where(s >= 0, s, k.att_slope * s)
            sup = env[k.lhs] != 0
            s = np.where(sup, s, -np.inf)
            rm = s.max(axis=1, keepdims=True, initial=-np.inf)
            rm = np.where(np.isfinite(rm), rm, 0.0)
            ex = np.where(sup, np.exp(s - rm), 0.0)
            alpha = ex / np.maximum(ex.sum(axis=1, keepdims=True), 1e-30)
            env[k.out] = np.where(alpha > k.att_threshold, alpha, 0.0)
            continue
        if k.kernel_type == KernelType.HEAD_COMBINE:
            heads = [env[name] for name in k.heads_in]
            out = (sum(heads) / len(heads) if k.head_mean
                   else np.concatenate(heads, axis=1))
            if k.epilogue_add is not None:
                out = out + env[k.epilogue_add]
            if k.activation_enabled and k.activation == Activation.ELU:
                out = np.where(out > 0, out, np.expm1(out))
            env[k.out] = out
            continue
        if k.kernel_type == KernelType.AGGREGATE and k.lhs == "A":
            x = env["A" if k.agg_op == AggOp.SUM else "A_mean"]
        else:
            x = env[k.lhs]
        out = x @ env[k.rhs]
        if k.epilogue_add is not None:
            out = out + env[k.epilogue_add] * k.epilogue_scale
        if k.activation_enabled:
            if k.activation == Activation.RELU:
                out = np.maximum(out, 0.0)
            elif k.activation == Activation.PRELU:
                out = np.where(out >= 0, out, 0.25 * out)
        env[k.out] = out
    return env[compiled.graph.kernels[-1].out]


def run_gat(*, write_json: bool = True) -> list:
    """GAT row (DESIGN.md §17): dynamic attention sparsity through the
    executor -- agreement with an independent float64 oracle, and the
    per-head plan evidence (each head's aggregate planned from that head's
    thresholded attention profile)."""
    b = gnn_models.build_dense("gat", "CO", scale=0.12, seed=2)
    last = b.compiled.graph.kernels[-1].out
    probe = runtime.FusedModelExecutor(keep_codes=True)
    env_f, _ = probe.run(b.compiled, b.tensors)
    oracle = _gat_oracle(b.compiled, b.tensors)
    oracle_ok = bool(np.allclose(np.asarray(env_f[last]), oracle,
                                 atol=3e-4, rtol=3e-4))
    heads = {k.out: probe.planned_codes[k.out]
             for k in b.compiled.graph.kernels
             if k.kernel_type == KernelType.AGGREGATE and k.lhs != "A"}
    hist = {out: {p.name: int((codes == int(p)).sum())
                  for p in Primitive}
            for out, codes in heads.items()}
    l1 = sorted(h for h in heads if h in ("G1h1", "G1h2"))
    distinct = (len(l1) == 2
                and not np.array_equal(heads[l1[0]], heads[l1[1]]))
    row = {
        "model": "gat", "dataset": "CO", "scale": 0.12,
        "oracle_ok": oracle_ok,
        "per_head_plan_histograms": hist,
        "layer1_head_plans_distinct": bool(distinct),
    }
    emit("engine.gat.CO", 0.0,
         f"oracle={oracle_ok} heads_distinct={distinct}")
    if write_json:
        _merge_json({"gat_rows": [row]})
    return [row]


if __name__ == "__main__":
    configure_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smallest sizes, gates on, no "
                         "BENCH_engine.json rewrite.  Without --formats "
                         "or --gat, both run")
    ap.add_argument("--formats", action="store_true",
                    help="the format-aware density sweep (row-CSR vs "
                         "block path); with --smoke it gates on parity AND "
                         "row-CSR winning at the sparsest point")
    ap.add_argument("--gat", action="store_true",
                    help="the GAT attention row; with --smoke it gates on "
                         "the independent float64 oracle")
    args = ap.parse_args()
    both = not (args.gat or args.formats)
    if args.gat or both:
        gat_rows = run_gat(write_json=not args.smoke)
        if args.smoke:
            bad = [r for r in gat_rows if not r["oracle_ok"]]
            if bad:
                sys.exit(f"gat oracle gate failed: {bad}")
    if args.formats or both:
        fmt_rows = run_formats(smoke=args.smoke, write_json=not args.smoke)
        if args.smoke:
            bad = [r for r in fmt_rows if not r["parity_ok"]]
            if bad:
                sys.exit(f"format-aware path breaks parity: {bad}")
            sparsest = min(fmt_rows, key=lambda r: r["density"])
            if sparsest["csr_kernels"] == 0 or sparsest["speedup"] <= 1.0:
                sys.exit("row-CSR does not win at the sparsest point: "
                         f"{sparsest}")
