"""Docs lint: every code reference in README.md / DESIGN.md must resolve.

Two checks, both cheap enough for every push:

1. path references -- any backticked `src/...`, `tests/...`,
   `benchmarks/...`, `examples/...`, or top-level `*.md` / `*.json` /
   `*.py` token must exist in the repo;
2. import references -- any backticked dotted `repro.*` module path must
   import (attribute tails like `repro.core.runtime.FusedModelExecutor`
   resolve module-then-attr), and the public engine surface the docs lean
   on is imported explicitly so a rename breaks CI, not the reader.

  PYTHONPATH=src python tools/check_doc_refs.py
"""
from __future__ import annotations

import importlib
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = ["README.md", "DESIGN.md"]

_PATH_RE = re.compile(
    r"`((?:src|tests|benchmarks|examples|tools|results)/[\w./-]+"
    r"|[\w-]+\.(?:md|json|py|yml))`")
_MOD_RE = re.compile(r"`(repro(?:\.\w+)+)`")

# the public surface the documentation's prose names without backticked
# dotted paths; keep in sync with README "Choosing an executor" / DESIGN 0/9
PUBLIC = [
    ("repro.core.runtime", ["FusedModelExecutor", "simulate_inference",
                            "propagate_stats", "InferenceReport"]),
    # attention_adjacency is the GAT edge-softmax the executor runs
    # (DESIGN 17 / README "Serving a mutating graph")
    ("repro.core.dynasparse", ["dynasparse_matmul", "DynasparseResult",
                               "dynasparse_dense_equivalent",
                               "attention_adjacency"]),
    ("repro.core.analyzer", ["plan_codes", "plan_codes_from_profiles",
                             "plan_format", "STRATEGIES",
                             "delta_replan_mask"]),
    # the format-aware planning surface (DESIGN 13 / README "Format-aware
    # aggregation")
    ("repro.core.perf_model", ["Format", "Primitive", "TPUCostModel",
                               "FPGACostModel"]),
    ("repro.core.formats", ["CSRMatrix", "ELLMatrix", "COOMatrix",
                            "dense_to_csr", "csr_to_dense", "coo_to_csr",
                            "csr_to_coo", "dense_to_ell", "csr_to_ell",
                            "ell_to_dense", "ell_matmul", "dense_to_coo",
                            "coo_to_dense"]),
    ("repro.kernels.ops", ["csr_spmm", "spdmm", "spmm", "matmul"]),
    ("repro.core.profiler", ["BlockProfile", "SparsityStats",
                             "block_density", "block_counts",
                             "batched_block_counts"]),
    ("repro.core.ir", ["OperandFlow", "ComputationGraph"]),
    ("repro.serving.engine", ["ServeEngine"]),
    # the serving surface DESIGN 10 / README "Serving a stream of graphs"
    # lean on; run_batch is the executor's multi-tenant entry point
    ("repro.serving.graph_engine", ["GraphServeEngine", "GraphRequest",
                                    "GraphResult", "random_requests"]),
    # the continuous-serving surface (DESIGN 11 / README "Continuous
    # serving") plus the overload-control surface (DESIGN 15 / README
    # "Overload-controlled serving")
    ("repro.serving.scheduler", ["ContinuousGraphServer", "QueuedRequest",
                                 "WaveLog", "plan_groups", "plan_lanes",
                                 "Ticket", "ClassStats"]),
    # the consolidated config surface (DESIGN 15): frozen dataclasses both
    # serving constructors accept via config=
    ("repro.serving.config", ["EngineConfig", "ServeConfig",
                              "merge_config", "UNSET"]),
    # the sharded-dispatch surface (DESIGN 12 / README "Sharding waves
    # over a device mesh")
    ("repro.distributed.sharding", ["cores_mesh", "wave_spec",
                                    "wave_shardings", "CORES_AXIS",
                                    # disjoint submesh layer (DESIGN 14 /
                                    # README "Disjoint lane submeshes")
                                    "partition_mesh", "partition_devices",
                                    "abstract_cores_mesh"]),
    ("repro.core.scheduler", ["schedule_lpt", "assign_bins",
                              "steal_rebalance"]),
    ("repro.models.gnn", ["build_dense", "build_sim", "GNN_MODELS",
                          "init_spec_weights"]),
    ("repro.data.graphs", ["normalize_adjacency", "materialize"]),
    # the giant-graph mini-batch surface (DESIGN 16 / README "Mini-batch
    # serving over a giant graph")
    # the streaming-delta surface rides along (DESIGN 17 / README
    # "Serving a mutating graph")
    ("repro.data.sampling", ["HostGraph", "SampledSubgraph",
                             "sample_subgraph", "powerlaw_host_graph",
                             "vertex_seed", "GraphDelta",
                             "AdjacencyBlockProfile"]),
    ("repro.serving.minibatch", ["FeatureStore", "VertexCache",
                                 "CacheStats", "SeedRequest",
                                 "MiniBatchPlanner", "MiniBatchServeEngine",
                                 "QueryTicket", "DeltaReport"]),
]

# bound methods the docs name explicitly (an attribute rename must break
# CI, not the reader)
PUBLIC_ATTRS = [
    ("repro.core.runtime", "FusedModelExecutor",
     ["run", "run_batch", "launch_batch", "finish_batch"]),
    ("repro.serving.graph_engine", "GraphServeEngine",
     ["serve", "run_naive", "bucket_for", "cut_wave", "dispatch_wave",
      "begin_wave", "finish_wave", "request_cost"]),
    ("repro.serving.scheduler", "ContinuousGraphServer",
     ["submit", "submit_query", "poll", "drain", "warmup", "wait_bound",
      "lane_estimate", "group_estimate", "from_config", "backlog_bound",
      "admission_estimate", "apply_delta"]),
    ("repro.serving.minibatch", "MiniBatchServeEngine",
     ["serve_queries", "oracle_queries", "report", "apply_delta"]),
    ("repro.serving.minibatch", "MiniBatchPlanner",
     ["apply_delta", "request_for", "complete", "lookup", "sample"]),
    ("repro.data.sampling", "HostGraph", ["apply_delta", "neighbors"]),
    ("repro.data.sampling", "AdjacencyBlockProfile",
     ["from_graph", "apply_delta", "densities"]),
    ("repro.core.profiler", "BlockProfile", ["pool_rows", "pool_cols"]),
    ("repro.serving.minibatch", "FeatureStore",
     ["gather", "gather_into", "update", "add_listener"]),
    ("repro.serving.minibatch", "VertexCache",
     ["get", "put", "invalidate"]),
    ("repro.serving.graph_engine", "GraphServeEngine", ["from_config"]),
    ("repro.core.scheduler", "schedule_weighted", []),
    ("repro.core.perf_model", "CostCalibration", ["observe", "seconds"]),
]


def check_paths(errors: list) -> None:
    for doc in DOCS:
        text = (REPO / doc).read_text()
        for ref in _PATH_RE.findall(text):
            if not (REPO / ref).exists():
                errors.append(f"{doc}: `{ref}` does not exist")


def _resolve(dotted: str) -> None:
    parts = dotted.split(".")
    for split in range(len(parts), 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:split]))
        except ImportError:
            continue
        for attr in parts[split:]:
            obj = getattr(obj, attr)      # AttributeError = broken ref
        return
    raise ImportError(f"no importable prefix of {dotted}")


def check_imports(errors: list) -> None:
    for doc in DOCS:
        text = (REPO / doc).read_text()
        for ref in set(_MOD_RE.findall(text)):
            try:
                _resolve(ref)
            except (ImportError, AttributeError) as e:
                errors.append(f"{doc}: `{ref}` does not resolve ({e})")
    for mod, names in PUBLIC:
        try:
            m = importlib.import_module(mod)
        except ImportError as e:
            errors.append(f"public surface: {mod} does not import ({e})")
            continue
        for name in names:
            if not hasattr(m, name):
                errors.append(f"public surface: {mod}.{name} is gone")
    for mod, cls, attrs in PUBLIC_ATTRS:
        try:
            obj = getattr(importlib.import_module(mod), cls)
        except (ImportError, AttributeError) as e:
            errors.append(f"public surface: {mod}.{cls} is gone ({e})")
            continue
        for attr in attrs:
            if not hasattr(obj, attr):
                errors.append(f"public surface: {mod}.{cls}.{attr} is gone")


def main() -> int:
    errors: list = []
    check_paths(errors)
    check_imports(errors)
    for e in errors:
        print(f"DOC-REF ERROR: {e}", file=sys.stderr)
    if not errors:
        print(f"doc refs OK ({', '.join(DOCS)} + public surface)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
