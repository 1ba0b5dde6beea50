"""Paper Table IX: compiler/preprocessing overhead (ms) per model x graph.

Measures IR generation + Algorithm 9 partitioning + static sparsity
profiling wall time on this host (the paper's Xeon numbers are 0.002-52 ms;
the claim reproduced is that preprocessing is negligible and reusable)."""
from __future__ import annotations

import numpy as np

from repro.core import compiler
from repro.core.compiler import GNNModelSpec, GraphMeta
from repro.data import graphs
from repro.jax_cache import configure_compile_cache
from repro.models.gnn import make_model_spec

from benchmarks.common import emit

MODELS = ("gcn", "sage", "gin", "sgc")
DATASETS = ("CI", "CO", "PU", "FL", "NE", "RE")


def run() -> None:
    for model in MODELS:
        for ds in DATASETS:
            g = graphs.TABLE_VI[ds]
            spec = make_model_spec(model, g.f_in, g.hidden, g.n_classes)
            meta = GraphMeta(ds, g.n_vertices, g.n_edges, g.f_in)
            cm = compiler.compile_model(spec, meta, n_cc=7, align=16)
            emit(f"table9/{model}/{ds}", cm.compile_seconds * 1e6,
                 f"N1={cm.partition.n1} N2={cm.partition.n2} "
                 f"kernels={len(cm.graph)}")


if __name__ == "__main__":
    configure_compile_cache()
    run()
