"""Paper Table X: accelerator-latency comparison on GCN (modeled).

Our Dynamic latency (cost-model simulation at the paper's FPGA constants)
vs the PUBLISHED BoostGCN / HyGCN numbers (their rows are cited from the
paper -- those accelerators cannot be re-run here).  The reproduced claim
is the RATIO structure: Dynasparse beats both despite lower peak TFLOPS."""
from __future__ import annotations

from repro import hw
from repro.jax_cache import configure_compile_cache
from repro.models import gnn

from benchmarks.common import emit, geomean

# published latencies (ms), Table X
BOOSTGCN = {"CI": 1.9e-2, "CO": 2.5e-2, "PU": 1.6e-1, "FL": 4.0e1,
            "RE": 1.9e2}
HYGCN = {"CI": 2.1e-2, "CO": 3e-1, "PU": 6.4e1, "RE": 2.9e2}
PAPER_DYNASPARSE = {"CI": 7.7e-3, "CO": 4.7e-3, "PU": 6.3e-2, "FL": 8.8e0,
                    "NE": 2.9e0, "RE": 1.0e2}


def run() -> None:
    ours = {}
    for ds in ("CI", "CO", "PU", "FL", "NE", "RE"):
        sim = gnn.build_sim("gcn", ds)
        ours[ds] = sim.simulate("dynamic").total_seconds(
            hw.ALVEO_U250.freq_hz) * 1e3
        paper = PAPER_DYNASPARSE[ds]
        emit(f"table10/gcn/{ds}/ours-modeled", ours[ds] * 1e3,
             f"paper-dynasparse={paper}ms ratio={ours[ds]/paper:.2f}")
    sp_boost = [BOOSTGCN[d] / ours[d] for d in BOOSTGCN]
    sp_hygcn = [HYGCN[d] / ours[d] for d in HYGCN]
    emit("table10/speedup-vs-BoostGCN", 0.0,
         f"{geomean(sp_boost):.1f}x geomean (paper: 2.7x)")
    emit("table10/speedup-vs-HyGCN", 0.0,
         f"{geomean(sp_hygcn):.1f}x geomean (paper: 171x)")


if __name__ == "__main__":
    configure_compile_cache()
    run()
