"""Paper Table VII: latency of S1 / S2 / Dynamic on unpruned GNNs.

All 4 models x all 6 Table VI graphs through the cost-model simulator at
FPGA constants (p_sys=16, 250 MHz, 7 CCs) with synthetic block statistics
matched to Table VI densities.  Reports per-cell latencies + SO-S1/SO-S2
speedups and the geomean (paper: 2.13x and 1.59x).
"""
from __future__ import annotations

from repro import hw
from repro.jax_cache import configure_compile_cache
from repro.models import gnn

from benchmarks.common import emit, geomean

MODELS = ("gcn", "sage", "gin", "sgc")
DATASETS = ("CI", "CO", "PU", "FL", "NE", "RE")


def run(models=MODELS, datasets=DATASETS) -> dict:
    so1, so2 = [], []
    freq = hw.ALVEO_U250.freq_hz
    for model in models:
        for ds in datasets:
            sim = gnn.build_sim(model, ds)
            lat = {s: sim.simulate(s).total_seconds(freq)
                   for s in ("dynamic", "s1", "s2")}
            so1.append(lat["s1"] / lat["dynamic"])
            so2.append(lat["s2"] / lat["dynamic"])
            emit(f"table7/{model}/{ds}/dynamic", lat["dynamic"] * 1e6,
                 f"SO-S1={so1[-1]:.2f}x SO-S2={so2[-1]:.2f}x")
    g1, g2 = geomean(so1), geomean(so2)
    emit("table7/geomean/SO-S1", 0.0, f"{g1:.2f}x (paper: 2.13x)")
    emit("table7/geomean/SO-S2", 0.0, f"{g2:.2f}x (paper: 1.59x)")
    return {"SO-S1": g1, "SO-S2": g2}


if __name__ == "__main__":
    configure_compile_cache()
    run()
