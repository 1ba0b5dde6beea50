"""Paper Table VIII + Figs 11/12: Dynamic-over-static speedup vs weight
sparsity.  Weight matrices pruned to each density band; the dynamic
strategy's advantage must GROW with sparsity (S1/S2 cannot exploit it)."""
from __future__ import annotations

from repro import hw
from repro.jax_cache import configure_compile_cache
from repro.models import gnn

from benchmarks.common import emit, geomean

BANDS = [(1.0, "0%"), (0.6, "<50%"), (0.4, "50-70%"), (0.2, "70-90%"),
         (0.05, ">90%")]
MODELS = ("gcn", "sage", "gin", "sgc")
DATASETS = ("CI", "CO", "PU")
PAPER = {"<50%": (2.16, 1.38), "50-70%": (4.36, 1.64),
         "70-90%": (10.77, 2.11), ">90%": (15.96, 5.03)}


def run(models=MODELS, datasets=DATASETS) -> dict:
    freq = hw.ALVEO_U250.freq_hz
    out = {}
    for density, band in BANDS:
        so1, so2 = [], []
        for model in models:
            for ds in datasets:
                sim = gnn.build_sim(model, ds, weight_density=density)
                lat = {s: sim.simulate(s).total_seconds(freq)
                       for s in ("dynamic", "s1", "s2")}
                so1.append(lat["s1"] / lat["dynamic"])
                so2.append(lat["s2"] / lat["dynamic"])
        g1, g2 = geomean(so1), geomean(so2)
        ref = PAPER.get(band)
        extra = f" (paper: {ref[0]}x/{ref[1]}x)" if ref else ""
        emit(f"table8/weights@{band}", 0.0,
             f"SO-S1={g1:.2f}x SO-S2={g2:.2f}x{extra}")
        out[band] = (g1, g2)
    return out


if __name__ == "__main__":
    configure_compile_cache()
    run()
