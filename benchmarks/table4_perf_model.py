"""Paper Table IV: the primitive performance-model surface.

Sweeps (a_X, a_Y) over the unit square and reports, per region, which
primitive Algorithm 7 selects and the modeled cycles for a 512^3 product --
the decision boundaries a_min=1/2 and a_max=2/p_sys are printed explicitly.
Also times the three Pallas primitives at matched tile density on CPU
interpret (trend check only; wall-clock MFU is NOT claimable here)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.perf_model import FPGACostModel, Primitive
from repro.jax_cache import configure_compile_cache
from repro.kernels import ops

from benchmarks.common import emit, timeit

MODEL = FPGACostModel()


def run(fast: bool = True) -> None:
    m = n = d = 512
    for ax in (0.01, 0.1, 0.3, 0.5, 0.9):
        for ay in (0.01, 0.5, 1.0):
            p = MODEL.select(ax, ay)
            cyc = float(MODEL.cycles(p, m, n, d, ax, ay))
            emit(f"table4/ax={ax}/ay={ay}", cyc / MODEL.freq_hz * 1e6,
                 f"primitive={Primitive(p).name} cycles={cyc:.0f}")
    emit("table4/boundary/gemm-spdmm", 0.0, "a_min = 1/2")
    emit("table4/boundary/spdmm-spmm", 0.0,
         f"a_max = 2/p = {2.0 / MODEL.p_sys}")

    # kernel-level trend check (interpret mode)
    rng = np.random.default_rng(0)
    size = 128 if fast else 512
    x_dense = jnp.asarray(rng.normal(size=(size, size)).astype(np.float32))
    mask = rng.random((size, size)) < 0.05
    x_sparse = jnp.asarray(
        rng.normal(size=(size, size)).astype(np.float32) * mask)
    y = jnp.asarray(rng.normal(size=(size, size)).astype(np.float32))
    t_gemm = timeit(lambda: ops.gemm(x_sparse, y, tile=(32, 32, 32))
                    .block_until_ready())
    t_spdmm = timeit(lambda: ops.spdmm(x_sparse, y, tile=(32, 32), bn=32)
                     .block_until_ready())
    emit("table4/kernel/gemm@5%", t_gemm, "interpret-mode wall (trend only)")
    emit("table4/kernel/spdmm@5%", t_spdmm,
         f"skips {100 * (1 - float((jnp.abs(x_sparse) > 0).mean())):.0f}% "
         "elements at tile granularity")


if __name__ == "__main__":
    configure_compile_cache()
    run()
