"""Quickstart: the Dynasparse idea in 30 lines.

Multiply a sparse matrix pair three ways -- GEMM / SpDMM / SPMM -- then let
the dynamic K2P analyzer (paper Algorithm 7) pick per-block primitives, and
show the predicted-latency win over the static mappings.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.dynasparse import dynasparse_matmul
from repro.core.perf_model import FPGACostModel, Primitive
from repro.jax_cache import configure_compile_cache
from repro.kernels import ops

configure_compile_cache()
rng = np.random.default_rng(0)

# a block-structured sparse matrix (dense block + sparse band + dead zone)
x = np.zeros((256, 256), np.float32)
x[:128, :128] = rng.normal(size=(128, 128))                       # dense
x[128:, :128] = rng.normal(size=(128, 128)) * (rng.random((128, 128)) < .05)
y = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
x = jnp.asarray(x)

# 1) every primitive computes the same value
ref = np.asarray(x @ y)
for name, fn in [("GEMM", ops.gemm),
                 ("SpDMM", lambda a, b: ops.spdmm(a, b, tile=(32, 32), bn=32)),
                 ("SPMM", lambda a, b: ops.spmm(a, b, tile=(32, 32)))]:
    out = np.asarray(fn(x, y))
    print(f"{name:6s} max|err| = {np.abs(out - ref).max():.2e}")

# 2) dynamic K2P picks per-block: GEMM for the dense block, SpDMM for the
#    sparse band, SKIP for the dead zone
res = dynasparse_matmul(x, y, block=(128, 128, 128),
                        cost_model=FPGACostModel())
hist = np.bincount(np.asarray(res.codes).ravel(), minlength=4)
print("\nK2P decisions [SKIP, GEMM, SPDMM, SPMM]:", hist)

# 3) predicted cycles: dynamic vs the static strategies of prior work
m = FPGACostModel()
total = {"dynamic": 0.0, "S1 (all SpDMM)": 0.0, "S2-style GEMM": 0.0}
for i in range(2):
    for k in range(2):
        ax = float(res.dens_x[i, k])
        for j in range(1):
            ay = float(res.dens_y[k, j])
            total["dynamic"] += float(m.cycles(m.select(ax, ay),
                                               128, 128, 128, ax, ay))
            total["S1 (all SpDMM)"] += float(
                m.cycles(Primitive.SPDMM, 128, 128, 128, ax, ay))
            total["S2-style GEMM"] += float(
                m.cycles(Primitive.GEMM, 128, 128, 128, ax, ay))
print("\npredicted cycles:")
for k, v in total.items():
    print(f"  {k:16s} {v:10.0f}  ({v / total['dynamic']:.2f}x)")
