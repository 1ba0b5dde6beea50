"""Sparsity Profiler kernel (paper Section V-B2).

The FPGA puts a comparator array + adder tree at the Result Buffer's output
port so density is counted during writeback for free.  The Pallas analogue:
a tiny grid kernel that writes each tile's nonzero count into an SMEM-resident
(Mb, Nb) output (a (1, 1) VMEM block breaks the TPU's (8, 128) block tiling) --
fusable onto the producing kernel's epilogue on real hardware, and
cheap enough to be "free" relative to the matmuls it profiles.  The counts
feed the runtime Analyzer's K2P decisions (Algorithm 7).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _profile_kernel(x_ref, o_ref):
    nnz = jnp.sum((x_ref[...] != 0).astype(jnp.int32))
    o_ref[pl.program_id(0), pl.program_id(1)] = nnz


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def tile_nnz(x: jnp.ndarray, *, tile: Tuple[int, int] = (128, 128),
             interpret: bool = False) -> jnp.ndarray:
    """Per-tile nonzero counts: (M, N) -> (Mb, Nb) int32.

    Shapes must be tile multiples (ops wrapper pads with zeros, which do not
    perturb the counts)."""
    m, n = x.shape
    tm, tn = tile
    assert m % tm == 0 and n % tn == 0, (x.shape, tile)
    mb, nb = m // tm, n // tn
    return pl.pallas_call(
        _profile_kernel,
        grid=(mb, nb),
        in_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((mb, nb), jnp.int32),
        interpret=interpret,
    )(x)
