"""Row-CSR SPMM primitive: row-gather sparse x dense matmul.

The paper's SPMM mode routes COO elements of the sparse operand to the bank
holding the matching dense row.  Below the block crossover density (DESIGN.md
section 13) even tile-level skipping pays for mostly-empty tiles, so this
kernel works at ROW granularity on the ELL view (``core.formats.ELLMatrix``):
for each output row the grid walks that row's ``rmax`` slots, and the slot's
column id -- delivered via scalar prefetch, exactly like the spdmm kernel's
tile columns -- selects which dense row to DMA.  Steps beyond the row's count
clamp their index map to the last valid slot (no new DMA) and ``pl.when``
masks the FLOPs, so cost tracks the actual row fill, not the capacity.

TPU tiling: a block's last two dims must be multiples of (8, 128) or equal
the array's.  ``y`` and the output therefore carry a unit middle axis
(``(k, 1, n)``), so a one-row ``(1, bn)`` block spans that axis whole, and
each lhs row's ``rmax`` values arrive as one ``(1, rmax)`` block from which
the step's value is selected by lane.  ``cols`` lives in SMEM by scalar
prefetch (1 MiB on v5e), which bounds ``m * round_up(rmax, 128)`` at
about 2^18 slots -- roughly 2000 rows at rmax <= 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _csr_spmm_kernel(cols_ref, counts_ref, clamp_ref, vals_ref, y_ref, o_ref,
                     acc_ref):
    del cols_ref, clamp_ref  # consumed by the index maps
    i, s = pl.program_id(0), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < counts_ref[i])
    def _mac():
        row = vals_ref[...].astype(jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        v = jnp.sum(jnp.where(lane == s, row, 0.0))
        acc_ref[...] += v * y_ref[...].astype(jnp.float32)

    @pl.when(s == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret", "out_dtype"))
def csr_spmm(vals: jnp.ndarray, cols: jnp.ndarray, counts: jnp.ndarray,
             y: jnp.ndarray, *, bn: int = 128, interpret: bool = False,
             out_dtype=None) -> jnp.ndarray:
    """``ell @ y`` for an ELL-view sparse lhs (``vals``/``cols`` (m, rmax),
    ``counts`` (m,) CAPPED at rmax).

    ``y`` is ``(k, n)`` with ``n % bn == 0`` (ops.csr_spmm owns padding);
    every ``cols`` entry must be a valid (clamped) row of ``y``, which
    ``formats.dense_to_ell`` guarantees.  Returns ``(m, n)``.
    """
    m, rmax = vals.shape
    n = y.shape[1]
    assert cols.shape == (m, rmax) and n % bn == 0, (vals.shape, y.shape)
    out_dtype = out_dtype or jnp.promote_types(vals.dtype, y.dtype)
    nb = n // bn
    # Clamp masked steps to the last valid slot: same index -> no extra DMA.
    clamp = jnp.maximum(counts - 1, 0)  # (m,)

    def y_index(i, j, s, cols_ref, counts_ref, clamp_ref):
        del counts_ref
        return (cols_ref[i, jnp.minimum(s, clamp_ref[i])], 0, j)

    if rmax == 0:  # zero-capacity lhs: keep one dummy (masked) slot
        vals = jnp.zeros((m, 1), vals.dtype)
        cols = jnp.zeros((m, 1), jnp.int32)
        rmax = 1

    row = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m, nb, rmax),
        in_specs=[
            pl.BlockSpec((row, 1, rmax), lambda i, j, s, *_: (i, 0, 0)),
            pl.BlockSpec((row, 1, bn), y_index),
        ],
        out_specs=pl.BlockSpec((row, 1, bn), lambda i, j, s, *_: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        _csr_spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, n), out_dtype),
        interpret=interpret,
    )(cols, counts, clamp, vals.reshape(m, 1, rmax),
      y.reshape(y.shape[0], 1, n))
    return out.reshape(m, n)
