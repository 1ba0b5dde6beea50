"""Data formats & transformations (paper Section V-A / V-B2).

The paper stores matrices in dense or COO format and converts between them
with a log-depth prefix-sum compaction network (D2S) / its inverse (S2D).
On TPU the same prefix-sum algorithm vectorizes to ``cumsum`` + scatter; all
converters here are jit-compatible with *static* capacity (``max_nnz``) and a
runtime validity count -- the standard padded-sparse idiom on accelerators.

Block-level formats: the TPU adaptation skips zero *tiles*, so we also keep a
BlockCOO/BlockCSR view: per-(row-panel) sorted nonzero tile-column indices
plus the dense tile payload, which is what the spdmm/spmm Pallas kernels
consume via scalar prefetch.

Row-level formats (DESIGN.md section 13): :class:`CSRMatrix` is the flat
padded ``indptr``/``indices``/``values`` storage format and
:class:`ELLMatrix` its fixed-slots-per-row execution view, which is what the
row-gather SPMM paths (``kernels.csr_spmm`` and :func:`ell_matmul`) consume
-- Pallas grids and XLA gathers both need a static per-row slot capacity.
:func:`dense_to_csr` is the reference converter (one global prefix sum, the
paper's D2S verbatim); :func:`dense_to_ell` is the in-program converter the
format-aware executor traces -- a hierarchical compaction (per-subtile
counts, a short log-depth prefix over subtiles, then rank selection inside
one gathered subtile per slot) that avoids full-length scans, which the CPU
backend lowers catastrophically.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class COOMatrix:
    """Padded COO: entries [0, nnz) are valid; the rest are (0, 0, 0.0).

    Rows/cols are int32; row-major sorted (row, then col) as the paper
    requires for SpDMM/SPMM operands.
    """

    rows: jnp.ndarray      # (capacity,) int32
    cols: jnp.ndarray      # (capacity,) int32
    values: jnp.ndarray    # (capacity,) dtype
    nnz: jnp.ndarray       # () int32
    shape: Tuple[int, int]

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    def density(self) -> jnp.ndarray:
        return self.nnz / (self.shape[0] * self.shape[1])


jax.tree_util.register_pytree_node(
    COOMatrix,
    lambda m: ((m.rows, m.cols, m.values, m.nnz), m.shape),
    lambda shape, leaves: COOMatrix(*leaves, shape=shape),
)


@dataclasses.dataclass
class BlockCSRMatrix:
    """Tile-level CSR over a (Mb x Kb) tile grid.

    ``col_idx[i, s]`` is the tile-column of the s-th nonzero tile in tile-row
    i (sorted ascending; entries >= counts[i] are padding = 0).
    ``blocks[i, s]`` is the dense (T_m, T_k) payload of that tile.
    """

    col_idx: jnp.ndarray   # (Mb, Smax) int32
    counts: jnp.ndarray    # (Mb,) int32  -- nnz tiles per tile-row
    blocks: jnp.ndarray    # (Mb, Smax, T_m, T_k)
    shape: Tuple[int, int]
    tile: Tuple[int, int]

    @property
    def grid(self) -> Tuple[int, int]:
        return (-(-self.shape[0] // self.tile[0]), -(-self.shape[1] // self.tile[1]))

    def tile_density(self) -> jnp.ndarray:
        mb, kb = self.grid
        return jnp.sum(self.counts) / (mb * kb)


jax.tree_util.register_pytree_node(
    BlockCSRMatrix,
    lambda m: ((m.col_idx, m.counts, m.blocks), (m.shape, m.tile)),
    lambda aux, leaves: BlockCSRMatrix(*leaves, shape=aux[0], tile=aux[1]),
)


# --------------------------------------------------------------------------
# Dense <-> COO (the D2S / S2D modules).
# --------------------------------------------------------------------------

def dense_to_coo(x: jnp.ndarray, capacity: Optional[int] = None) -> COOMatrix:
    """D2S: prefix-sum compaction of nonzeros into padded COO (row-major).

    Mirrors the paper's D2S module: the shift amount of each element is the
    number of zeros before it, i.e. position = prefix-sum of the nonzero
    indicator.  We express the log(n)-stage shift network as one cumsum +
    scatter, which is its SIMD equivalent.
    """
    m, n = x.shape
    capacity = int(capacity if capacity is not None else m * n)
    flat = x.reshape(-1)
    mask = flat != 0
    nnz = jnp.sum(mask).astype(jnp.int32)
    # prefix-sum compaction: destination slot of element i (clamped into pad)
    dest = jnp.where(mask, jnp.cumsum(mask) - 1, capacity)
    dest = jnp.minimum(dest, capacity)  # out-of-capacity nonzeros drop into pad
    lin = jnp.arange(m * n, dtype=jnp.int32)
    rows_src = lin // n
    cols_src = lin % n
    rows = jnp.zeros((capacity + 1,), jnp.int32).at[dest].set(rows_src.astype(jnp.int32))
    cols = jnp.zeros((capacity + 1,), jnp.int32).at[dest].set(cols_src.astype(jnp.int32))
    vals = jnp.zeros((capacity + 1,), x.dtype).at[dest].set(flat)
    return COOMatrix(rows[:capacity], cols[:capacity], vals[:capacity],
                     jnp.minimum(nnz, capacity), (m, n))


def coo_to_dense(coo: COOMatrix) -> jnp.ndarray:
    """S2D: scatter valid COO entries back into a dense matrix."""
    m, n = coo.shape
    valid = jnp.arange(coo.capacity) < coo.nnz
    vals = jnp.where(valid, coo.values, 0)
    # invalid entries all scatter-add 0 to (0, 0): harmless.
    rows = jnp.where(valid, coo.rows, 0)
    cols = jnp.where(valid, coo.cols, 0)
    out = jnp.zeros((m, n), coo.values.dtype)
    return out.at[rows, cols].add(vals)


# --------------------------------------------------------------------------
# Dense <-> BlockCSR (tile-level, for the TPU kernels).
# --------------------------------------------------------------------------

def pad_to_tiles(x: jnp.ndarray, tile: Tuple[int, int]) -> jnp.ndarray:
    """Zero-pad (M, N) up to multiples of ``tile``."""
    m, n = x.shape
    tm, tn = tile
    pm, pn = (-m) % tm, (-n) % tn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def tile_view(x: jnp.ndarray, tile: Tuple[int, int]) -> jnp.ndarray:
    """(M, N) -> (Mb, Nb, tm, tn) tile tensor (pads to tile multiples)."""
    x = pad_to_tiles(x, tile)
    m, n = x.shape
    tm, tn = tile
    return x.reshape(m // tm, tm, n // tn, tn).transpose(0, 2, 1, 3)


def untile_view(tiles: jnp.ndarray, shape: Tuple[int, int]) -> jnp.ndarray:
    mb, nb, tm, tn = tiles.shape
    full = tiles.transpose(0, 2, 1, 3).reshape(mb * tm, nb * tn)
    return full[: shape[0], : shape[1]]


def dense_to_bcsr(x: jnp.ndarray, tile: Tuple[int, int],
                  smax: Optional[int] = None) -> BlockCSRMatrix:
    """Compact nonzero tiles of each tile-row (prefix-sum compaction again)."""
    tiles = tile_view(x, tile)                      # (Mb, Kb, tm, tk)
    mb, kb = tiles.shape[:2]
    smax = int(smax if smax is not None else kb)
    nz = jnp.any(tiles != 0, axis=(2, 3))           # (Mb, Kb) tile occupancy
    counts = jnp.sum(nz, axis=1).astype(jnp.int32)
    dest = jnp.where(nz, jnp.cumsum(nz, axis=1) - 1, smax)
    dest = jnp.minimum(dest, smax)
    row_ids = jnp.broadcast_to(jnp.arange(mb)[:, None], (mb, kb))
    col_ids = jnp.broadcast_to(jnp.arange(kb)[None, :], (mb, kb))
    col_idx = (
        jnp.zeros((mb, smax + 1), jnp.int32)
        .at[row_ids, dest].set(col_ids.astype(jnp.int32))[:, :smax]
    )
    blocks = (
        jnp.zeros((mb, smax + 1) + tiles.shape[2:], x.dtype)
        .at[row_ids, dest].set(tiles)[:, :smax]
    )
    return BlockCSRMatrix(col_idx, jnp.minimum(counts, smax), blocks,
                          shape=x.shape, tile=tile)


@dataclasses.dataclass
class BlockCSCMatrix:
    """Tile-level CSC over a (Kb x Nb) tile grid (for SPMM's right operand).

    ``row_idx[j, s]`` is the tile-row of the s-th nonzero tile in tile-column
    j; ``blocks[j, s]`` its (T_k, T_n) payload (NOT transposed).
    """

    row_idx: jnp.ndarray   # (Nb, Smax) int32
    counts: jnp.ndarray    # (Nb,) int32
    blocks: jnp.ndarray    # (Nb, Smax, T_k, T_n)
    shape: Tuple[int, int]
    tile: Tuple[int, int]

    @property
    def grid(self) -> Tuple[int, int]:
        return (-(-self.shape[0] // self.tile[0]), -(-self.shape[1] // self.tile[1]))


jax.tree_util.register_pytree_node(
    BlockCSCMatrix,
    lambda m: ((m.row_idx, m.counts, m.blocks), (m.shape, m.tile)),
    lambda aux, leaves: BlockCSCMatrix(*leaves, shape=aux[0], tile=aux[1]),
)


def dense_to_bcsc(x: jnp.ndarray, tile: Tuple[int, int],
                  smax: Optional[int] = None) -> BlockCSCMatrix:
    """Compact nonzero tiles of each tile-COLUMN (transposed grid walk;
    tile payloads stay untransposed so the MXU contraction is direct)."""
    tiles = tile_view(x, tile)                      # (Kb, Nb, tk, tn)
    kb, nb = tiles.shape[:2]
    smax = int(smax if smax is not None else kb)
    nz = jnp.any(tiles != 0, axis=(2, 3))           # (Kb, Nb)
    counts = jnp.sum(nz, axis=0).astype(jnp.int32)  # per column
    dest = jnp.where(nz, jnp.cumsum(nz, axis=0) - 1, smax)
    dest = jnp.minimum(dest, smax)
    row_ids = jnp.broadcast_to(jnp.arange(kb)[:, None], (kb, nb))
    col_ids = jnp.broadcast_to(jnp.arange(nb)[None, :], (kb, nb))
    row_idx = (
        jnp.zeros((nb, smax + 1), jnp.int32)
        .at[col_ids, dest].set(row_ids.astype(jnp.int32))[:, :smax]
    )
    blocks = (
        jnp.zeros((nb, smax + 1) + tiles.shape[2:], x.dtype)
        .at[col_ids, dest].set(tiles)[:, :smax]
    )
    pad_shape = (kb * tile[0], nb * tile[1])
    return BlockCSCMatrix(row_idx, jnp.minimum(counts, smax), blocks,
                          shape=pad_shape, tile=tile)


def bcsr_to_dense(b: BlockCSRMatrix) -> jnp.ndarray:
    mb, kb = b.grid
    smax = b.col_idx.shape[1]
    tiles = jnp.zeros((mb, kb) + b.blocks.shape[2:], b.blocks.dtype)
    valid = jnp.arange(smax)[None, :] < b.counts[:, None]
    cols = jnp.where(valid, b.col_idx, kb)  # invalid -> scratch col kb
    row_ids = jnp.broadcast_to(jnp.arange(mb)[:, None], (mb, smax))
    tiles = jnp.concatenate([tiles, jnp.zeros((mb, 1) + tiles.shape[2:], tiles.dtype)], 1)
    vals = jnp.where(valid[..., None, None], b.blocks, 0)
    tiles = tiles.at[row_ids, cols].add(vals)[:, :kb]
    return untile_view(tiles, b.shape)


# --------------------------------------------------------------------------
# Row-level CSR (padded indptr/indices/values, static capacity).
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CSRMatrix:
    """Padded flat CSR with STATIC capacity.

    ``indptr`` is monotone with ``indptr[-1] == nnz`` (clamped to capacity);
    entries ``[indptr[r], indptr[r+1])`` of ``indices``/``values`` are row
    r's column ids (ascending) and values.  Slots ``>= nnz`` are (0, 0.0)
    padding, exactly like :class:`COOMatrix`.
    """

    indptr: jnp.ndarray    # (m + 1,) int32
    indices: jnp.ndarray   # (capacity,) int32
    values: jnp.ndarray    # (capacity,)
    shape: Tuple[int, int]

    @property
    def capacity(self) -> int:
        return self.indices.shape[0]

    @property
    def nnz(self) -> jnp.ndarray:
        return self.indptr[-1]

    def density(self) -> jnp.ndarray:
        return self.nnz / (self.shape[0] * self.shape[1])


jax.tree_util.register_pytree_node(
    CSRMatrix,
    lambda m: ((m.indptr, m.indices, m.values), m.shape),
    lambda shape, leaves: CSRMatrix(*leaves, shape=shape),
)


def dense_to_csr(x: jnp.ndarray, capacity: Optional[int] = None) -> CSRMatrix:
    """D2S into flat CSR: one global prefix-sum compaction (reference path).

    Same compaction network as :func:`dense_to_coo`, but the row ids are
    folded into ``indptr`` (cumulative per-row counts, clamped to capacity --
    the clamp is consistent with which entries drop into the pad, because
    row-major compaction drops exactly the trailing ones).
    """
    m, n = x.shape
    capacity = int(capacity if capacity is not None else m * n)
    flat = x.reshape(-1)
    mask = flat != 0
    dest = jnp.where(mask, jnp.cumsum(mask) - 1, capacity)
    dest = jnp.minimum(dest, capacity)
    cols_src = (jnp.arange(m * n, dtype=jnp.int32) % n).astype(jnp.int32)
    cols = jnp.zeros((capacity + 1,), jnp.int32).at[dest].set(cols_src)
    vals = jnp.zeros((capacity + 1,), x.dtype).at[dest].set(flat)
    row_counts = jnp.sum(x != 0, axis=1)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.minimum(jnp.cumsum(row_counts), capacity).astype(jnp.int32)])
    return CSRMatrix(indptr, cols[:capacity], vals[:capacity], (m, n))


def _csr_rows(c: CSRMatrix) -> jnp.ndarray:
    """Row id of each storage slot (searchsorted over the row boundaries)."""
    e = jnp.arange(c.capacity)
    return jnp.searchsorted(c.indptr[1:], e, side="right").astype(jnp.int32)


def csr_to_dense(c: CSRMatrix) -> jnp.ndarray:
    m, n = c.shape
    valid = jnp.arange(c.capacity) < c.nnz
    rows = jnp.where(valid, jnp.minimum(_csr_rows(c), m - 1), 0)
    cols = jnp.where(valid, c.indices, 0)
    vals = jnp.where(valid, c.values, 0)
    return jnp.zeros((m, n), c.values.dtype).at[rows, cols].add(vals)


def coo_to_csr(coo: COOMatrix) -> CSRMatrix:
    """Fold row-major COO row ids into ``indptr`` (no re-sort needed)."""
    m, _ = coo.shape
    valid = jnp.arange(coo.capacity) < coo.nnz
    bound = jnp.arange(m + 1)
    indptr = jnp.sum(valid[None, :] & (coo.rows[None, :] < bound[:, None]),
                     axis=1).astype(jnp.int32)
    return CSRMatrix(indptr,
                     jnp.where(valid, coo.cols, 0),
                     jnp.where(valid, coo.values, 0), coo.shape)


def csr_to_coo(c: CSRMatrix) -> COOMatrix:
    m, _ = c.shape
    valid = jnp.arange(c.capacity) < c.nnz
    rows = jnp.where(valid, jnp.minimum(_csr_rows(c), m - 1), 0)
    return COOMatrix(rows.astype(jnp.int32),
                     jnp.where(valid, c.indices, 0),
                     jnp.where(valid, c.values, 0),
                     c.nnz.astype(jnp.int32), c.shape)


# --------------------------------------------------------------------------
# ELL: the fixed-slots-per-row execution view of row-CSR.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ELLMatrix:
    """Padded row-CSR execution view: ``rmax`` slots per row.

    ``values[i, s]`` / ``cols[i, s]`` are row i's s-th nonzero (slots beyond
    the row's count hold value 0 and a clamped in-range column, so gathers
    through them are safe and contribute nothing).  ``row_counts`` keeps the
    TRUE (uncapped) per-row nonzero counts, so ``max(row_counts) <= rmax``
    is an exact lossless-fit predicate.
    """

    values: jnp.ndarray      # (m, rmax)
    cols: jnp.ndarray        # (m, rmax) int32
    row_counts: jnp.ndarray  # (m,) int32 -- TRUE counts, may exceed rmax
    shape: Tuple[int, int]

    @property
    def rmax(self) -> int:
        return self.values.shape[1]


jax.tree_util.register_pytree_node(
    ELLMatrix,
    lambda m: ((m.values, m.cols, m.row_counts), m.shape),
    lambda shape, leaves: ELLMatrix(*leaves, shape=shape),
)


def _hillis(a: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum over the last axis (log-depth shift network --
    the paper's D2S compaction network verbatim, and much faster than the
    CPU backend's ``cumsum`` lowering on short axes)."""
    n = a.shape[-1]
    d = 1
    while d < n:
        a = a + jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(d, 0)])[..., :-d]
        d *= 2
    return a


def dense_to_ell(x: jnp.ndarray, rmax: int, sub: int = 16) -> ELLMatrix:
    """Hierarchical D2S into ELL: the in-program converter.

    Per row: count nonzeros per ``sub``-wide subtile, prefix-sum over the
    (short) subtile axis, then for each of the ``rmax`` slots locate the
    subtile holding that rank and resolve the exact column with one more
    prefix inside a single gathered subtile.  Everything is O(m * rmax * sub)
    gather/compare work with only log-depth prefixes -- no full-width scan.
    """
    m, k = x.shape
    pad = (-k) % sub
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    k2 = xp.shape[1]
    S = k2 // sub
    msub = (xp != 0).reshape(m, S, sub)
    sub_cnt = jnp.sum(msub, axis=2, dtype=jnp.int32)             # (m, S)
    sub_inc = _hillis(sub_cnt)                                   # inclusive
    counts = sub_inc[:, -1]
    sub_exc = sub_inc - sub_cnt                                  # exclusive
    targets = jnp.arange(1, rmax + 1, dtype=jnp.int32)
    # subtile that holds the t-th nonzero of each row
    j = jnp.sum(sub_inc[:, None, :] < targets[None, :, None],
                axis=2, dtype=jnp.int32)                         # (m, rmax)
    j = jnp.minimum(j, S - 1)
    base = jnp.take_along_axis(sub_exc, j, axis=1)
    rank = targets[None, :] - base                               # 1-indexed
    flat = jnp.arange(m, dtype=jnp.int32)[:, None] * S + j
    g = jnp.take(msub.reshape(m * S, sub).astype(jnp.int32), flat, axis=0)
    gp = _hillis(g)                                              # (m, rmax, sub)
    off = jnp.sum(gp < rank[:, :, None], axis=2, dtype=jnp.int32)
    cols = jnp.minimum(j * sub + off, k - 1)
    vals = jnp.take_along_axis(xp, cols, axis=1)
    valid = jnp.arange(rmax, dtype=jnp.int32)[None, :] < counts[:, None]
    return ELLMatrix(jnp.where(valid, vals, 0), cols.astype(jnp.int32),
                     counts, (m, k))


def csr_to_ell(c: CSRMatrix, rmax: int) -> ELLMatrix:
    """Flat CSR -> ELL: scatter each slot to (row, slot - indptr[row])."""
    m, _ = c.shape
    e = jnp.arange(c.capacity)
    rows = _csr_rows(c)
    pos = e - c.indptr[jnp.minimum(rows, m - 1)]
    valid = (e < c.nnz) & (pos < rmax)
    r = jnp.where(valid, jnp.minimum(rows, m - 1), 0)
    p = jnp.where(valid, pos, rmax)
    cols = jnp.zeros((m, rmax + 1), jnp.int32).at[r, p].set(c.indices)[:, :rmax]
    vals = jnp.zeros((m, rmax + 1), c.values.dtype).at[r, p].set(c.values)[:, :rmax]
    row_counts = (c.indptr[1:] - c.indptr[:-1]).astype(jnp.int32)
    return ELLMatrix(vals, cols, row_counts, c.shape)


def ell_to_dense(ell: ELLMatrix) -> jnp.ndarray:
    """S2D (lossless only when every row fits: max(row_counts) <= rmax)."""
    m, k = ell.shape
    rmax = ell.rmax
    valid = (jnp.arange(rmax)[None, :]
             < jnp.minimum(ell.row_counts, rmax)[:, None])
    rows = jnp.broadcast_to(jnp.arange(m)[:, None], (m, rmax))
    return (jnp.zeros((m, k), ell.values.dtype)
            .at[rows, ell.cols].add(jnp.where(valid, ell.values, 0)))


def ell_matmul(ell: ELLMatrix, y: jnp.ndarray) -> jnp.ndarray:
    """Row-gather SPMM (XLA path): out[i] = sum_s vals[i,s] * y[cols[i,s]].

    Invalid slots carry value 0 and an in-range column, so no masking is
    needed.  Accumulates in f32 like the block primitives.
    """
    g = jnp.take(y, ell.cols, axis=0).astype(jnp.float32)        # (m, rmax, n)
    return jnp.sum(ell.values.astype(jnp.float32)[:, :, None] * g, axis=1)
