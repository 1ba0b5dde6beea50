"""Fused-mode dynasparse matmul: value preservation + dispatch codes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.dynasparse import (dynasparse_dense_equivalent,
                                   dynasparse_matmul)
from repro.core.perf_model import FPGACostModel, Primitive, TPUCostModel

RNG = np.random.default_rng(3)


def sparse(m, n, density):
    x = RNG.normal(size=(m, n)).astype(np.float32)
    return jnp.asarray(x * (RNG.random((m, n)) < density))


@pytest.mark.parametrize("cost_model", [FPGACostModel(), TPUCostModel()])
@pytest.mark.parametrize("dens", [0.0, 0.05, 0.6])
def test_value_equals_dense(cost_model, dens):
    x, y = sparse(96, 128, dens), sparse(128, 64, 0.8)
    r = dynasparse_matmul(x, y, block=(32, 32, 32), cost_model=cost_model)
    np.testing.assert_allclose(
        np.asarray(r.out), np.asarray(dynasparse_dense_equivalent(x, y)),
        atol=2e-4, rtol=2e-4)


def test_codes_follow_block_density():
    x = jnp.zeros((64, 64), jnp.float32)
    x = x.at[:32, :32].set(1.0)                  # dense block
    x = x.at[32:, :32].set(
        jnp.asarray((RNG.random((32, 32)) < 0.05).astype(np.float32)))
    y = jnp.ones((64, 32), jnp.float32)
    r = dynasparse_matmul(x, y, block=(32, 32, 32),
                          cost_model=FPGACostModel())
    codes = np.asarray(r.codes)                  # (I=2, J=1, K=2)
    assert codes[0, 0, 0] == Primitive.GEMM      # dense x dense
    assert codes[0, 0, 1] == Primitive.SKIP      # zero block skipped
    assert codes[1, 0, 0] == Primitive.SPDMM     # sparse x dense
    assert codes[1, 0, 1] == Primitive.SKIP


def test_use_kernels_branches():
    x, y = sparse(32, 32, 0.1), sparse(32, 32, 0.9)
    r = dynasparse_matmul(x, y, block=(16, 16, 16),
                          cost_model=FPGACostModel(), use_kernels=True,
                          tile=(8, 8))
    np.testing.assert_allclose(
        np.asarray(r.out), np.asarray(dynasparse_dense_equivalent(x, y)),
        atol=1e-3, rtol=1e-3)


def test_jit_composability():
    @jax.jit
    def f(x, y):
        return dynasparse_matmul(x, y, block=(32, 32, 32),
                                 cost_model=TPUCostModel()).out

    x, y = sparse(64, 64, 0.2), sparse(64, 64, 0.7)
    np.testing.assert_allclose(
        np.asarray(f(x, y)),
        np.asarray(dynasparse_dense_equivalent(x, y)), atol=2e-4, rtol=2e-4)


def masked_block_product(x, y, codes, block):
    """NumPy reference: sum of the block products whose code is not SKIP."""
    bm, bk, bn = block
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    out = np.zeros((x.shape[0], y.shape[1]))
    for i, j, k in zip(*np.nonzero(np.asarray(codes) != Primitive.SKIP)):
        out[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn] += (
            x[i * bm:(i + 1) * bm, k * bk:(k + 1) * bk]
            @ y[k * bk:(k + 1) * bk, j * bn:(j + 1) * bn])
    return out


@pytest.mark.parametrize("use_kernels", [False, True])
def test_verbatim_skip_codes_drop_nonzero_blocks(use_kernels):
    """SKIP placed on nonzero blocks drops exactly those products, and a
    non-finite lhs block under SKIP for every output column stays out."""
    block = (16, 16, 16)
    rng = np.random.default_rng(11)
    x = rng.normal(size=(64, 48)).astype(np.float32)  # every block nonzero
    y = jnp.asarray(rng.normal(size=(48, 32)).astype(np.float32))
    codes = rng.integers(0, 4, size=(4, 2, 3)).astype(np.int32)
    codes[0, :, 1] = Primitive.SKIP
    codes[3, 1, :] = Primitive.SKIP
    x[:16, 16:32] = np.nan                           # block (0, 1)
    r = dynasparse_matmul(jnp.asarray(x), y, codes=jnp.asarray(codes),
                          block=block, use_kernels=use_kernels, tile=(8, 8))
    want = masked_block_product(x, y, codes, block)
    np.testing.assert_array_equal(np.asarray(r.codes), codes)
    np.testing.assert_allclose(np.asarray(r.out), want, atol=2e-4,
                               rtol=2e-4)
    assert np.all(np.asarray(r.out)[48:, 16:] == 0)


@pytest.mark.parametrize("use_kernels, loops", [(False, False),
                                                (True, True)])
def test_task_loop_only_on_kernel_path(use_kernels, loops):
    """The XLA path is one contraction with no loop; the Pallas path keeps
    its scan over tasks."""
    x, y = sparse(64, 48, 0.3), sparse(48, 32, 0.6)
    text = dynasparse_matmul.lower(x, y, block=(16, 16, 16),
                                   use_kernels=use_kernels,
                                   tile=(8, 8)).as_text()
    assert ("while" in text) == loops
