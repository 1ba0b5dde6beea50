"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, SMEM or VMEM overflow, programs that do not fit the
device.  These tests lower and compile for one chip of a described
``v5e:2x2`` topology -- the fused gcn program on Cora and the published
GAT on a PPI test graph at full size, one serving wave program, and every
Pallas kernel with ``interpret=False`` -- and check that each kernel lands
in the program as a ``tpu_custom_call``.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture (never at import), so
only the worker that runs these tests loads the TPU library.  The
persistent compilation cache is off around the compiles: an entry written
for a described chip cannot be read back without one.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compiler, profiler, runtime
from repro.core.compiler import GraphMeta
from repro.core.dynasparse import dynasparse_matmul
from repro.core.ir import Activation
from repro.data import graphs as graph_data
from repro.kernels import ops
from repro.models import gnn


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _counts(sharding, shape, block):
    c = jax.eval_shape(lambda x: profiler.block_counts(x, block),
                       jax.ShapeDtypeStruct(tuple(shape), jnp.float32))
    return _shape(sharding, c.shape, c.dtype)


def _gcn(n_vertices, f_in, hidden, n_classes):
    spec = gnn.make_model_spec("gcn", f_in, hidden, n_classes)
    meta = GraphMeta("compile", n_vertices, n_vertices * 8, f_in)
    cm = compiler.compile_model(spec, meta, n_cc=7, align=16,
                                on_chip_bytes=256 * 1024)
    weights = gnn.init_spec_weights(spec)
    return cm, {name: w.shape for name, w in weights.items()}


def test_fused_gcn_cora_full_size_compiles(one_chip):
    """The whole-model program of chip_smoke's first phase: gcn on Cora at
    2708 vertices and widths 1433 -> 16 -> 7."""
    g = graph_data.TABLE_VI["CO"]
    cm, weights = _gcn(g.n_vertices, g.f_in, g.hidden, g.n_classes)
    n = g.n_vertices
    shapes = {"A": (n, n), "A_mean": (n, n), "H0": (n, g.f_in), **weights}
    tensors = {k: _shape(one_chip, s) for k, s in shapes.items()}
    ex = runtime.FusedModelExecutor(strategy="dynamic")
    fn, needed = ex._program(cm, tensors)
    counts = tuple(_counts(one_chip, shapes[name], blk)
                   for name, blk in needed)
    compiled = fn.lower(tensors, counts).compile()
    mem = compiled.memory_analysis()
    # the program holds its inputs plus temporaries well inside 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 ** 30
    # the block contraction's masked lhs is J copies of its operand where
    # XLA materializes it; at Cora's grids that stays a small multiple of A
    assert mem.temp_size_in_bytes < 256 * 2 ** 20


def test_fused_gat_ppi_full_size_compiles(one_chip):
    """Published GAT on one PPI test graph (2,762 vertices; widths 50 ->
    4 x 256 -> 4 x 256 + skip -> 6 x 121 averaged), the program of the
    benchmark's ``gat-ppi.whole`` cell, at the serving defaults."""
    spec = compiler.GNNModelSpec(
        "gat", [50, 1024, 1024, 121], activation=Activation.ELU,
        gat_heads=(4, 4, 6), gat_skip=(2,))
    n = 2762
    cm = compiler.compile_model(spec, GraphMeta("ppi", n, 39711, 50),
                                n_cc=7, align=16, on_chip_bytes=256 * 1024)
    weights = {k: w.shape for k, w in gnn.init_spec_weights(spec).items()}
    shapes = {"A": (n, n), "A_mean": (n, n), "H0": (n, 50), **weights}
    tensors = {k: _shape(one_chip, s) for k, s in shapes.items()}
    ex = runtime.FusedModelExecutor(strategy="dynamic")
    fn, needed = ex._program(cm, tensors)
    counts = tuple(_counts(one_chip, shapes[name], blk)
                   for name, blk in needed)
    compiled = fn.lower(tensors, counts).compile()
    mem = compiled.memory_analysis()
    # the 14 heads' dense (n, n) attention and masked aggregation
    # operands stay a few hundred MB of the 16 GB
    assert mem.temp_size_in_bytes < 512 * 2 ** 20
    text = compiled.as_text()
    for scope in ("attention", "head_combine", "aggregate", "update"):
        assert f"/{scope}/" in text, scope


def test_serving_wave_program_compiles(one_chip):
    """One wave of four 256-vertex requests at Cora's widths, the bucket
    chip_smoke's serving phase reaches with its largest requests."""
    bucket, slots = 256, 4
    cm, weights = _gcn(bucket, 1433, 16, 7)
    ex = runtime.FusedModelExecutor(strategy="dynamic",
                                    collect_report=False)
    flows = ex._resolved_flows(cm)
    needed = ex._needed_inputs(flows)
    shared_needed = tuple((n, b) for n, b in needed if n in weights)
    request_needed = tuple((n, b) for n, b in needed if n not in weights)
    per_request = {"A": (bucket, bucket), "H0": (bucket, 1433)}
    shared = {k: _shape(one_chip, s) for k, s in weights.items()}
    batched = {name: _shape(one_chip, (slots,) + per_request[name])
               for name in {n for n, _ in request_needed}}
    shared_counts = tuple(_counts(one_chip, weights[name], blk)
                          for name, blk in shared_needed)
    fn = ex._build_batch(cm, shared_needed, request_needed)
    fn.lower(shared, shared_counts, batched).compile()


def _matmuls(text):
    """The compiled program's matmuls (the TPU's convolutions), operand
    names and memory-space marks left out."""
    return sorted(
        re.sub(r"%[\w.\-]+|S\(1\)", "", line.split(", metadata=")[0]).strip()
        for line in text.splitlines() if " convolution(" in line)


@pytest.mark.parametrize("n, f_in, f_out", [(256, 256, 16), (256, 1433, 16),
                                            (128, 500, 128)])
def test_block_contraction_compiles_alike_in_a_wave(one_chip, n, f_in, f_out):
    """An Update then an Aggregate, alone and inside a scan over four
    stacked requests (as a serving wave runs them), compile to the same
    matmuls, which is what keeps served answers bitwise equal to the
    per-request engine's on the chip."""
    mm = functools.partial(dynasparse_matmul, block=(16, 16, 16))

    def model(a, h, w):
        return mm(a, mm(h, w).out, activation="relu").out

    def wave(a, h, w):
        return jax.lax.scan(lambda _, ah: (None, model(*ah, w)), None,
                            (a, h))[1]

    shapes = ((n, n), (n, f_in))
    w = _shape(one_chip, (f_in, f_out))
    alone = jax.jit(model).lower(*(_shape(one_chip, s) for s in shapes), w)
    stacked = jax.jit(wave).lower(
        *(_shape(one_chip, (4,) + s) for s in shapes), w)
    want = _matmuls(alone.compile().as_text())
    assert want and _matmuls(stacked.compile().as_text()) == want


KERNELS = {
    "gemm": lambda x, y: ops.gemm(x, y, interpret=False),
    "spdmm": lambda x, y: ops.spdmm(x, y, interpret=False),
    "spmm": lambda x, y: ops.spmm(x, y, interpret=False),
    "csr_spmm": lambda x, y: ops.csr_spmm(x, y, rmax=64, interpret=False),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_matmul_kernel_compiles(one_chip, name):
    """Each primitive at 128 tiles on a (512, 384) x (384, 256) product."""
    x, y = _shape(one_chip, (512, 384)), _shape(one_chip, (384, 256))
    text = jax.jit(KERNELS[name]).lower(x, y).compile().as_text()
    assert "tpu_custom_call" in text


def test_tile_nnz_compiles(one_chip):
    x = _shape(one_chip, (512, 384))
    fn = jax.jit(lambda x: ops.tile_nnz(x, tile=(128, 128), interpret=False))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()

