"""GNN models (GCN / GraphSAGE / GIN / SGC / GAT) through the Dynasparse stack.

The model IS its IR: ``core.compiler`` turns a ``GNNModelSpec`` + graph meta
into Aggregate/Update kernels, and either the real-numerics executor
(``core.runtime.FusedModelExecutor``) or the cost-model simulator executes
it.  This module provides the bundle plumbing: weight init/pruning,
dataset wiring, the two evaluation paths used by tests/benchmarks/examples,
and :func:`reference_forward`, the plain float32 ``jax.numpy`` forward the
executor is checked against.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compiler, runtime
from repro.core.compiler import CompiledModel, GNNModelSpec, GraphMeta
from repro.core.ir import Activation, AggOp, KernelType
from repro.core.profiler import SparsityStats
from repro.data import graphs as graph_data

GNN_MODELS = ("gcn", "sage", "gin", "sgc", "gat")


def make_model_spec(model: str, f_in: int, hidden: int, n_classes: int
                    ) -> GNNModelSpec:
    """The paper's 2-layer models (Section VIII-A).  ``gat`` is the
    published architecture at these widths: two ELU heads of ``hidden / 2``
    concatenated, then two heads of ``n_classes`` averaged."""
    agg = AggOp.MEAN if model == "sage" else AggOp.SUM
    dims = [f_in, n_classes] if model == "sgc" else [f_in, hidden, n_classes]
    if model == "gat":
        return GNNModelSpec(model, dims, activation=Activation.ELU,
                            gat_heads=(2, 2))
    return GNNModelSpec(model, dims, agg_op=agg)


def _glorot_pruned(kernels, *, seed: int, density: float
                   ) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    out: Dict[str, np.ndarray] = {}
    for k in kernels:
        if k.kernel_type == KernelType.ATTENTION:
            # per-head attention vectors (f, 1); glorot, never pruned --
            # a zeroed entry would statically kill a feature channel's
            # contribution to every score, which defeats the point of
            # input-dependent attention sparsity.
            for name in (k.att_src, k.att_dst):
                if name in out:
                    continue
                lim = np.sqrt(6.0 / (k.f_in + 1))
                out[name] = rng.uniform(
                    -lim, lim, size=(k.f_in, 1)).astype(np.float32)
            continue
        if k.kernel_type != KernelType.UPDATE or k.rhs in out:
            continue
        lim = np.sqrt(6.0 / (k.f_in + k.f_out))
        w = rng.uniform(-lim, lim, size=(k.f_in, k.f_out)).astype(np.float32)
        out[k.rhs] = graph_data.prune_weights(w, density, rng)
    return out


def _ref_dot(x, y):
    return jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _ref_activation(spec: GNNModelSpec):
    return {Activation.RELU: jax.nn.relu, Activation.ELU: jax.nn.elu,
            Activation.NONE: lambda v: v,
            Activation.PRELU: lambda v: jnp.where(v >= 0, v, 0.25 * v),
            }[spec.activation]


def _ref_attention(a, z, att_src, att_dst, slope, threshold):
    """Masked edge-softmax over the support of ``a`` (self loops included),
    weights at or below ``threshold`` dropped; empty rows stay zero."""
    s_src = _ref_dot(z, att_src)                       # (n, 1)
    s_dst = _ref_dot(z, att_dst)                       # (n, 1)
    scores = s_src + s_dst.T
    scores = jnp.where(scores >= 0, scores, slope * scores)
    support = a != 0
    row_max = jnp.max(jnp.where(support, scores, -jnp.inf), axis=1,
                      keepdims=True)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    ex = jnp.where(support, jnp.exp(scores - row_max), 0.0)
    alpha = ex / jnp.maximum(jnp.sum(ex, axis=1, keepdims=True), 1e-30)
    return jnp.where(alpha > threshold, alpha, 0.0)


def reference_forward(spec: GNNModelSpec, tensors: Dict[str, jnp.ndarray]
                      ) -> jnp.ndarray:
    """Plain float32 forward of a zoo model, at ``Precision.HIGHEST``.

    Written from the model definitions alone (no IR, no partitioning, no
    K2P dispatch), so it checks the engines rather than restating them.
    ``tensors`` holds ``A`` (GCN-normalized adjacency), ``A_mean``
    (row-normalized), ``H0`` and the weights under the names
    :func:`init_weights` gives them.  Returns the (n, n_classes) logits.
    """
    t = {k: jnp.asarray(v, jnp.float32) for k, v in tensors.items()}
    a, h = t["A"], t["H0"]
    model = spec.model.lower()
    relu = jax.nn.relu
    n_layers = spec.n_layers
    for l in range(1, n_layers + 1):
        act = (lambda v: v) if l == n_layers else _ref_activation(spec)
        if model == "gcn":
            h = act(_ref_dot(a, _ref_dot(h, t[f"W{l}"])))
        elif model == "sage":
            h = act(_ref_dot(_ref_dot(t["A_mean"], h), t[f"Wneigh{l}"])
                    + _ref_dot(h, t[f"Wself{l}"]))
        elif model == "gin":
            n = _ref_dot(a, h) + (1.0 + spec.gin_eps) * h
            h = act(_ref_dot(relu(_ref_dot(n, t[f"Wa{l}"])), t[f"Wb{l}"]))
        elif model == "sgc":
            if l == 1:
                for _ in range(spec.sgc_hops):
                    h = _ref_dot(a, h)
            h = act(_ref_dot(h, t[f"W{l}"]))
        elif model == "gat":
            # heads concatenated in a hidden layer, averaged in the output
            # layer; the skip projection joins before the activation
            heads = []
            for hd in range(1, spec.gat_heads[l - 1] + 1):
                z = _ref_dot(h, t[f"Wg{l}h{hd}"])
                alpha = _ref_attention(
                    a, z, t[f"a_src{l}h{hd}"], t[f"a_dst{l}h{hd}"],
                    spec.att_slope, spec.att_threshold)
                heads.append(_ref_dot(alpha, z))
            out = (sum(heads[1:], heads[0]) / len(heads) if l == n_layers
                   else jnp.concatenate(heads, axis=1))
            if l in spec.gat_skip:
                out = out + _ref_dot(h, t[f"P{l}"])
            h = act(out)
        else:
            raise ValueError(f"unknown GNN model {spec.model!r}")
    return h


def reference_error(out, ref) -> Tuple[float, float]:
    """``(max |out - ref|, that / max |ref|)`` -- the error figures every
    check against :func:`reference_forward` is gated on."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    err = float(np.max(np.abs(out - ref)))
    return err, err / max(float(np.max(np.abs(ref))), 1e-30)


def init_weights(compiled: CompiledModel, *, seed: int = 0,
                 density: float = 1.0) -> Dict[str, np.ndarray]:
    """Glorot weights for every Update kernel, magnitude-pruned to
    ``density`` (paper Section VIII-B evaluates 0-90%+ weight sparsity)."""
    return _glorot_pruned(compiled.graph.kernels, seed=seed, density=density)


def init_spec_weights(spec: GNNModelSpec, *, seed: int = 0,
                      density: float = 1.0) -> Dict[str, np.ndarray]:
    """Weights for a model SPEC, independent of any concrete graph.

    Weight shapes depend only on the layer dims, never on |V|, so a serving
    engine shares ONE weight set across all of its shape buckets
    (`serving.graph_engine.GraphServeEngine`).  Bitwise-identical to
    :func:`init_weights` on any compile of the same spec: the kernel walk
    (and hence the rng consumption order) is the graph builder's, which
    does not look at the graph meta.
    """
    meta = GraphMeta(spec.model, 1, 1, spec.layer_dims[0])
    graph = compiler.build_computation_graph(spec, meta)
    return _glorot_pruned(graph.kernels, seed=seed, density=density)


@dataclasses.dataclass
class DenseGNN:
    """Engine-ready bundle on a materialized (small) graph."""

    compiled: CompiledModel
    tensors: Dict[str, jnp.ndarray]
    graph: graph_data.DenseGraph

    def run(self, engine=None, *, strategy: Optional[str] = None
            ) -> Tuple[jnp.ndarray, runtime.InferenceReport]:
        """One inference through ``engine``, a
        :class:`runtime.FusedModelExecutor` (the whole model as ONE
        jit-compiled program).  Pass ``strategy`` as a shortcut for
        ``FusedModelExecutor(strategy=...)``.
        """
        if engine is None:
            engine = runtime.FusedModelExecutor(
                strategy=strategy or "dynamic")
        elif strategy is not None and strategy != engine.strategy:
            raise ValueError(
                f"strategy {strategy!r} conflicts with engine "
                f"strategy {engine.strategy!r}")
        env, rep = engine.run(self.compiled, self.tensors)
        return env[self.compiled.graph.kernels[-1].out], rep


def build_dense(model: str, dataset: str, *, scale: float = 0.25,
                n_cc: int = 7, weight_density: float = 1.0, seed: int = 0,
                on_chip_bytes: Optional[int] = None, align: int = 16
                ) -> DenseGNN:
    """Materialize a scaled dataset + compile + init weights (numerics path).

    ``align=16`` keeps partitions meaningful at test scale; production TPU
    tiling uses 128 (the default elsewhere).
    """
    g = graph_data.materialize(dataset, scale=scale, seed=seed)
    spec = make_model_spec(model, g.spec.f_in, g.spec.hidden, g.spec.n_classes)
    meta = GraphMeta(dataset, g.spec.n_vertices, g.spec.n_edges, g.spec.f_in)
    tensors = {
        "A": jnp.asarray(g.a_gcn),
        "A_mean": jnp.asarray(g.a_mean),
        "H0": jnp.asarray(g.h0),
    }
    cm = compiler.compile_model(
        spec, meta, n_cc=n_cc, tensors=tensors, align=align,
        on_chip_bytes=on_chip_bytes or 256 * 1024)
    for name, w in init_weights(cm, seed=seed, density=weight_density).items():
        tensors[name] = jnp.asarray(w)
        cm.static_stats[name] = SparsityStats.measure(
            tensors[name], (cm.partition.n2, cm.partition.n2))
    return DenseGNN(cm, tensors, g)


@dataclasses.dataclass
class SimGNN:
    """Cost-model bundle at full Table VI scale (no numerics)."""

    compiled: CompiledModel
    stats: Dict[str, SparsityStats]

    def simulate(self, strategy: str, model=None, n_cc: Optional[int] = None
                 ) -> runtime.InferenceReport:
        return runtime.simulate_inference(self.compiled, self.stats,
                                          strategy=strategy, model=model,
                                          n_cc=n_cc)


def build_sim(model: str, dataset: str, *, n_cc: int = 7,
              weight_density: float = 1.0, seed: int = 0,
              relu_keep: float = 0.5, align: int = 16,
              on_chip_bytes: int = 6 * 1024 * 1024) -> SimGNN:
    """Full-scale bundle: Alg. 9 partitioning + synthetic block stats +
    density propagation for the runtime-only intermediate features.

    Defaults model the paper's FPGA: partitions align to p_sys=16 and the
    per-core buffer budget is ~45MB/7 cores.  (The TPU path uses align=128
    and the VMEM budget instead.)
    """
    if model == "gat":
        raise NotImplementedError(
            "gat has no cost-model simulation path: attention sparsity is "
            "input-dependent, so there is no density to propagate -- use "
            "the real-numerics engines (build_dense / serving)")
    spec_g = graph_data.TABLE_VI[dataset]
    spec = make_model_spec(model, spec_g.f_in, spec_g.hidden,
                           spec_g.n_classes)
    meta = GraphMeta(dataset, spec_g.n_vertices, spec_g.n_edges, spec_g.f_in)
    cm = compiler.compile_model(spec, meta, n_cc=n_cc, align=align,
                                on_chip_bytes=on_chip_bytes)
    p = cm.partition
    stats = graph_data.block_stats(dataset, p.n1, p.n2, seed=seed)
    for k in cm.graph.kernels:
        if k.kernel_type != KernelType.UPDATE or k.rhs in stats:
            continue
        stats.update(graph_data.weight_stats(
            [k.f_in, k.f_out], p.n2, weight_density, seed=seed,
            names=[k.rhs]))
    stats = runtime.propagate_stats(cm, stats, relu_keep=relu_keep)
    return SimGNN(cm, stats)
