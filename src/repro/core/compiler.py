"""Compiler (paper Section IV): GNN model spec + graph meta -> optimized IR.

Step 1 parses the model into a computation graph of Aggregate/Update kernels
(Fig. 10 layer IRs); Step 2 runs data partitioning (Algorithm 9) and attaches
execution schemes (Algorithms 2/3).  It also pre-profiles the compile-time-
known densities (A, W, H^0) with counters, exactly as the paper's compiler
does -- intermediate feature densities are left to the runtime profiler.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import partitioner
from repro.core.ir import (Activation, AggOp, ComputationGraph, ExecutionScheme,
                           KernelIR, KernelType)
from repro.core.profiler import SparsityStats


@dataclasses.dataclass
class GraphMeta:
    """Meta data of the input graph (paper Table II inputs)."""

    name: str
    n_vertices: int
    n_edges: int
    f_in: int


@dataclasses.dataclass
class GNNModelSpec:
    """User-level model definition (the paper takes PyG specs; we take this)."""

    model: str                       # gcn | sage | gin | sgc | gat
    layer_dims: List[int]            # [f_in, hidden, ..., f_out]
    agg_op: AggOp = AggOp.SUM
    activation: Activation = Activation.RELU
    sgc_hops: int = 2                # K for SGC
    gin_eps: float = 0.0
    # GAT only (DESIGN.md §17), as published: ``gat_heads[l - 1]`` heads in
    # layer l, concatenated in a hidden layer (``layer_dims[l]`` is heads x
    # head width) and averaged in the output layer (``layer_dims[-1]`` is
    # the head width); ``gat_skip`` lists the layers that add a projected
    # skip connection ``H_{l-1} P_l`` before the activation.  The threshold
    # is the post-softmax cutoff below which an attention weight is dropped
    # to exactly zero; 0 keeps the exact edge softmax.
    gat_heads: Tuple[int, ...] = ()
    gat_skip: Tuple[int, ...] = ()
    att_slope: float = 0.2
    att_threshold: float = 0.0

    @property
    def n_layers(self) -> int:
        return len(self.layer_dims) - 1

    def head_width(self, layer: int) -> int:
        """Output width of one GAT head of ``layer`` (1-based)."""
        heads, width = self.gat_heads[layer - 1], self.layer_dims[layer]
        if layer == self.n_layers:
            return width
        if width % heads:
            raise ValueError(f"layer {layer}: width {width} does not split "
                             f"into {heads} concatenated heads")
        return width // heads


@dataclasses.dataclass
class CompiledModel:
    graph: ComputationGraph
    partition: partitioner.PartitionConfig
    static_stats: Dict[str, SparsityStats]   # densities known at compile time
    compile_seconds: float


def _agg(layer: int, f: int, meta: GraphMeta, src: str, dst: str,
         op: AggOp, act: Activation = Activation.NONE,
         act_on: bool = False, **kw) -> KernelIR:
    return KernelIR(KernelType.AGGREGATE, layer, f, f, meta.n_vertices,
                    meta.n_edges, agg_op=op, activation=act,
                    activation_enabled=act_on,
                    name=f"l{layer}.agg", lhs="A", rhs=src, out=dst, **kw)


def _upd(layer: int, f_in: int, f_out: int, meta: GraphMeta, src: str,
         w: str, dst: str, act: Activation = Activation.NONE,
         act_on: bool = False, **kw) -> KernelIR:
    return KernelIR(KernelType.UPDATE, layer, f_in, f_out, meta.n_vertices,
                    meta.n_edges, activation=act, activation_enabled=act_on,
                    name=f"l{layer}.upd.{w}", lhs=src, rhs=w, out=dst, **kw)


def build_computation_graph(spec: GNNModelSpec, meta: GraphMeta) -> ComputationGraph:
    """Fig. 10: per-layer kernel IRs for GCN / GraphSAGE / GIN / SGC / GAT.

    Kernel ordering inside a GCN layer follows the cheaper association:
    when f_in > f_out we transform first (Update -> Aggregate) -- the paper's
    GCN discussion ("the first Update(H0, W1) kernel of GCN") confirms this
    ordering; otherwise Aggregate -> Update.
    """
    ks: List[KernelIR] = []
    act = spec.activation
    h = "H0"
    model = spec.model.lower()
    L = spec.n_layers
    for l in range(1, L + 1):
        f_in, f_out = spec.layer_dims[l - 1], spec.layer_dims[l]
        last = l == L
        if model == "gcn":
            if f_in > f_out:
                ks.append(_upd(l, f_in, f_out, meta, h, f"W{l}", f"Z{l}"))
                ks.append(_agg(l, f_out, meta, f"Z{l}", f"H{l}", spec.agg_op,
                               act, act_on=not last))
            else:
                ks.append(_agg(l, f_in, meta, h, f"Z{l}", spec.agg_op))
                ks.append(_upd(l, f_in, f_out, meta, f"Z{l}", f"W{l}", f"H{l}",
                               act, act_on=not last))
        elif model == "sage":
            # h' = act(W_self h + W_neigh * mean_agg(h))
            ks.append(_agg(l, f_in, meta, h, f"N{l}", AggOp.MEAN))
            ks.append(_upd(l, f_in, f_out, meta, h, f"Wself{l}", f"S{l}"))
            ks.append(_upd(l, f_in, f_out, meta, f"N{l}", f"Wneigh{l}", f"H{l}",
                           act, act_on=not last, epilogue_add=f"S{l}"))
        elif model == "gin":
            # h' = MLP((1 + eps) h + sum_agg(h)); 2-layer MLP
            ks.append(_agg(l, f_in, meta, h, f"N{l}", AggOp.SUM,
                           epilogue_add=h, epilogue_scale=1.0 + spec.gin_eps))
            ks.append(_upd(l, f_in, f_out, meta, f"N{l}", f"Wa{l}", f"M{l}",
                           act, act_on=True))
            ks.append(_upd(l, f_out, f_out, meta, f"M{l}", f"Wb{l}", f"H{l}",
                           act, act_on=not last))
        elif model == "gat":
            # Per head hd: Z = h W_hd (Update); T = edge softmax over the
            # adjacency support with the head's scores (Attention); G = T Z
            # (Aggregate).  Each head's T has its own runtime density, so
            # the fused walk plans a distinct (primitive, format) grid per
            # head from the propagated writeback profiles.  The heads then
            # meet in one HEAD_COMBINE kernel: concatenated (hidden layer)
            # or averaged (output layer), plus the skip projection S = h P
            # where the layer has one, then the activation (DESIGN.md §17).
            if len(spec.gat_heads) != L:
                raise ValueError(f"gat needs one head count per layer, got "
                                 f"{spec.gat_heads} for {L} layers")
            fh = spec.head_width(l)
            heads_out = []
            for hd in range(1, spec.gat_heads[l - 1] + 1):
                z, t = f"Z{l}h{hd}", f"T{l}h{hd}"
                ks.append(_upd(l, f_in, fh, meta, h, f"Wg{l}h{hd}", z))
                ks.append(KernelIR(
                    KernelType.ATTENTION, l, fh, fh, meta.n_vertices,
                    meta.n_edges, name=f"l{l}.att.h{hd}", lhs="A", rhs=z,
                    out=t, att_src=f"a_src{l}h{hd}", att_dst=f"a_dst{l}h{hd}",
                    att_slope=spec.att_slope,
                    att_threshold=spec.att_threshold))
                ks.append(KernelIR(
                    KernelType.AGGREGATE, l, fh, fh, meta.n_vertices,
                    meta.n_edges, agg_op=AggOp.SUM, name=f"l{l}.agg.h{hd}",
                    lhs=t, rhs=z, out=f"G{l}h{hd}"))
                heads_out.append(f"G{l}h{hd}")
            skip = None
            if l in spec.gat_skip:
                skip = f"S{l}"
                ks.append(_upd(l, f_in, f_out, meta, h, f"P{l}", skip))
            ks.append(KernelIR(
                KernelType.HEAD_COMBINE, l, f_out, f_out, meta.n_vertices,
                meta.n_edges, activation=act, activation_enabled=not last,
                name=f"l{l}.combine", out=f"H{l}", epilogue_add=skip,
                heads_in=tuple(heads_out), head_mean=last))
        elif model == "sgc":
            # SGC collapses to A^K H W with no inter-hop nonlinearity;
            # emitted as K Aggregates (first layer only) + one Update.
            if l == 1:
                hop_src = h
                for hop in range(1, spec.sgc_hops + 1):
                    ks.append(_agg(l, f_in, meta, hop_src, f"P{hop}", spec.agg_op))
                    hop_src = f"P{hop}"
                ks.append(_upd(l, f_in, f_out, meta, hop_src, f"W{l}", f"H{l}",
                               act, act_on=not last))
            else:
                ks.append(_upd(l, f_in, f_out, meta, h, f"W{l}", f"H{l}",
                               act, act_on=not last))
        else:
            raise ValueError(f"unknown GNN model {spec.model!r}")
        h = f"H{l}"
    return ComputationGraph(ks, model_name=model, graph_name=meta.name)


def compile_model(
    spec: GNNModelSpec,
    meta: GraphMeta,
    *,
    n_cc: int,
    tensors: Optional[Dict[str, np.ndarray]] = None,
    eta: int = partitioner.ETA_DEFAULT,
    on_chip_bytes: Optional[int] = None,
    align: int = 128,
) -> CompiledModel:
    """Full compilation: IR -> partitioning -> static sparsity profiling."""
    t0 = time.perf_counter()
    graph = build_computation_graph(spec, meta)
    kwargs = dict(n_cc=n_cc, eta=eta, align=align)
    if on_chip_bytes is not None:
        kwargs["on_chip_bytes"] = on_chip_bytes
    cfg = partitioner.choose_partition_sizes(graph, **kwargs)
    partitioner.apply_partitioning(graph, cfg)
    static_stats: Dict[str, SparsityStats] = {}
    if tensors:
        for name, arr in tensors.items():
            # convention: adjacency at (N1, N1); everything else (weights,
            # features) at (N2, N2) -- Aggregate consumers pool rows to N1.
            block = (cfg.n1, cfg.n1) if name.startswith("A") else (cfg.n2, cfg.n2)
            static_stats[name] = SparsityStats.measure(arr, block)
    dt = time.perf_counter() - t0
    return CompiledModel(graph, cfg, static_stats, dt)
