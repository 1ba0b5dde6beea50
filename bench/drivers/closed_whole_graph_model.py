"""Closed loop of whole-graph inferences of a model whose parameters and
program spec come from its model module: one client sends the same graph
again as soon as its logits are on the host.

The loop, the executor at the program's serving defaults and the
comparison after the window are those of ``closed_whole_graph``.  The
model module (``models/<model>.py``) gives ``init_weights(config, seed)``,
``program_spec(config)``, ``dims(config)`` and ``attention_bytes(config,
n, nnz)``.  With the profiler on, the traced window's device time is also
reduced by the program's ``attention`` name scope into ``attention_s``,
device seconds per traced inference: device operations are named by their
HLO instruction, which the compiled program's text maps to the scope.
"""
from __future__ import annotations

import glob
import os
import re
import time

import numpy as np

from bench import graphs, reference
from bench import trace as tracing
from bench.drivers.closed_whole_graph import Driver as WholeGraph

# ``%fusion.3 = ... metadata={op_name="jit(fused)/attention/..." ...}``
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?'
                      r'metadata=\{op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict:
    """HLO instruction name -> the name scopes of its ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m:
            out[m.group(1)] = set(m.group(2).split("/"))
    return out


def scope_seconds(planes, scopes: dict, scope: str) -> float:
    """Device seconds of the operations under ``scope`` inside the traced
    window (``bench.window``), the mean over the trace's devices."""
    window = [(ev.start_ns, ev.start_ns + ev.duration_ns)
              for p in planes if p.name.startswith("/host:")
              for ln in p.lines for ev in ln.events
              if ev.name == tracing.WINDOW]
    if not window:
        raise ValueError(f"trace has no {tracing.WINDOW!r} span")
    lo, hi = window[0]
    per_device = []
    for p in planes:
        if not p.name.startswith("/device:") or "CUSTOM" in p.name:
            continue
        for ln in p.lines:
            if ln.name != tracing.OPS_LINE:
                continue
            names, s, e = tracing._events(ln)
            dur = np.clip(e, lo, hi) - np.clip(s, lo, hi)
            per_device.append(sum(
                d for name, d in zip(names, dur)
                if d > 0 and scope in scopes.get(tracing._short(name), ())))
    return float(np.mean(per_device)) * 1e-9 if per_device else 0.0


class Driver(WholeGraph):
    def __init__(self, cell, seed: int, tracer):
        import jax.numpy as jnp

        from repro.core import compiler, runtime
        from repro.serving.config import EngineConfig

        self.cell, self.tracer = cell, tracer
        cfg = cell.config
        model = reference.model(cfg)
        t = time.monotonic()
        g = graphs.make_graph(cfg["graph"])
        self.a_gcn, self.a_mean = graphs.normalized(g.dense())
        self.h0 = graphs.make_features(cfg["features"], g.n_vertices)
        self.weights = model.init_weights(cfg, seed)
        self.attention_bytes = model.attention_bytes(
            cfg, g.n_vertices, int(np.count_nonzero(self.a_gcn)))
        self.phases = {"data_s": time.monotonic() - t}
        t = time.monotonic()
        dims = model.dims(cfg)
        defaults = EngineConfig(f_in=dims[0])
        meta = compiler.GraphMeta(cfg["name"], g.n_vertices, g.n_edges,
                                  dims[0])
        self.tensors = {"A": jnp.asarray(self.a_gcn),
                        "A_mean": jnp.asarray(self.a_mean), "H0": self.h0,
                        **self.weights}
        self.compiled = compiler.compile_model(
            model.program_spec(cfg), meta, n_cc=defaults.n_cc,
            tensors=self.tensors, align=defaults.align,
            on_chip_bytes=defaults.on_chip_bytes)
        self.executor = runtime.FusedModelExecutor(
            strategy=defaults.strategy)
        self.final = self.compiled.graph.kernels[-1].out
        self._infer()                    # compiles, or loads from the cache
        self.phases["build_and_compile_s"] = time.monotonic() - t
        t = time.monotonic()
        self._infer()
        self.phases["warmup_s"] = time.monotonic() - t
        self.scopes = None
        if tracer.directory is not None:
            t = time.monotonic()
            fn, needed = self.executor._program(self.compiled, self.tensors)
            self.scopes = op_scopes(fn.lower(
                self.tensors, self.executor._input_counts(
                    needed, self.tensors)).compile().as_text())
            self.phases["scopes_s"] = time.monotonic() - t
        self.outputs = []

    def window(self, seconds: float, trace_seconds: float) -> dict:
        record = super().window(seconds, trace_seconds)
        n = record["traced_inferences"]
        if self.scopes is None or not n:
            return record
        import jax

        paths = glob.glob(os.path.join(self.tracer.directory, "**",
                                       "*.xplane.pb"), recursive=True)
        planes = list(jax.profiler.ProfileData.from_file(paths[0]).planes)
        record["attention_s"] = scope_seconds(planes, self.scopes,
                                              "attention") / n
        record["attention_bytes"] = self.attention_bytes
        return record
