"""Batched serving engine: slot-based continuous batching (lite).

A fixed-size slot array holds concurrent sequences sharing one KV cache;
finished slots are refilled from the queue between decode steps (the KV
cache is reset per admission wave for simplicity -- slot-level paged
reuse is an engine extension point, noted in DESIGN.md).  Greedy or
temperature sampling.  The decode step is jitted once per (batch, max_seq).

The Dynasparse tie-in: with ``cfg.dynasparse_ffn=True`` every FFN matmul in
the decode step routes through the fused dynasparse dispatcher, so pruned
weights / sparse activations are exploited per block at serve time -- the
paper's runtime K2P embedded in an LM serving loop.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model_zoo import ModelBundle


@dataclasses.dataclass
class Request:
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int = 32
    request_id: int = 0


@dataclasses.dataclass
class Result:
    request_id: int
    tokens: np.ndarray              # generated tokens


class ServeEngine:
    """Slot-based continuous-batching LM server over a ``ModelBundle``.

    ``generate(requests)`` admits requests in waves of ``slots`` concurrent
    sequences: one jitted left-padded prefill per wave, then one jitted
    decode step per token shared by all live slots (both cached by
    ``jax.jit`` on (batch, seq) shapes, so steady-state waves re-launch
    without re-tracing).  Sampling is greedy at ``temperature<=0``, else
    softmax sampling on the host.  Sequences stop at ``max_new_tokens`` or
    ``max_seq``; the KV cache is reset per admission wave (slot-level paged
    reuse is the recorded extension point, DESIGN.md section 5).

    Dynasparse tie-in: build the bundle with ``cfg.dynasparse_ffn=True``
    and every FFN matmul in prefill/decode routes through
    ``dynasparse_matmul`` (``models.layers._linear``), giving pruned
    weights / sparse activations per-block K2P dispatch at serve time --
    the same contracts as the GNN engines (strategy fixed to ``dynamic``,
    ``use_kernels`` off => one XLA block contraction, SKIP as a mask).
    """

    def __init__(self, bundle: ModelBundle, params, *, slots: int = 8,
                 max_seq: int = 256, temperature: float = 0.0,
                 rng_seed: int = 0):
        self.bundle = bundle
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.temperature = temperature
        self.rng = np.random.default_rng(rng_seed)
        self._prefill = jax.jit(
            lambda p, toks: bundle.prefill(p, {"tokens": toks},
                                           max_seq=max_seq))
        self._decode = jax.jit(
            lambda p, c, t, pos: bundle.decode_step(p, c, t, pos))

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        logits = logits[:, : self.bundle.cfg.vocab_size]
        if self.temperature <= 0:
            return logits.argmax(-1).astype(np.int32)
        # Gumbel-max: argmax(z + g) ~ Categorical(softmax(z)).  One
        # vectorized draw for the whole batch (no softmax materialization,
        # no per-row rng.choice loop); deterministic under rng_seed.
        z = logits / self.temperature
        g = self.rng.gumbel(size=z.shape)
        return (z + g).argmax(-1).astype(np.int32)

    def generate(self, requests: List[Request]) -> List[Result]:
        """Processes requests in admission waves of `slots`."""
        results: List[Result] = []
        queue = list(requests)
        while queue:
            wave = queue[: self.slots]
            queue = queue[self.slots:]
            results.extend(self._run_wave(wave))
        return results

    def _run_wave(self, wave: List[Request]) -> List[Result]:
        b = len(wave)
        plen = max(len(r.prompt) for r in wave)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(wave):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        logits, caches = self._prefill(self.params, jnp.asarray(toks))
        out = [[] for _ in wave]
        cur = self._sample(np.asarray(logits))
        alive = np.array([r.max_new_tokens > 0 for r in wave])
        for i in range(b):
            if alive[i]:
                out[i].append(int(cur[i]))
        budget = np.array([r.max_new_tokens for r in wave])
        pos = plen
        steps = int(budget.max(initial=0)) - 1
        for _ in range(max(steps, 0)):
            if pos >= self.max_seq:
                break
            logits, caches = self._decode(
                self.params, caches, jnp.asarray(cur[:, None]),
                jnp.int32(pos))
            cur = self._sample(np.asarray(logits))
            pos += 1
            for i in range(b):
                if len(out[i]) < budget[i]:
                    out[i].append(int(cur[i]))
        return [Result(r.request_id, np.array(o, np.int32))
                for r, o in zip(wave, out)]
