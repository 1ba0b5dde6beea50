"""Batched LM serving with dynamic-sparsity FFN dispatch.

Serves two engines side by side on the same pruned weights: a dense
baseline and the dynasparse engine (fused K2P dispatch inside the decode
step).  Outputs must match token-for-token; the dispatch histogram shows
SpDMM/SKIP taking over as pruning deepens -- the paper's Figure 11/12
trend, live in an LM serving loop.

  PYTHONPATH=src python examples/serve_lm.py --prune 0.1
"""
import argparse
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.core.dynasparse import dynasparse_matmul
from repro.core.perf_model import TPUCostModel
from repro.jax_cache import configure_compile_cache
from repro.launch.serve import prune_ffn
from repro.models import model_zoo
from repro.serving.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--prune", type=float, default=0.1,
                    help="FFN weight density after magnitude pruning")
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    cfg = smoke_config("llama3.2-1b")
    bundle = model_zoo.build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    params = prune_ffn(params, args.prune, np.random.default_rng(0))

    rng = np.random.default_rng(1)
    reqs = [Request(rng.integers(0, cfg.vocab_size, size=(12,)).astype(
        np.int32), max_new_tokens=8, request_id=i)
        for i in range(args.requests)]

    dense = ServeEngine(bundle, params, slots=4, max_seq=24).generate(
        list(reqs))
    cfg_ds = dataclasses.replace(cfg, dynasparse_ffn=True)
    sparse_engine = ServeEngine(model_zoo.build(cfg_ds), params, slots=4,
                                max_seq=24)
    sparse = sparse_engine.generate(list(reqs))

    same = all(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(dense, sparse))
    print(f"prune-density={args.prune}: dense vs dynasparse outputs "
          f"identical: {same}")
    for r in sparse[:3]:
        print(f"  req {r.request_id}: {r.tokens}")

    # show the dispatcher's decisions on one pruned FFN weight
    w = params["stack"][0]["ffn"]["w1"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (256, w.shape[0]),
                          jnp.float32)
    res = dynasparse_matmul(x, w.astype(jnp.float32), block=(64, 64, 64),
                            cost_model=TPUCostModel())
    hist = np.bincount(np.asarray(res.codes).ravel(), minlength=4)
    print(f"FFN w1 K2P histogram [SKIP, GEMM, SPDMM, SPMM]: {hist}")


if __name__ == "__main__":
    configure_compile_cache()
    main()
