"""Serving driver: continuous-batching generation with any registered arch.

Demonstrates the inference path the decode_32k / long_500k dry-run shapes
lower: per-request bucketed prefill into fixed-capacity decode slots, then
compiled one-token decode steps over all active slots, with mid-decode
admission and per-slot early exit (serving/engine.py).

``--arch`` takes a registered arch at its published widths
(``qwen2-1.5b``), its CPU-sized variant (``qwen2-1.5b-reduced``), or
``tiny``.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b-reduced \\
      --requests 6
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \\
      --max-prompt 1100 --s-max 2048   # one TPU v5e
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.tiny import TINY
from repro.models import Model
from repro.models.transformer import DEFAULT_CTX
from repro.serving.engine import ContinuousBatchingEngine, ServeEngine


def main(argv=None):
    """Serve ``--requests`` random prompts on ``argv`` (default
    ``sys.argv[1:]``); returns the engine after it drained."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny",
                    help="tiny, a registered arch at its published widths, "
                         "or <arch>-reduced for its CPU-sized variant")
    ap.add_argument("--engine", default="continuous",
                    choices=["continuous", "naive"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "ref"],
                    help="decode-attention route (continuous engine)")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "pallas", "online", "dense"],
                    help="prefill forward-attention route")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-prompt", type=int, default=24,
                    help="prompt lengths are drawn from [4, max-prompt)")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (continuous) / batch size (naive)")
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(sys.argv[1:] if argv is None else argv)

    cfg = TINY if a.arch == "tiny" else get_config(a.arch)
    ctx = dataclasses.replace(DEFAULT_CTX, attn_backend=a.attn_backend)
    model = Model(cfg, ctx=ctx)
    params = model.init(jax.random.key(a.seed))
    print(f"arch={cfg.name} params={model.n_params:,} ({cfg.dtype}) "
          f"engine={a.engine}")

    rng = np.random.default_rng(a.seed)
    prompts = [rng.integers(0, cfg.vocab,
                            size=int(rng.integers(4, a.max_prompt)))
               for _ in range(a.requests)]
    t0 = time.time()
    if a.engine == "continuous":
        engine = ContinuousBatchingEngine(
            model, params, max_slots=a.max_batch, S_max=a.s_max, bucket=16,
            decode_backend=a.backend, attn_backend=a.attn_backend)
        for p in prompts:
            engine.submit(p, max_new_tokens=a.max_new)
        outs = engine.run()
        stats = engine.stats
    else:
        engine = ServeEngine(model, params, max_batch=a.max_batch, bucket=16)
        for p in prompts:
            engine.submit(p, max_new_tokens=a.max_new)
        outs = engine.flush()
        stats = {}
    dt = time.time() - t0
    for i, o in enumerate(outs):
        print(f"req {i}: generated {len(o)} tokens: {o.tolist()}")
    n_tok = sum(len(o) for o in outs)
    extra = (f" ttft={stats['ttft_mean_s']:.2f}s "
             f"compiles={stats['compile_misses']}" if stats else "")
    print(f"{n_tok} tokens in {dt:.1f}s ({n_tok / dt:.1f} tok/s,"
          f" {a.engine} batching with cache{extra})")
    return engine


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
