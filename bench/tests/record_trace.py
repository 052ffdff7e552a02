"""Records the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out_dir>

Two named jitted programs run inside the harness's host spans, with a host
sleep between them so that the device has a known idle gap; the trace and
the host-clock lengths of the sleeps are written to ``<out_dir>``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from fedrun import ROUND_SPAN, WINDOW_SPAN  # noqa: E402


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    @jax.jit
    def group(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    @jax.jit
    def server(x):
        return x * 2.0 + 1.0

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(server(group(x)))
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp)
    sleeps = []
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation(ROUND_SPAN):
                y = jax.block_until_ready(group(x))
                t0 = time.perf_counter()
                time.sleep(0.02)
                sleeps.append(time.perf_counter() - t0)
                jax.block_until_ready(server(y))
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(pb, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out, "small.json"), "w") as f:
        json.dump({"sleeps_s": sleeps,
                   "device_kind": jax.devices()[0].device_kind}, f)


if __name__ == "__main__":
    main(sys.argv[1])
