"""Records the small TPU trace that ``test_scopes.py`` attributes.

    python3 bench/tests/record_scoped_trace.py <out_dir>

A jitted program whose phases sit in ``zo.*`` scopes runs inside the
program's ``fl.*`` spans (``repro/obs.py``) and the harness's window and
round spans; in each round the host sleeps inside ``fl.uplink`` while the
device waits, so the device has a known idle gap in a known span.  Writes
``scoped.xplane.pb``, the program's optimized HLO text ``scoped.hlo.txt``
and ``scoped.json`` (the sleeps' host-clock lengths) to ``<out_dir>``.
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

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
from fedrun import ROUND_SPAN, WINDOW_SPAN  # noqa: E402

from repro import obs  # noqa: E402

ROUNDS = 3


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    @jax.jit
    def group(w, x):
        with jax.named_scope("zo.sample"):
            z = jax.random.normal(jax.random.key(0), w.shape, w.dtype)
        with jax.named_scope("zo.perturb"):
            wp = w + 1e-3 * z
        with jax.named_scope("zo.forward"):
            for _ in range(4):
                x = jnp.tanh(x @ wp)
        with jax.named_scope("zo.update"):
            return w - 1e-2 * jnp.mean(x) * z, x

    @jax.jit
    def server(w):
        return w * 0.5 + 1.0

    w = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(server(group(w, x)[0]))
    hlo = group.lower(w, x).compile().as_text()
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp)
    sleeps = []
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for r in range(ROUNDS):
            with jax.profiler.TraceAnnotation(ROUND_SPAN), \
                    obs.span("fl.round", round=r):
                with obs.span("fl.group"):
                    w2, y = group(w, x)
                with obs.span("fl.group_wait"):
                    jax.block_until_ready(y)
                with obs.span("fl.uplink"):
                    t0 = time.perf_counter()
                    time.sleep(0.02)
                    sleeps.append(time.perf_counter() - t0)
                with obs.span("fl.update"):
                    jax.block_until_ready(server(w2))
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(pb, os.path.join(out, "scoped.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out, "scoped.hlo.txt"), "w") as f:
        f.write(hlo)
    with open(os.path.join(out, "scoped.json"), "w") as f:
        json.dump({"sleeps_s": sleeps, "rounds": ROUNDS,
                   "device_kind": jax.devices()[0].device_kind}, f)


if __name__ == "__main__":
    main(sys.argv[1])
