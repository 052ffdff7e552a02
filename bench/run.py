"""Benchmark entry: one cell, one seed, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's federated server through the program's own
entries (weights from the seed, sensitivity mask, clients, FederatedZO),
runs the first rounds that the reference checks, then times whole rounds
for ``--seconds``.  With ``--trace 1`` the window is profiled and the
per-layer metrics are read from the trace; otherwise the end-to-end ones.
After the window the program's state is freed and the plain reference
(``reference.py``) decides ``correct``.

The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error and the last key of
that object.  Off a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from spec import BENCH, CACHE_DIR, ROOT, load_cell, peaks  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def start(cell, cache_dir: str, require_tpu: bool):
    """Point JAX's compilation cache into the checkout, check the device
    and import the program from this checkout.  Returns the devices, or
    None where the run must not go on."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        print(f"no TPU: JAX's default device is {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    if require_tpu and len(devs) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return None
    src = os.path.join(ROOT, "src")
    sys.path.insert(0, src)
    import repro  # a namespace package: check every directory it spans
    where = [os.path.abspath(p) for p in repro.__path__]
    if any(not p.startswith(src + os.sep) for p in where):
        print(f"the program was imported from {where}, not from {src}",
              file=sys.stderr)
        return None
    from repro.launch.compile_cache import enable_compile_cache
    if enable_compile_cache() != cache_dir:
        print("the program set another compile cache", file=sys.stderr)
        return None
    return devs


def run(argv=None, *, root: str = ROOT, bench: str = BENCH,
        require_tpu: bool = True, cache_dir: str = None,
        t_start: float = None) -> int:
    """One run; ``root`` and ``bench`` say where ``BENCHMARK.json`` and the
    cell's files are (the program is always this checkout's ``src``)."""
    a = _args(argv)
    cell = load_cell(a.workload, root, bench)
    cache_dir = cache_dir or os.path.join(ROOT, CACHE_DIR)
    devs = start(cell, cache_dir, require_tpu)
    if devs is None:
        return 2
    import jax

    import fedrun
    import tracefile
    from reference import Reference, compare

    counter = fedrun.CompileCounter()
    sess = fedrun.Session(cell, a.seed)
    sess.build()
    record = sess.first_rounds()
    setup_s = time.time() - (T_START if t_start is None else t_start)
    tr = cell.traffic
    before = counter.snapshot()
    trace_dir = None
    if a.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        win = sess.window(min(a.seconds, tr["trace"]["seconds"]),
                          min_rounds=tr["trace"]["min_rounds"], traced=True)
        jax.profiler.stop_trace()
    else:
        win = sess.window(a.seconds)
    in_window = {k: v - before[k] for k, v in counter.snapshot().items()}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs[:cell.chips]) or None
    print(json.dumps({"info": {
        "workload": cell.name, "seed": a.seed, **sess.routes(),
        "rounds_in_window": len(win["round_s"]),
        "zo_steps_in_window": len(win["round_s"]) * win["steps_per_round"],
        "compiles_in_window": in_window,
        "compile_cache": cache_dir, "spans_s": sess.spans,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)}}}),
        flush=True)
    sess.close()

    trace = None
    if trace_dir:
        trace = tracefile.reduce(
            tracefile.load(trace_dir, (fedrun.WINDOW_SPAN,
                                       fedrun.ROUND_SPAN)),
            fedrun.WINDOW_SPAN,
            gap_spans=(fedrun.WINDOW_SPAN, fedrun.ROUND_SPAN))
        shutil.rmtree(trace_dir, ignore_errors=True)

    ref = Reference(cell.family, cell.published, cell.config["dtype"], tr,
                    fl_seed=sess.seeds["fl"],
                    weights_seed=sess.seeds["weights"])
    numbers = compare(ref, record)
    check = {k: {"value": _finite(v), "limit": cell.limits[k]}
             for k, v in numbers.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in check.values()) and win["failed"] == 0

    run_record = {
        "window": win, "setup_s": setup_s, "spans": sess.spans,
        "memory_peak_bytes": peak_bytes,
        "flops_per_step": fedrun.forward_flops_per_step(cell),
        "peak": peaks(devs[0].device_kind) if require_tpu else
        {"bf16_flops": float("nan")},
        "trace": trace}
    metrics = {}
    for m in (cell.per_layer if a.trace else cell.end_to_end):
        v = m.read(run_record)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(win["round_s"]),
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["check"] = check
    for k, c in check.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
