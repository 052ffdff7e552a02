"""Where a cell's time goes, in the program's own names: one process that
sets up as ``run.py`` does, times a window of whole rounds without the
profiler and then one with it, on the same compiled programs, and reads
the traced one.

    python3 bench/attribute.py --workload <cell> --seed <n> [--seconds <s>]
        [--out <file.json>]

It prints, as one JSON object on the last line:

* ``metrics`` — the cell's per-layer metrics of the traced window (the
  readers under ``metrics/``), and three read through ``tracescope.py``:
  ``zo_perturb_device_ms`` and ``zo_forward_device_ms`` (the client group
  program's device self-time in the ``zo.perturb`` / ``zo.forward``
  scopes, per ZO step) and ``server_idle_ms_per_round`` (device idle time
  whose gap lies in the server's ``fl.inputs``, ``fl.uplink``,
  ``fl.replay``, ``fl.aggregate`` or ``fl.update`` span, per round);
* ``checks`` — the share of the group program's op time that joins to its
  HLO, the scopes' sum against the program's time, and the share of the
  idle time that a program span names;
* ``profiler`` — the median round without and with the profiler, and the
  in-memory recorder's cost per span on this host.

``--out`` writes the whole attribution (every scope, the largest ops no
scope places, every idle span, the program's span and compile totals, and
every compile after set-up).  No reference runs: this reads, it
does not decide ``correct``.  Off a TPU it exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402

from spec import (BENCH, CACHE_DIR, ROOT, load_cell, load_module,  # noqa: E402
                  peaks)


def device_metrics(scopes: dict, idle: dict, window: dict) -> dict:
    """The three per-layer numbers of the scope join and the idle split
    (None each where the trace holds no device)."""
    from tracescope import SERVER_SPANS
    if not scopes or not idle:
        return dict.fromkeys(("zo_perturb_device_ms", "zo_forward_device_ms",
                              "server_idle_ms_per_round"))
    rounds = len(window["round_s"])
    steps = rounds * window["steps_per_round"]
    sc = scopes["scopes_s"]
    return {
        "zo_perturb_device_ms": sc["zo.perturb"] / steps * 1e3,
        "zo_forward_device_ms": sc["zo.forward"] / steps * 1e3,
        "server_idle_ms_per_round": sum(
            idle["idle_s"].get(s, 0.0) for s in SERVER_SPANS) / rounds * 1e3}


def _reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "attribute_" + name)


def span_cost_us(n: int = 20000) -> float:
    """Host microseconds one empty ``obs.span`` costs, no profiler on."""
    from repro import obs
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("bench.span_cost"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None, *, root: str = ROOT, bench: str = BENCH,
         require_tpu: bool = True, cache_dir: str = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of each window (default: the traffic's "
                         "traced-window seconds)")
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = load_cell(a.workload, root, bench)
    import run
    devs = run.start(cell, cache_dir or os.path.join(ROOT, CACHE_DIR),
                     require_tpu)
    if devs is None:
        return 2
    import jax

    import fedrun
    import tracefile
    import tracescope
    from repro import obs

    tr = cell.traffic
    seconds = a.seconds or tr["trace"]["seconds"]
    sess = fedrun.Session(cell, a.seed)
    sess.build()
    sess.first_rounds()
    setup_s = time.time() - T_START
    off = sess.window(seconds, min_rounds=tr["trace"]["min_rounds"])
    trace_dir = tempfile.mkdtemp(prefix="bench_attribute_")
    jax.profiler.start_trace(trace_dir)
    on = sess.window(seconds, min_rounds=tr["trace"]["min_rounds"],
                     traced=True)
    jax.profiler.stop_trace()
    (T, width), = sess.server.zo_routes
    t0 = time.perf_counter()
    hlo = sess.server.group_hlo_text(T, width)
    hlo_s = time.perf_counter() - t0
    routes = sess.routes()
    sess.close()

    trace = tracefile.load(trace_dir, tracescope.Prefixes(
        "bench.", *tracescope.PROGRAM_SPANS))
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = fedrun.WINDOW_SPAN
    reduced = tracefile.reduce(trace, win, gap_spans=(win, fedrun.ROUND_SPAN))
    scopes = tracescope.scope_times(trace, win, hlo)
    idle = tracescope.idle_by_span(trace, win)
    record = {"window": on, "setup_s": setup_s, "spans": sess.spans,
              "memory_peak_bytes": None,
              "flops_per_step": fedrun.forward_flops_per_step(cell),
              "peak": peaks(devs[0].device_kind) if require_tpu else
              {"bf16_flops": float("nan")}, "trace": reduced}
    metrics = {m.name: m.read(record) for m in cell.per_layer}
    metrics["mask_topk_s"] = _reader("mask_topk_s").read(record)
    # set-up ends at the first round of the first window here, so the
    # reader counts both windows' rounds
    metrics["setup_compile_s"] = _reader("setup_compile_s").read(
        {"window": {"round_s": off["round_s"] + on["round_s"]}})
    metrics.update(device_metrics(scopes, idle, on))

    group_ms = metrics.get("zo_step_device_ms")
    steps = len(on["round_s"]) * on["steps_per_round"]
    scope_ms = (sum(scopes["scopes_s"].values()) / steps * 1e3
                if scopes else None)
    checks = {
        "joined_share": scopes.get("joined_share"),
        "scopes_ms_per_step": scope_ms,
        "scopes_over_group_program": (scope_ms / group_ms
                                      if scope_ms and group_ms else None),
        "idle_named_share": idle.get("named_share")}
    rec = obs.export()
    first = sorted(s["t0_ns"] for s in rec["spans"]
                   if s["name"] == "fl.round")[-len(off["round_s"])
                                               - len(on["round_s"])]
    by_span = defaultdict(float)
    for c in rec["compiles"]:
        by_span[f"{c['span']} r{c['round']} {c['program']}"] += c["seconds"]
    profiler = {"round_s_off": statistics.median(off["round_s"]),
                "round_s_on": statistics.median(on["round_s"]),
                "rounds_off": len(off["round_s"]),
                "rounds_on": len(on["round_s"])}
    profiler["on_over_off"] = profiler["round_s_on"] / profiler["round_s_off"]
    full = {"workload": cell.name, "seed": a.seed, **routes,
            "device": devs[0].device_kind, "setup_s": setup_s,
            "group_hlo_text_s": hlo_s, "metrics": metrics, "checks": checks,
            "scopes": scopes, "idle_by_span": idle,
            "idle_gaps": reduced.get("idle_gaps"),
            "round_s": {"off": off["round_s"], "on": on["round_s"]},
            "obs": obs.totals(rec),
            "compiles_by_span": dict(sorted(by_span.items(),
                                            key=lambda kv: -kv[1])[:25]),
            "compiles_after_setup": [c for c in rec["compiles"]
                                     if c["t1_ns"] > first]}
    profiler["span_cost_us"] = span_cost_us()
    full["profiler"] = profiler
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(full, f, indent=1)
    print(json.dumps({k: full[k] for k in (
        "workload", "seed", "device", "setup_s", "group_hlo_text_s",
        "metrics", "checks", "profiler")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
