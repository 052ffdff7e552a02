"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

:func:`load` turns an ``.xplane.pb`` into plain event lists; :func:`reduce`
works on those lists alone, so it is tested on a small recorded trace.

* device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line gives the
  busy intervals and op names, their ``XLA Modules`` line the programs
  (jit names, with the compile id in parentheses stripped);
* host spans are the harness's own ``TraceAnnotation`` names (any host
  line), and name the device's idle gaps.

On a TPU v5 lite the device clock of a trace can lead the host's by about
a millisecond, so the window's edges are that uncertain.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)

_ID = re.compile(r"\(\d+\)$")


def program_name(name: str) -> str:
    return _ID.sub("", name).strip()


def load(trace_dir: str, host_spans=()) -> dict:
    """{'devices': {plane: {'ops': [...], 'modules': [...]}},
    'host': [...]} from the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name in host_spans)
    return out


def op_name(name: str) -> str:
    """``%fusion.4 = bf16[16,256]{...} fusion(...)`` -> ``fusion.4
    bf16[16,256]``: the HLO instruction and its result type."""
    head, _, rhs = name.partition(" = ")
    return (head.lstrip("%") + " " + rhs.split("{")[0].split(" ")[0]).strip()


def self_times(iv: List[Interval]) -> Dict[str, float]:
    """Each op's time minus that of the ops nested inside it (a while
    loop's event spans its body's ops), summed by :func:`op_name`."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []  # [name, end, self]

    def close(until: float):
        while stack and stack[-1][1] <= until:
            n, _, own = stack.pop()
            out[n] += own

    for n, s, e in sorted(iv, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([op_name(n), e, e - s])
    close(float("inf"))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv if e > lo and s < hi]


def reduce(trace: dict, window_span: str, gap_spans=(),
           top: int = 10) -> dict:
    """Busy time, per-program and per-op device time and named idle gaps,
    inside the host span ``window_span`` (the traced window).

    Times are seconds, averaged over the device planes.  An idle gap is
    named by the innermost of ``gap_spans`` (host spans, outermost first)
    that covers its midpoint, and by the program that last started
    before it."""
    wins = [(s, e) for n, s, e in trace["host"] if n == window_span]
    if not wins or not trace["devices"]:
        return {}
    lo, hi = wins[0]
    n_dev = len(trace["devices"])
    busy = 0.0
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    gap_count: Dict[str, int] = defaultdict(int)
    host = [(n, s, e) for n, s, e in trace["host"] if n in gap_spans]
    for dev in trace["devices"].values():
        op_iv = _clip(dev["ops"] or dev["modules"], lo, hi)
        mods = sorted(_clip(dev["modules"], lo, hi), key=lambda x: x[1])
        for n, v in self_times(op_iv).items():
            ops[n] += v / n_dev
        for n, s, e in mods:
            programs[program_name(n)] += (e - s) / n_dev
        u = union([(s, e) for _, s, e in op_iv])
        busy += sum(e - s for s, e in u) / n_dev
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            label = "no host span"
            for name in gap_spans:
                if any(s <= mid < e for n, s, e in host if n == name):
                    label = name
            before = [program_name(n) for n, s, e in mods if s <= a]
            label += " after " + (before[-1] if before else "window start")
            gaps[label] += (b - a) / n_dev
            gap_count[label] += 1
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns,
        "programs": {k: v * ns for k, v in programs.items()},
        "device_ops": sorted(([k, v * ns] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([f"{k} (x{gap_count[k]})", v * ns]
                             for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
