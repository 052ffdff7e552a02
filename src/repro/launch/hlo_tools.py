"""HLO inspection helpers for the perf hillclimb (§Perf methodology).

The dry-run profile is ``lowered/compiled.as_text()`` + ``cost_analysis()``;
this module extracts the *largest* collective / copy ops with shapes so a
hypothesis can name the exact tensor whose movement it claims to remove.

Usage:
  PYTHONPATH=src python -m repro.launch.hlo_tools --arch qwen2-7b \
      --shape decode_32k [--top 15] [--depth 1]
"""
from __future__ import annotations

import argparse
import re
from collections import defaultdict
from typing import List, Tuple

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")
# per-device traffic multiplier relative to the op's output bytes (ring algs)
COLLECTIVE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                     "reduce-scatter": 1.0, "all-to-all": 1.0,
                     "collective-permute": 1.0}
OPS = COLLECTIVE_OPS + ("copy", "dynamic-update-slice", "dynamic-slice")


def shape_bytes(shapes_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in (per-device) HLO text.

    Returns ``{op: bytes}`` over :data:`COLLECTIVE_OPS` (async ``-start``
    forms counted once, ``-done`` forms skipped).  Used by the dry-run's
    roofline extraction and ``benchmarks/fl_scale_bench.py``; multiply by
    :data:`COLLECTIVE_FACTOR` for ring-algorithm wire traffic."""
    out = {op: 0.0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s*(\(?[\w\[\],{}\s/#*]*?)\s*(all-reduce|all-gather|"
                      r"reduce-scatter|all-to-all|collective-permute)"
                      r"(-start|-done)?\(", line)
        if not m or (m.group(3) == "-done"):
            continue
        out[m.group(2)] += shape_bytes(m.group(1))
    return out


def top_ops(hlo_text: str, ops=OPS, top: int = 20
            ) -> List[Tuple[int, str, str]]:
    """Largest ops by output bytes: (bytes, op, line-prefix)."""
    found = []
    pat = re.compile(r"=\s*(\(?[\w\[\],{}\s/#*]*?)\s*(" + "|".join(ops)
                     + r")(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        b = shape_bytes(m.group(1))
        found.append((b, m.group(2), line.strip()[:180]))
    found.sort(key=lambda t: -t[0])
    return found[:top]


def op_totals(hlo_text: str, ops=OPS) -> dict:
    tot = defaultdict(float)
    pat = re.compile(r"=\s*(\(?[\w\[\],{}\s/#*]*?)\s*(" + "|".join(ops)
                     + r")(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if m:
            tot[m.group(2)] += shape_bytes(m.group(1))
    return dict(tot)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when the backend
    offers no analysis)."""
    return compiled.cost_analysis() or {}


def main():
    # import here so --xla_force_host_platform_device_count is set first
    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    from repro.launch import dryrun as DR

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--step", default=None)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--depth", type=int, default=1,
                    help="periods to keep (unrolled); 0 = full scan")
    ap.add_argument("--multi", action="store_true")
    a = ap.parse_args()

    from repro.configs import get_config, get_shape
    from repro.launch.mesh import make_mesh_from_config, mesh_config

    cfg = get_config(a.arch)
    if a.depth:
        cfg = DR._shallow_cfg(cfg, a.depth)
    shape = get_shape(a.shape)
    mc = mesh_config(multi_pod=a.multi)
    mesh = make_mesh_from_config(mc)
    step = a.step or DR.STEP_FOR_SHAPE[shape.kind]
    jf, args = DR.build_lowerable(cfg, shape, mesh, mc, step,
                                  unroll_all=bool(a.depth))
    compiled = jf.lower(*args).compile()
    text = compiled.as_text()
    print(f"== {a.arch} x {a.shape} ({step}) depth={a.depth or 'full'} ==")
    print("op totals (per-device bytes):")
    for op, b in sorted(op_totals(text).items(), key=lambda kv: -kv[1]):
        print(f"  {op:22s} {b / 1e6:12.1f} MB")
    print(f"\ntop {a.top} ops:")
    for b, op, line in top_ops(text, top=a.top):
        print(f"  {b / 1e6:10.1f} MB  {line}")


if __name__ == "__main__":
    main()
