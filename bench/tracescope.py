"""Attribution inside a traced window, from the program's own names:

* :func:`scope_times` — one program's device self-time by ``zo.*`` scope.
  A trace's op events carry the HLO instruction's name (``%fusion.357 =
  ...``) but no ``op_name``; the program's optimized HLO text
  (``FederatedZO.group_hlo_text``) carries each instruction's ``op_name``
  metadata, whose path names the scope (``core/zo.py``).  The join is by
  instruction name, unique in a module, and result type, so that a text
  from another compile of the program cannot join by name alone.  An
  instruction whose ``op_name`` names no scope (a copy XLA inserted
  carries no metadata) takes the scope that all its scoped users agree
  on, and that time is reported apart as inherited.
* :func:`idle_by_span` — the device's idle time by the innermost program
  span (``repro/obs.py``: ``fl.*``, ``mask.*``) covering each gap's
  midpoint.

Both read :func:`tracefile.load`'s event lists; :class:`Prefixes` makes
``load`` keep every host event whose name starts with one of its prefixes.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Tuple

from tracefile import _clip, op_name, program_name, self_times, union

SCOPES = ("zo.sample", "zo.perturb", "zo.forward", "zo.update")
OTHER = "zo.other"
PROGRAM_SPANS = ("fl.", "mask.")
# the server's host work between the client group programs of a round
SERVER_SPANS = ("fl.inputs", "fl.uplink", "fl.replay", "fl.aggregate",
                "fl.update")
OUTSIDE = "outside every program span"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_REF = re.compile(r"%([^\s,(){}=]+)")


class Prefixes:
    """``name in Prefixes("fl.", ...)`` when ``name`` starts with one."""

    def __init__(self, *prefixes: str):
        self.prefixes = prefixes

    def __contains__(self, name: str) -> bool:
        return name.startswith(self.prefixes)


def scope_of(path: str, scopes=SCOPES) -> str:
    """The innermost of ``scopes`` on an ``op_name`` path, else OTHER."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return OTHER


def hlo_index(hlo_text: str,
              scopes=SCOPES) -> Dict[str, Tuple[str, str, bool]]:
    """{``<instruction> <result type>``: (op_name, scope, inherited)} of
    every instruction of an HLO module's text.  ``op_name`` is the
    instruction's metadata or ""; ``scope`` the innermost of ``scopes`` on
    it or, where it names none, the one scope that every scoped user of
    the instruction names (``inherited``); else OTHER."""
    instrs = [(name, rest, m.group(1) if m else "")
              for name, rest in _INSTR.findall(hlo_text)
              for m in [_OP_NAME.search(rest)]]
    own = {name: scope_of(op, scopes) for name, _, op in instrs}
    users = defaultdict(set)
    for name, rest, _ in instrs:
        for ref in _REF.findall(rest.split(", metadata=")[0]):
            if ref in own and ref != name:
                users[ref].add(name)
    out = {}
    for name, rest, op in instrs:
        sc, inh = own[name], False
        if sc == OTHER:
            up = {own[u] for u in users[name]} - {OTHER}
            if len(up) == 1:
                sc, inh = up.pop(), True
        out[op_name(f"%{name} = {rest}")] = (op, sc, inh)
    return out


def _inside(intervals, t: float) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``intervals``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def scope_times(trace: dict, window_span: str, hlo_text: str,
                program: str = "jit_group", scopes=SCOPES) -> dict:
    """Self-time of the ops that ran inside ``program``'s module events in
    the window, by scope (seconds, averaged over the device planes).

    ``scopes_s`` holds every scope and OTHER: the ops whose instruction
    carries no scope and those the HLO text does not name (by name and
    result type).
    ``inherited_s`` is the part of ``scopes_s`` placed by users' scopes
    (:func:`hlo_index`); ``top_other`` the largest ops left in OTHER, with
    their ``op_name``.  ``joined_share`` is the share of the ops'
    self-time whose instruction the HLO text names; ``program_s`` the
    module events' own time, which the ops' self-time falls short of by
    the gaps between ops."""
    wins = [(s, e) for n, s, e in trace["host"] if n == window_span]
    if not wins or not trace["devices"]:
        return {}
    lo, hi = wins[0]
    index = hlo_index(hlo_text, scopes)
    n_dev = len(trace["devices"])
    by_scope: Dict[str, float] = defaultdict(float)
    inherited: Dict[str, float] = defaultdict(float)
    other: Dict[str, float] = defaultdict(float)
    ops_t = joined = prog = 0.0
    for dev in trace["devices"].values():
        mods = union([(s, e) for n, s, e in _clip(dev["modules"], lo, hi)
                      if program_name(n) == program])
        prog += sum(e - s for s, e in mods)
        ops = [(n, s, e) for n, s, e in _clip(dev["ops"], lo, hi)
               if _inside(mods, (s + e) / 2)]
        for key, t in self_times(ops).items():
            ops_t += t
            _, sc, inh = index.get(key, ("", OTHER, False))
            if key in index:
                joined += t
            by_scope[sc] += t
            if inh:
                inherited[sc] += t
            if sc == OTHER:
                other[key] += t
    ns = 1e-9 / n_dev
    top = sorted(other.items(), key=lambda kv: -kv[1])[:10]
    return {"scopes_s": {k: by_scope.get(k, 0.0) * ns
                         for k in (*scopes, OTHER)},
            "inherited_s": {k: v * ns for k, v in inherited.items()},
            "top_other": [[k, index.get(k, (None,))[0], v * ns]
                          for k, v in top],
            "ops_s": ops_t * ns, "program_s": prog * ns,
            "joined_share": joined / ops_t if ops_t else None}


def idle_by_span(trace: dict, window_span: str,
                 prefixes=PROGRAM_SPANS) -> dict:
    """Idle device time in the window by the innermost host span whose
    name starts with one of ``prefixes`` covering the gap's midpoint
    (OUTSIDE where none does); seconds, averaged over the device planes.
    ``named_share`` is the share of the idle time that a span names."""
    wins = [(s, e) for n, s, e in trace["host"] if n == window_span]
    if not wins or not trace["devices"]:
        return {}
    lo, hi = wins[0]
    host = [(n, s, e) for n, s, e in trace["host"] if n.startswith(prefixes)]
    n_dev = len(trace["devices"])
    idle: Dict[str, float] = defaultdict(float)
    for dev in trace["devices"].values():
        busy = union([(s, e) for _, s, e in
                      _clip(dev["ops"] or dev["modules"], lo, hi)])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            # innermost: the covering span that opened last
            cover = [(s, -e, n) for n, s, e in host if s <= mid < e]
            idle[max(cover)[2] if cover else OUTSIDE] += b - a
    ns = 1e-9 / n_dev
    total = sum(idle.values())
    return {"idle_s": {k: v * ns for k, v in sorted(idle.items(),
                                                    key=lambda kv: -kv[1])},
            "window_s": (hi - lo) * 1e-9, "idle_total_s": total * ns,
            "named_share": (1.0 - idle.get(OUTSIDE, 0.0) / total
                            if total else None)}
