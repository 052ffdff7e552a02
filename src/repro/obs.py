"""The program's spans, counters and compile records, always on.

* :class:`span` — a named host interval.  Each closed span is kept in a
  bounded in-memory buffer as ``(name, round, parent, t0_ns, t1_ns,
  attrs)`` and, under a running ``jax.profiler`` trace, also lands on the
  trace's host plane (``TraceAnnotation``), on the same clock as the
  device's ops.  Spans nest: the innermost open span is the parent, and a
  ``round`` given to a span is inherited by the spans opened inside it.
* :func:`count` — plain named counters.
* compile records — every ``/jax/core/compile/*`` event JAX reports
  (trace, lowering, backend compile), with its seconds, keyed by the
  program (``jit(<name>)``) and by the innermost open span.

A span reads the host clock twice and does nothing else: no device
synchronisation and no transfer.  It ends where the host code inside it
ends; the device time of what it dispatched is read from a trace.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Optional

import jax

MAX_RECORDS = 65536
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_spans: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_compiles: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_counters: Dict[str, int] = collections.Counter()
_n_compiles = 0   # backend compiles since import or reset, never dropped
_open = threading.local()


def _stack() -> list:
    st = getattr(_open, "stack", None)
    if st is None:
        st = _open.stack = []
    return st


class span:
    """``with span("fl.round", round=r) as s: ... s.attrs["up_bytes"] = n``.

    ``seconds`` holds the span's length once it has closed."""

    __slots__ = ("name", "round", "parent", "attrs", "t0_ns", "t1_ns",
                 "_ann")

    def __init__(self, name: str, round: Optional[int] = None, **attrs):
        self.name, self.round, self.attrs = name, round, attrs
        self.parent = None
        self.t0_ns = self.t1_ns = 0

    def __enter__(self) -> "span":
        st = _stack()
        if st:
            top = st[-1]
            self.parent = top.name
            if self.round is None:
                self.round = top.round
        st.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        _spans.append((self.name, self.round, self.parent, self.t0_ns,
                       self.t1_ns, self.attrs or None))

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


def count(name: str, n: int = 1) -> None:
    _counters[name] += n


def _program(fun_name: str) -> str:
    """Trace events name the Python function, lowering and compile events
    ``jit(<function>)``: key all three by the latter."""
    return fun_name if "(" in fun_name else f"jit({fun_name})"


def _on_event(event: str, seconds: float, **kw) -> None:
    global _n_compiles
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    st = _stack()
    top = st[-1] if st else None
    _compiles.append((_program(str(kw.get("fun_name", "?"))), kind,
                      float(seconds), top.name if top else None,
                      top.round if top else None, time.perf_counter_ns()))
    if kind == "compile":
        _n_compiles += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


def compile_count() -> int:
    """Backend compiles recorded since import (or the last :func:`reset`)."""
    return _n_compiles


def export() -> dict:
    """``{spans, counters, compiles}`` as plain Python; a compile record's
    ``t1_ns`` is when JAX reported it (the end of that step), on the spans'
    clock."""
    return {
        "spans": [dict(name=n, round=r, parent=p, t0_ns=t0, t1_ns=t1,
                       **(a or {}))
                  for n, r, p, t0, t1, a in _spans],
        "counters": dict(_counters),
        "compiles": [dict(program=f, kind=k, seconds=s, span=n, round=r,
                          t1_ns=t)
                     for f, k, s, n, r, t in _compiles],
    }


_SPAN_KEYS = ("name", "round", "parent", "t0_ns", "t1_ns")


def totals(rec: Optional[dict] = None) -> dict:
    """Of :func:`export`'s record: seconds by span name, the spans'
    numeric attributes summed by span name (``fl.round``'s bytes), the
    counters, and compile seconds by program (all three steps summed)."""
    rec = export() if rec is None else rec
    spans: Dict[str, float] = collections.defaultdict(float)
    attrs: Dict[str, dict] = collections.defaultdict(collections.Counter)
    for s in rec["spans"]:
        spans[s["name"]] += (s["t1_ns"] - s["t0_ns"]) * 1e-9
        for k, v in s.items():
            if k not in _SPAN_KEYS and isinstance(v, (int, float)):
                attrs[s["name"]][k] += v
    compiles: Dict[str, float] = collections.defaultdict(float)
    for c in rec["compiles"]:
        compiles[c["program"]] += c["seconds"]
    return {"spans_s": dict(spans),
            "span_attrs": {k: dict(v) for k, v in attrs.items()},
            "counters": rec["counters"], "compiles_s": dict(compiles)}


def reset() -> None:
    global _n_compiles
    _spans.clear()
    _compiles.clear()
    _counters.clear()
    _n_compiles = 0
