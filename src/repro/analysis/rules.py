"""The rule catalog (DESIGN.md §10): six invariants over every registered
hot path.

Each rule reads per-program configuration from ``Built.meta``:

* ``seq_threshold`` — the S for the dense-materialization scan (must
  exceed every non-sequence dim of the program, so only a genuine
  [S, S]-class buffer trips it); absent -> rule skipped.
* ``dense_limit`` — how many >= S dims constitute a violation (default 2).
* ``allow`` — ``{rule_name: (primitive, ...)}`` allowlists; an allowlisted
  primitive's outputs are exempt (document why at the registry site).
* ``const_bytes_limit`` — recompile-hazard constvar size gate (default
  4 KiB: PRNG folds and iota helpers stay under it, a baked weight or
  position table does not).
* ``dyn_dims`` — ``{name: value}`` dims the program would re-trace on
  (bucket widths, S); scalar literals equal to one are warned about.
* ``runtime`` — False disables the trace-count harness (abstract args).
* ``comm`` — comm-budget configuration (presence enables the rule):
  ``param_bytes``, ``allgather_max_bytes``, ``other_collective_max_bytes``
  and optionally ``expected_up_bytes`` + ``commlog_up_bytes`` for the
  CommLog cross-check.
* ``peak_bytes_budget`` — liveness-estimate ceiling (absent -> estimate
  reported as info only).
* ``arch`` / ``vmem_budget_bytes`` — VMEM-fit budget for pallas_call
  block working sets (default the conservative ~16 MiB/core of the
  Pallas guide).
"""
from __future__ import annotations

from typing import List

from repro.analysis.core import Built, Finding, Rule
from repro.analysis.walk import (constvar_records, iter_eqns,
                                 liveness_peak_bytes, pallas_block_records,
                                 square_dim_findings)

MAX_REPORTED = 8          # cap repeated findings per (rule, program)

# per-arch VMEM budgets for one pallas_call block working set (bytes).
# "tpu" is the conservative ~16 MiB/core floor; newer parts have more.
VMEM_BUDGETS = {"tpu": 16 * 2 ** 20, "tpu_v5e": 128 * 2 ** 20}

F64_DTYPES = ("float64", "complex128")
LOWP_DTYPES = ("bfloat16", "float16")
# reductions whose accumulator dtype follows the (low-precision) output
# aval — the kernels deliberately contract these to f32 (zo_update /
# flash_attention keep f32 VMEM accumulators), so a low-precision aval
# here means silently lossy accumulation.
REDUCE_PRIMS = ("reduce_sum", "cumsum", "dot_general", "add_any",
                "reduce_window_sum", "reduce_prod")
# jax.debug.print lowers to its own ``debug_print`` primitive, not a
# ``*callback`` one
HOST_SYNC_PRIMS = ("infeed", "outfeed", "debug_print")


def check_no_dense_intermediates(jaxpr, S: int, limit: int = 2,
                                 allow_primitives=()) -> List[dict]:
    """The analyzer's dense-materialization scan as a standalone predicate
    (what tests/test_attn_backends.py and benchmarks/attn_bench.py call):
    returns the offending ``{primitive, shape, dtype}`` records — empty
    means no intermediate holds ``limit`` dims of size >= ``S``."""
    return square_dim_findings(jaxpr, S, limit=limit,
                               allow_primitives=allow_primitives)


class DenseMaterializationRule(Rule):
    """No intermediate may hold >= ``dense_limit`` dims of size >=
    ``seq_threshold`` — the generalized no-[S, S] / no-[K, P] buffer
    proof.  A blockwise attention forward that never holds two >= S dims
    on one buffer cannot have materialized the score matrix; a federated
    round that never holds [K, n_params] cannot have densified per-client
    model copies."""

    name = "dense-materialization"
    description = "no [S,S]/[K,P]-class dense intermediates"
    needs = ("jaxpr",)

    def applicable(self, built: Built) -> bool:
        return built.meta.get("seq_threshold") is not None

    def check(self, program, built, artifacts):
        S = built.meta["seq_threshold"]
        limit = built.meta.get("dense_limit", 2)
        recs = check_no_dense_intermediates(
            artifacts.jaxpr(), S, limit=limit,
            allow_primitives=self.allow(built))
        return [self.finding(
            program, f"{r['primitive']} materializes {r['dtype']}"
            f"{r['shape']} ({limit}+ dims >= {S})", **r)
            for r in recs[:MAX_REPORTED]]


class DtypeDriftRule(Rule):
    """No f64 aval anywhere (a single Python-float promotion under x64
    multiplies every buffer it touches by 2x and falls off the TPU fast
    path), and no f16/bf16-accumulated reduction — the kernels contract
    reductions to f32 VMEM accumulators, so a low-precision reduce aval
    is silently lossy summation."""

    name = "dtype-drift"
    description = "no f64 avals; no f16/bf16 reduction accumulation"
    needs = ("jaxpr",)

    def check(self, program, built, artifacts):
        allow = self.allow(built)
        out: List[Finding] = []
        jx = artifacts.jaxpr()
        for aval in getattr(jx, "in_avals", []):
            if str(getattr(aval, "dtype", "")) in F64_DTYPES:
                out.append(self.finding(
                    program, f"f64 input aval {aval}", dtype=str(aval.dtype)))
        for eqn, depth in iter_eqns(jx):
            prim = eqn.primitive.name
            if prim in allow:
                continue
            for var in eqn.outvars:
                dt = str(getattr(var.aval, "dtype", ""))
                if dt in F64_DTYPES:
                    out.append(self.finding(
                        program, f"{prim} produces {dt} "
                        f"{list(getattr(var.aval, 'shape', ()))}",
                        primitive=prim, dtype=dt, depth=depth))
                elif dt in LOWP_DTYPES and prim in REDUCE_PRIMS:
                    out.append(self.finding(
                        program, f"{prim} accumulates in {dt} "
                        f"(cast operand or set preferred_element_type=f32)",
                        primitive=prim, dtype=dt, depth=depth))
        return out[:MAX_REPORTED]


class HostSyncRule(Rule):
    """No host round-trips inside jitted hot paths: ``pure_callback`` /
    ``io_callback`` / ``debug_callback`` / ``debug_print`` (jax.debug.print)
    equations and infeed/outfeed all serialize the device stream against Python —
    at decode-step or ZO-step granularity one stray print costs more
    than the step."""

    name = "host-sync"
    description = "no callbacks / infeed / outfeed in jitted paths"
    needs = ("jaxpr",)

    def check(self, program, built, artifacts):
        allow = self.allow(built)
        out = []
        for eqn, depth in iter_eqns(artifacts.jaxpr()):
            prim = eqn.primitive.name
            if prim in allow:
                continue
            if "callback" in prim or prim in HOST_SYNC_PRIMS:
                out.append(self.finding(
                    program, f"host-sync primitive '{prim}' in jitted path",
                    primitive=prim, depth=depth))
        return out[:MAX_REPORTED]


class RecompileHazardRule(Rule):
    """Three escalating signals that a hot path re-traces or re-ships:

    1. (error) constvars above ``const_bytes_limit`` — big closure
       captures are re-hashed every call and re-trace whenever the Python
       value is rebuilt (the pre-PR2 per-flush serving bug).
    2. (warning) scalar int literals equal to a declared dynamic dim —
       a baked ``S``/bucket width that will fork the compile cache.
    3. (error) the trace-count harness: call the built fn twice with the
       same concrete args — any XLA compile on the second call (counted
       from ``repro.obs``'s compile records) means steady-state
       serving/training re-traces.
    """

    name = "recompile-hazard"
    description = "no big baked constants; no steady-state retrace"
    needs = ("jaxpr", "runtime")

    def check(self, program, built, artifacts):
        out: List[Finding] = []
        limit = built.meta.get("const_bytes_limit", 4096)
        for rec in constvar_records(artifacts.jaxpr()):
            if rec["bytes"] > limit:
                out.append(self.finding(
                    program, f"baked-in constant {rec['dtype']}"
                    f"{rec['shape']} ({rec['bytes']} B > {limit} B): "
                    f"closure capture re-traces when rebuilt", **rec))
        out.extend(self._literal_warnings(program, built, artifacts))
        if built.meta.get("runtime", True):
            n = self._second_call_compiles(built)
            if n:
                out.append(self.finding(
                    program, f"{n} XLA compile(s) on a repeat call with "
                    f"identical arguments: the hot path re-traces at "
                    f"steady state", compiles=n))
        return out

    def _literal_warnings(self, program, built, artifacts):
        dyn = built.meta.get("dyn_dims") or {}
        if not dyn:
            return []
        from jax.extend import core as jex_core
        hits = []
        values = {v: k for k, v in dyn.items()}
        for eqn, _ in iter_eqns(artifacts.jaxpr()):
            for v in eqn.invars:
                if (isinstance(v, jex_core.Literal)
                        and isinstance(v.val, int) and v.val in values):
                    hits.append((eqn.primitive.name, v.val))
        return [self.finding(
            program, f"scalar literal {val} (= dyn dim "
            f"'{values[val]}') baked into {prim}: changing it re-traces",
            severity="warning", primitive=prim, value=val)
            for prim, val in hits[:3]]

    @staticmethod
    def _second_call_compiles(built: Built) -> int:
        import jax

        from repro import obs
        jax.block_until_ready(built.fn(*built.args))   # warm-up call
        before = obs.compile_count()
        jax.block_until_ready(built.fn(*built.args))
        return obs.compile_count() - before


class CommBudgetRule(Rule):
    """The paper's headline invariant, structurally: uplink stays
    O(seeds + scalars), never O(model).  On the compiled sharded round the
    only model-sized collective allowed is the plan's ZeRO-3 parameter
    all-gather (bounded by ``allgather_max_bytes``); everything else must
    fit ``other_collective_max_bytes``.  When the builder ran a live
    round, ``commlog_up_bytes`` must equal the protocol's
    4*K*T*n_dirs-byte accounting and stay far under one model."""

    name = "comm-budget"
    description = "collective bytes: gather <= plan budget, uplink O(scalars)"
    needs = ("hlo",)

    def applicable(self, built: Built) -> bool:
        return bool(built.meta.get("comm"))

    def check(self, program, built, artifacts):
        from repro.launch.hlo_tools import collective_bytes
        comm = built.meta["comm"]
        coll = collective_bytes(artifacts.hlo())
        out = []
        ag = coll.get("all-gather", 0.0)
        others = sum(v for k, v in coll.items() if k != "all-gather")
        ag_max = comm.get("allgather_max_bytes")
        if ag_max is not None and ag > ag_max:
            out.append(self.finding(
                program, f"all-gather bytes {ag:.0f} exceed the plan's "
                f"parameter-gather budget {ag_max:.0f}", bytes=ag,
                budget=ag_max, collectives=coll))
        other_max = comm.get("other_collective_max_bytes")
        if other_max is not None and others > other_max:
            out.append(self.finding(
                program, f"non-gather collective bytes {others:.0f} exceed "
                f"the O(seeds+scalars) budget {other_max:.0f}",
                bytes=others, budget=other_max, collectives=coll))
        up = comm.get("commlog_up_bytes")
        expected = comm.get("expected_up_bytes")
        if up is not None and expected is not None and up != expected:
            out.append(self.finding(
                program, f"CommLog uplink {up} B != protocol accounting "
                f"{expected} B (4*K*T*n_dirs)", up=up, expected=expected))
        pb = comm.get("param_bytes")
        if up is not None and pb is not None and up * 8 > pb:
            out.append(self.finding(
                program, f"uplink {up} B is O(model) ({pb} B of "
                f"parameters): the scalar-only protocol is broken",
                up=up, param_bytes=pb))
        if not out:
            out.append(self.finding(
                program, f"collectives within budget: "
                f"all-gather {ag:.0f} B, other {others:.0f} B",
                severity="info", collectives=coll))
        return out


class MemoryCeilingRule(Rule):
    """Peak-live-bytes liveness estimate per program (regression gate via
    ``peak_bytes_budget``; the estimate always lands in the report so
    benchmarks/memory_footprint.py comparisons have a static counterpart)
    plus a VMEM-fit check: every pallas_call's block working set (kernel
    invars/outvars = inputs + outputs + scratch for one grid step) must
    fit the per-arch VMEM budget."""

    name = "memory-ceiling"
    description = "peak live bytes under budget; pallas blocks fit VMEM"
    needs = ("jaxpr",)

    def check(self, program, built, artifacts):
        out: List[Finding] = []
        jx = artifacts.jaxpr()
        peak = liveness_peak_bytes(jx)
        budget = built.meta.get("peak_bytes_budget")
        if budget is not None and peak > budget:
            out.append(self.finding(
                program, f"liveness peak estimate {peak} B exceeds budget "
                f"{budget} B", peak_bytes=peak, budget=budget))
        else:
            out.append(self.finding(
                program, f"liveness peak estimate {peak} B"
                + (f" (budget {budget} B)" if budget else ""),
                severity="info", peak_bytes=peak))
        vmem = built.meta.get("vmem_budget_bytes",
                              VMEM_BUDGETS[built.meta.get("arch", "tpu")])
        for rec in pallas_block_records(jx):
            if rec["block_bytes"] > vmem:
                out.append(self.finding(
                    program, f"pallas_call '{rec['name']}' block working "
                    f"set {rec['block_bytes']} B exceeds VMEM budget "
                    f"{vmem} B", name=rec["name"],
                    block_bytes=rec["block_bytes"], budget=vmem))
        return out


ALL_RULES = (DenseMaterializationRule(), DtypeDriftRule(), HostSyncRule(),
             RecompileHazardRule(), CommBudgetRule(), MemoryCeilingRule())


def rules_by_name(names=None):
    table = {r.name: r for r in ALL_RULES}
    if names is None:
        return list(ALL_RULES)
    missing = [n for n in names if n not in table]
    if missing:
        raise KeyError(f"unknown rule(s) {missing}; "
                       f"have {sorted(table)}")
    return [table[n] for n in names]
