"""Known-bad / known-good fixture programs: the analyzer's self-test.

Every rule ships at least one deliberately-broken program it MUST flag
and a minimal clean twin it must pass — so the analyzer itself is
falsifiable (``python -m repro.analysis --selftest`` /
``--fixture <rule>``; tests/test_analysis.py runs the same matrix).

Fixtures are self-contained (no model stack) so a selftest failure
always means the *rule* regressed, not the repo.
"""
from __future__ import annotations

from typing import Dict, List

from repro.analysis.core import Built, Program

_S = 128


# ---------------------------------------------------- dense fixtures ------
def _dense_bad() -> Built:
    import jax.numpy as jnp

    def fn(q, k):           # materialized [S, S] score matrix
        return (jnp.einsum("sd,td->st", q, k) ** 2).sum()

    q = jnp.ones((_S, 16))
    return Built(fn, (q, q), meta=dict(seq_threshold=_S))


def _dense_good() -> Built:
    import jax.numpy as jnp

    def fn(q, k):           # same reduction, no [S, S] buffer
        return ((q * k).sum(-1) ** 2).sum()

    q = jnp.ones((_S, 16))
    return Built(fn, (q, q), meta=dict(seq_threshold=_S))


# ---------------------------------------------------- dtype fixtures ------
def _dtype_bad() -> Built:
    import jax
    import jax.numpy as jnp

    def fn(x, y):
        # bf16 reduction: accumulates in bf16 instead of f32
        return jnp.sum(x.astype(jnp.bfloat16)), y * 2.0

    # f64 avals require x64 mode, which this process keeps off — trace
    # the jaxpr under the scoped enable and hand it to the rule directly
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(fn)(jnp.ones((8,), jnp.float32),
                                   jnp.ones((8,), jnp.float64))
    x = jnp.ones((8,), jnp.float32)
    return Built(fn, (x, x), meta=dict(runtime=False),
                 overrides={"jaxpr": jaxpr})


def _dtype_good() -> Built:
    import jax.numpy as jnp

    def fn(x, y):
        return jnp.sum(x), y * 2.0

    x = jnp.ones((8,), jnp.float32)
    return Built(fn, (x, x))


# ------------------------------------------------- host-sync fixtures -----
def _hostsync_bad() -> Built:
    import jax
    import jax.numpy as jnp

    def fn(x):
        jax.debug.print("loss={l}", l=x.sum())   # debug_callback eqn
        return x * 2.0

    return Built(fn, (jnp.ones((8,)),))


def _hostsync_good() -> Built:
    import jax.numpy as jnp

    def fn(x):
        return x * 2.0

    return Built(fn, (jnp.ones((8,)),))


# ------------------------------------------------- recompile fixtures -----
def _recompile_bad_const() -> Built:
    import jax.numpy as jnp
    import numpy as np

    table = np.arange(8192, dtype=np.float32)    # 32 KiB closure capture

    def fn(x):
        return x + jnp.asarray(table)[: x.shape[0]]

    return Built(fn, (jnp.ones((8,)),), meta=dict(runtime=False))


def _recompile_bad_retrace() -> Built:
    import jax
    import jax.numpy as jnp

    def fn(x):
        # fresh jit per call: every invocation traces + compiles again —
        # the pre-PR2 per-flush serving bug in miniature
        return jax.jit(lambda y: y * 2.0)(x)

    return Built(fn, (jnp.ones((8,)),))


def _recompile_good() -> Built:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    return Built(fn, (jnp.ones((8,)),))


# ------------------------------------------------------ comm fixtures -----
_HLO_BAD = """\
ENTRY %round () -> f32[] {
  %p = f32[1000000]{0} parameter(0)
  %ag = f32[4000000]{0} all-gather(f32[1000000]{0} %p), dimensions={0}
  %ar = f32[1000000]{0} all-reduce(f32[1000000]{0} %p), to_apply=%sum
}
"""

_HLO_GOOD = """\
ENTRY %round () -> f32[] {
  %p = f32[250000]{0} parameter(0)
  %ag = f32[1000000]{0} all-gather(f32[250000]{0} %p), dimensions={0}
  %ar = f32[16]{0} all-reduce(f32[16]{0} %s), to_apply=%sum
}
"""


def _comm_bad() -> Built:
    # O(model) uplink + blown gather budget + CommLog mismatch, expressed
    # as synthetic HLO so the self-test needs no multi-device mesh
    pb = 4_000_000
    return Built(lambda: None, (), overrides={"hlo": _HLO_BAD},
                 meta=dict(comm=dict(
                     param_bytes=pb, allgather_max_bytes=3 * pb // 4,
                     other_collective_max_bytes=2 ** 16,
                     expected_up_bytes=64, commlog_up_bytes=pb)))


def _comm_good() -> Built:
    pb = 1_000_000
    return Built(lambda: None, (), overrides={"hlo": _HLO_GOOD},
                 meta=dict(comm=dict(
                     param_bytes=pb, allgather_max_bytes=4 * pb,
                     other_collective_max_bytes=2 ** 16,
                     expected_up_bytes=64, commlog_up_bytes=64)))


# ---------------------------------------------------- memory fixtures -----
def _memory_bad_peak() -> Built:
    import jax.numpy as jnp

    def fn(x):               # 64 MiB [4096, 4096] f32 intermediate
        return jnp.outer(x, x).sum()

    return Built(fn, (jnp.ones((4096,)),),
                 meta=dict(peak_bytes_budget=8 * 2 ** 20, runtime=False))


def _memory_bad_vmem() -> Built:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):               # 16 MiB in + 16 MiB out in one block
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    return Built(fn, (jnp.ones((2048, 2048)),), meta=dict(runtime=False))


def _memory_bad_residual_stack() -> Built:
    """The pre-recompute-VJP first_order pattern: differentiating a scan
    over query blocks stacks every block's [blk, S] softmax residuals for
    the backward — O(S^2) live bytes (the measured 186 MB peak at model
    shapes).  The budget is recompute-sized (O(S*dh), what the flash
    kernel's VJP keeps), so the stacked residuals must trip the gate."""
    import jax
    import jax.numpy as jnp

    S, blk, dh = 1024, 128, 16

    def attn_loss(q, k):
        qb = q.reshape(S // blk, blk, dh)

        def one(_, qi):
            s = qi @ k.T                       # [blk, S] scores
            p = jax.nn.softmax(s, axis=-1)     # residual the scan stacks
            return _, (p @ k).sum()

        _, outs = jax.lax.scan(one, None, qb)
        return outs.sum()

    def fn(q, k):
        return jax.grad(attn_loss)(q, k)

    q = jnp.ones((S, dh))
    # recompute-sized ceiling: O(S*dh) residuals are ~64 KiB here; the
    # stacked [S/blk, blk, S] score residuals are ~4 MiB
    return Built(fn, (q, q), meta=dict(peak_bytes_budget=2 * 2 ** 20,
                                       runtime=False))


def _memory_good() -> Built:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        y = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
        return (y * x).sum()

    return Built(fn, (jnp.ones((128, 128)),),
                 meta=dict(peak_bytes_budget=8 * 2 ** 20))


FIXTURES: Dict[str, Dict[str, List[Program]]] = {
    "dense-materialization": dict(
        bad=[Program("fixture:dense:bad", "materialized [S,S] scores",
                     _dense_bad)],
        good=[Program("fixture:dense:good", "blockwise-style reduction",
                      _dense_good)]),
    "dtype-drift": dict(
        bad=[Program("fixture:dtype:bad", "f64 aval + bf16 reduction",
                     _dtype_bad)],
        good=[Program("fixture:dtype:good", "f32 throughout",
                      _dtype_good)]),
    "host-sync": dict(
        bad=[Program("fixture:host-sync:bad", "jax.debug.print in path",
                     _hostsync_bad)],
        good=[Program("fixture:host-sync:good", "pure fn", _hostsync_good)]),
    "recompile-hazard": dict(
        bad=[Program("fixture:recompile:bad-const",
                     "32 KiB closure constant", _recompile_bad_const),
             Program("fixture:recompile:bad-retrace",
                     "fresh jit per call", _recompile_bad_retrace)],
        good=[Program("fixture:recompile:good", "stable jitted fn",
                      _recompile_good)]),
    "comm-budget": dict(
        bad=[Program("fixture:comm:bad",
                     "O(model) uplink / blown gather budget", _comm_bad)],
        good=[Program("fixture:comm:good", "gather + scalar psum only",
                      _comm_good)]),
    "memory-ceiling": dict(
        bad=[Program("fixture:memory:bad-peak", "64 MiB dense outer",
                     _memory_bad_peak),
             Program("fixture:memory:bad-vmem",
                     "32 MiB pallas block working set", _memory_bad_vmem),
             Program("fixture:memory:bad-residual-stack",
                     "scan-stacked attention backward residuals vs a "
                     "recompute-sized budget", _memory_bad_residual_stack)],
        good=[Program("fixture:memory:good", "small blocks, small peak",
                      _memory_good)]),
}
