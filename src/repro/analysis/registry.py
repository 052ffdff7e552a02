"""The registered hot paths: every program the repo's perf story rests
on, built with tiny concrete shapes so the full rule sweep stays
seconds-cheap on CPU.

Shape plan (shared across the LM programs): ``TINY`` with ``vocab=256``
at ``S=320`` — S then exceeds *every* non-sequence dim (d_model 64,
d_ff 128, vocab 256, n_heads 4) AND the attention auto-dispatch
threshold (``ATTN_AUTO_MIN_S`` = 256), so (a) the only way to trip the
dense-materialization rule is a genuine [S, S]-class buffer, and (b)
``backend="auto"`` resolves to the same blockwise route production
takes at scale.

Liveness budgets (``peak_bytes_budget``) are regression gates set at
roughly 2x the measured estimate of the current tree — a structural
change that doubles a hot path's working set should fail loudly, normal
drift should not.  All budgets are per-program meta, so tightening or
allowlisting is a one-line registry edit (DESIGN.md §10).
"""
from __future__ import annotations

from typing import List, Optional

from repro.analysis.core import Built, Program, ProgramSkip

S = 320              # sequence length: > vocab(256) > ATTN_AUTO_MIN_S
MiB = 2 ** 20


def _tiny_lm():
    """(cfg, model, params, space) for the LM-shaped programs."""
    import jax

    from repro.configs.tiny import TINY
    from repro.core import random_mask
    from repro.models import Model
    cfg = TINY.replace(vocab=256)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    space = random_mask(params, density=1e-2, seed=3, balanced=False)
    return cfg, model, params, space


def _tokens(*shape):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, 256, size=shape), jnp.int32)


def build_zo_train_loop() -> Built:
    """The compiled high-frequency training burst:
    ``fl_step.make_fl_train_loop`` (T=1 MEERKAT steps in one jitted
    scan), 2 steps x 2 clients at S=320."""
    import jax

    from repro.core.fl_step import make_fl_train_loop
    cfg, model, params, space = _tiny_lm()
    n_steps, n_clients, b = 2, 2, 1
    loop = make_fl_train_loop(
        lambda p, bt: model.loss(p, bt, per_example=True), space,
        eps=1e-3, lr=1e-2, n_clients=n_clients, n_steps=n_steps)
    batches = {"tokens": _tokens(n_steps, n_clients * b, S)}
    return Built(
        jax.jit(loop), (params, jax.random.key(1), batches),
        meta=dict(seq_threshold=S, dyn_dims={"S": S},
                  # the liveness estimate (walk.liveness_peak_bytes) of
                  # this program on the space.add route, traced on CPU:
                  # 11.7 MB
                  peak_bytes_budget=24 * MiB))


def _round_problem():
    """Synthetic-classification round problem (mirrors
    tools/fl_mesh_parity.py): the FederatedZO server's own group program
    at its production shape class, cheap enough to also *run* one round
    for the CommLog cross-check."""
    import jax

    from repro.configs.tiny import TINY
    from repro.core import random_mask
    from repro.data.synthetic import TaskSpec, make_task_fns
    from repro.models import Model
    model = Model(TINY)
    params = model.init(jax.random.key(0))
    loss, per_example, _ = make_task_fns(model, TaskSpec())
    space = random_mask(params, density=1e-2, seed=3, balanced=False)
    return model, params, loss, space


def _group_fn(loss, space, *, T: int, eps=1e-3, lr=5e-2, sharded=False):
    """The server's client-group body (``FederatedZO._batch_run_for``):
    per-client T-step local loops under ``jax.lax.map``."""
    import jax
    import jax.numpy as jnp

    from repro.core import zo
    run = zo.make_local_run(loss, space, eps, lr, n_dirs=1, sharded=sharded)

    def group(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(lambda b: run(params, keys, b, zeros), batches)

    return group


def build_fl_round() -> Built:
    """Unsharded ``FederatedZO`` round group: K=4 clients x T=2 local
    steps over the synthetic task."""
    import jax
    model, params, loss, space = _round_problem()
    K, T, b = 4, 2, 8
    group = _group_fn(loss, space, T=T)
    keys = jax.random.split(jax.random.key(2), T)
    batches = {"tokens": _tokens(K, T, b, 16),
               "label": _tokens(K, T, b) % 4}
    return Built(
        jax.jit(group), (params, keys, batches),
        meta=dict(dyn_dims={"K": K},
                  peak_bytes_budget=8 * MiB))    # measured ~2.9 MB


def build_fl_round_sharded() -> Built:
    """The sharded round: the same group body under
    ``FLShardPlan.shard_group`` on a 2x2 mesh (ZeRO-3 parameter gather at
    round entry, clients over the mesh batch axes).  Also runs one live
    ``FederatedZO`` round on the plan to cross-check ``CommLog``
    accounting against the protocol's 4*K*T*n_dirs bytes."""
    import jax

    if jax.device_count() < 4:
        raise ProgramSkip(
            "needs >= 4 devices (run `python -m repro.analysis`, which "
            "forces host devices before importing jax)")

    import numpy as np

    from repro.configs.base import FLConfig
    from repro.core import Client, FederatedZO
    from repro.data.partition import dirichlet_partition, subset
    from repro.data.synthetic import TaskSpec, sample_dataset
    from repro.sharding.fl import make_fl_plan
    model, params, loss, space = _round_problem()
    plan = make_fl_plan(spec="2x2")
    K, T, b = 4, 2, 8
    group = _group_fn(loss, space, T=T, sharded=True)
    keys = jax.random.split(jax.random.key(2), T)
    batches = {"tokens": _tokens(K, T, b, 16),
               "label": _tokens(K, T, b) % 4}
    fn = jax.jit(plan.shard_group(group, batches, K, out_ndims=(2, 2)))
    args = (plan.place_params(params), plan.place_replicated(keys),
            plan.place_client_batches(batches, K))

    # live round on the same plan: the protocol's byte accounting
    fl = FLConfig(n_clients=K, local_steps=T, lr=5e-2, eps=1e-3, seed=0)
    train = sample_dataset(TaskSpec(), 256, seed=1)
    parts = dirichlet_partition(train["label"], K, 0.5, seed=0)
    clients = [Client(k, subset(train, p), b) for k, p in enumerate(parts)]
    srv = FederatedZO(loss, params, space, fl, clients, plan=plan)
    srv.run_round()
    param_bytes = int(sum(np.prod(p.shape) * p.dtype.itemsize
                          for p in jax.tree.leaves(params)))
    return Built(
        fn, args,
        meta=dict(
            dyn_dims={"K": K},
            peak_bytes_budget=8 * MiB,           # measured ~3.3 MB
            comm=dict(
                param_bytes=param_bytes,
                # one ZeRO-3 gather of the weights per round body; 3x
                # covers the reverse scatter + async-pair double counting
                allgather_max_bytes=3 * param_bytes,
                # uplink-class traffic: deltas [K, n] + gs [K, T] + slop,
                # still ~100x under one model copy
                other_collective_max_bytes=8 * K * (space.n + T) + 2 ** 16,
                expected_up_bytes=4 * K * T * getattr(fl, "n_dirs", 1),
                commlog_up_bytes=int(srv.comm.up_bytes))))


def build_ckpt_roundtrip() -> Built:
    """The fault-tolerance save/restore round trip
    (``checkpoint/state.py``): a live ``FederatedZO`` server runs a
    round, snapshots, and restores into a fresh twin; the analyzed
    program is the round group *as driven by restored parameters*, so
    the rule sweep (dtype drift, host syncs, dense materialization,
    liveness) covers the resume path the kill-recover drill exercises.
    Restore fidelity is asserted here at build time — a checkpoint that
    loses bits must fail the sweep, not just the e2e drill."""
    import os
    import tempfile

    import jax
    import numpy as np

    from repro.configs.base import FLConfig
    from repro.core import Client, FederatedZO
    from repro.data.partition import dirichlet_partition, subset
    from repro.data.synthetic import TaskSpec, sample_dataset
    model, params, loss, space = _round_problem()
    K, T, b = 4, 2, 8
    fl = FLConfig(n_clients=K, local_steps=T, lr=5e-2, eps=1e-3, seed=0)
    train = sample_dataset(TaskSpec(), 256, seed=1)
    parts = dirichlet_partition(train["label"], K, 0.5, seed=0)

    def mk():
        clients = [Client(k, subset(train, p), b)
                   for k, p in enumerate(parts)]
        return FederatedZO(loss, params, space, fl, clients)

    srv = mk()
    srv.run_round()
    twin = mk()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        srv.save_checkpoint(path)
        twin.load_checkpoint(path)
    for a, c in zip(jax.tree.leaves(srv.params), jax.tree.leaves(twin.params)):
        if not np.array_equal(np.asarray(a), np.asarray(c)):
            raise AssertionError("checkpoint round trip lost parameter bits")

    group = _group_fn(loss, space, T=T)
    keys = jax.random.split(jax.random.key(2), T)
    batches = {"tokens": _tokens(K, T, b, 16),
               "label": _tokens(K, T, b) % 4}
    return Built(
        jax.jit(group), (twin.params, keys, batches),
        meta=dict(dyn_dims={"K": K},
                  peak_bytes_budget=8 * MiB))     # same body as fl_round


def build_prefill() -> Built:
    """``models/decode.prefill`` — the serving admission path: right-
    padded B=2 prompt batch with per-row lengths at S=320."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode as D
    cfg, model, params, _ = _tiny_lm()
    ctx = model.ctx

    def fn(p, batch, lengths):
        return D.prefill(p, batch, cfg, ctx, S_max=S, lengths=lengths)

    batch = {"tokens": _tokens(2, S)}
    lengths = jnp.asarray([S, 200], jnp.int32)
    return Built(
        jax.jit(fn), (params, batch, lengths),
        meta=dict(seq_threshold=S, dyn_dims={"S": S},
                  peak_bytes_budget=24 * MiB))   # measured ~11 MB


def build_decode_burst() -> Built:
    """The continuous-batching engine's compiled decode burst
    (``ContinuousBatchingEngine._decode_fn``), tailed variant: 4 steps
    over 2 slots against an S_max=320 cache — the steady-state serving
    inner loop."""
    import jax.numpy as jnp

    from repro.serving.engine import ContinuousBatchingEngine
    cfg, model, params, _ = _tiny_lm()
    eng = ContinuousBatchingEngine(model, params, max_slots=2, S_max=S,
                                   bucket=16)
    fn = eng._decode_fn(4, True)
    remaining = jnp.asarray([3, 2], jnp.int32)
    return Built(
        fn, (params, eng.last_logits, eng.cache, remaining),
        meta=dict(seq_threshold=S, dyn_dims={"S_max": S},
                  peak_bytes_budget=8 * MiB))    # measured ~3.8 MB


def build_first_order() -> Built:
    """``train/first_order.make_train_step`` — the backprop baseline the
    roofline compares against (and the mask-calibration gradient path)."""
    from repro.train.first_order import make_train_step
    cfg, model, params, _ = _tiny_lm()
    init, step = make_train_step(lambda p, b: model.loss(p, b), lr=1e-3)
    batch = {"tokens": _tokens(2, S)}
    return Built(
        step, (params, init(params), batch),
        # measured ~17.3 MB now that the grad trace routes through the
        # flash-attention kernel's recompute-based VJP (O(S*dh) residuals:
        # only O and the per-row logsumexp survive the forward).  The old
        # differentiable-online route stacked blockwise score residuals in
        # its scan-over-blocks VJP — ~186 MB at these shapes, the pattern
        # the memory-ceiling bad fixture now pins down — so this budget
        # both gates the baseline from silently growing and proves the
        # recompute backward holds the paper's ZO-memory comparison honest.
        meta=dict(seq_threshold=S, dyn_dims={"S": S},
                  peak_bytes_budget=36 * MiB))


HOT_PATHS = (
    Program("zo_train_loop",
            "fl_step.make_fl_train_loop: jitted T=1 MEERKAT burst",
            build_zo_train_loop),
    Program("fl_round",
            "FederatedZO round group (lax.map clients), unsharded",
            build_fl_round),
    Program("fl_round_sharded",
            "FederatedZO round group under FLShardPlan.shard_group (2x2)",
            build_fl_round_sharded),
    Program("ckpt_roundtrip",
            "checkpoint save/restore round trip driving the round group",
            build_ckpt_roundtrip),
    Program("prefill",
            "models/decode.prefill: right-padded serving admission",
            build_prefill),
    Program("decode_burst",
            "ContinuousBatchingEngine._decode_fn: compiled decode burst",
            build_decode_burst),
    Program("first_order",
            "train/first_order.make_train_step: backprop baseline",
            build_first_order),
)


def programs_by_name(names: Optional[List[str]] = None) -> List[Program]:
    table = {p.name: p for p in HOT_PATHS}
    if names is None:
        return list(HOT_PATHS)
    missing = [n for n in names if n not in table]
    if missing:
        raise KeyError(f"unknown program(s) {missing}; "
                       f"have {sorted(table)}")
    return [table[n] for n in names]
