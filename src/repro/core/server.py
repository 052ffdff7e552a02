"""Federated orchestration: MEERKAT (Alg. 2), high-frequency MEERKAT (Alg. 3),
MEERKAT-VP (Alg. 1) and the baselines (Full-FedZO, weight-magnitude mask,
random mask, LoRA-FedZO, random-early-stop).

The server *never* sees client data: it receives only projected-gradient
scalars and replays virtual paths from the shared seed ladder.  For
simulation speed, clients with the same local-step count T are executed as a
single vmapped jit call; the *aggregated update is always computed from the
server-side virtual-path reconstruction* of the uploaded scalars (exactness
vs the client-side trajectory is unit-tested).

**Mesh route** (``plan=``, a :class:`repro.sharding.fl.FLShardPlan`): the
same round executes sharded on a device mesh — parameters per
``sharding/rules.py`` (FSDP by default), the vmapped client axis over the
``('pod','data')`` batch axes.  Everything the virtual-path replay consumes
(seed keys, the [K, T] scalars, GradIP inputs) is gathered to host first,
so reconstruction, aggregation, GradIP trajectories and VPCS decisions are
bit-identical to the single-device path (DESIGN.md §9; parity-tested by
``tools/fl_mesh_parity.py``).

**Fault tolerance** (DESIGN.md §11): ``run_round(faults=)`` tolerates
clients dropping (aggregate over survivors) and straggling (bounded
staleness, seed-replayed exactly at arrival), and
``save_checkpoint``/``load_checkpoint`` snapshot/restore the complete
server state for bit-exact resume after a kill — including across mesh
shapes.  Deterministic fault schedules come from
``repro.fault.FaultPlan``.

**Fleet scale** (DESIGN.md §12): with ``fl.sample_frac < 1`` each round
runs a seeded fixed-size cohort (``core/sampling.ClientSampler``; fault
events restrict to the sampled cohort, unsampled clients get explicit
GradIP gaps), and ``fl.quantize`` routes the scalar uplink through the
``core/quantize`` codec — clients apply the wire-grid values in-loop
(exact replay), so the server reconstructs virtual paths from the
*dequantized* upload bit-exactly.  Server state stays O(seeds + scalars)
in the fleet size K: parameters + per-client scalars only, never
K x model (``checkpoint/state.server_state_sizes`` accounts it).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import FLConfig
from repro.core import seeds as S
from repro.core import virtual_path as VP
from repro.core import vpcs as VPCS
from repro.core import zo as ZO
from repro.core.gradip import gradip_trajectory
from repro.core.quantize import make_codec
from repro.core.sampling import ClientSampler


class Client:
    """Holds a local dataset and a data pointer (paper §2.5: flagged clients
    resume from where they stopped so all data is eventually used).

    ``data``: dict of equally-long numpy arrays (leading dim = examples);
    ``batch_size``: examples per local step."""

    def __init__(self, cid: int, data: Dict[str, np.ndarray], batch_size: int):
        self.cid = cid
        self.data = data
        self.batch_size = batch_size
        self.ptr = 0
        self.n = len(next(iter(data.values())))

    def next_batches(self, T: int):
        """Stack of T batches — each value [T, batch_size, ...] — advancing
        the pointer with wraparound."""
        idx = (self.ptr + np.arange(T * self.batch_size)) % self.n
        self.ptr = int((self.ptr + T * self.batch_size) % self.n)
        sel = {k: v[idx] for k, v in self.data.items()}
        return {k: v.reshape(T, self.batch_size, *v.shape[1:])
                for k, v in sel.items()}


def _per_step(g: np.ndarray) -> np.ndarray:
    """Reduce a client's uploaded scalars to one per local step: [T] stays
    [T]; multi-direction [T, K] averages over K (the K directions estimate
    the same step gradient, so their mean is the step's GradIP scalar)."""
    g = np.asarray(g)
    return g.mean(axis=1) if g.ndim > 1 else g


@dataclass
class CommLog:
    """Cumulative FL protocol traffic in **bytes** (f32 scalars = 4 B each;
    seeds = 8 B).  Counts the paper's client<->server payloads only —
    intra-mesh collective traffic is measured separately from compiled HLO
    (``benchmarks/fl_scale_bench.py``)."""
    up_bytes: int = 0
    down_bytes: int = 0

    def add(self, up: int, down: int):
        self.up_bytes += int(up)
        self.down_bytes += int(down)


class FederatedZO:
    """Generic sparse-ZO FL server; the ``space`` argument selects the method
    (MEERKAT sensitivity mask / magnitude / random / dense / LoRA).

    Args:
      loss_fn: scalar client loss ``(params, batch) -> f32`` (mean over the
        batch).
      params: initial parameter pytree.  With ``plan`` set it is committed
        to the mesh per the plan's rule at construction.
      space: coordinate space (``core/spaces.py``) — defines ``n``, z
        sampling, and the sparse scatter.
      fl: :class:`FLConfig` hyper-parameters.
      clients: the client fleet (``Client`` instances).
      eval_fn: optional jitted ``(params, batch) -> {metric: f32}``.
      high_freq: force Alg. 3 downlink accounting; default T==1.
      plan: optional :class:`repro.sharding.fl.FLShardPlan` — run every
        client group sharded on the plan's mesh (see module docstring).
      sampler: optional :class:`repro.core.sampling.ClientSampler`
        override; by default one is built from ``fl.sample_frac < 1``
        (seeded with ``fl.seed``, weighted by client data size when
        ``fl.sample_weighted``).  ``None`` with ``sample_frac == 1``
        runs the whole fleet every round (today's dense protocol).
      codec: optional uplink codec override (``core/quantize.py``); by
        default built from ``fl.quantize`` (``"none"`` = raw f32)."""

    def __init__(self, loss_fn: Callable, params, space, fl: FLConfig,
                 clients: Sequence[Client], eval_fn: Optional[Callable] = None,
                 high_freq: Optional[bool] = None, plan=None, sampler=None,
                 codec=None):
        self.loss_fn = loss_fn
        self.space = space
        self.fl = fl
        self.plan = plan
        self.params = params if plan is None else plan.place_params(params)
        self.clients = list(clients)
        self.eval_fn = eval_fn
        self.high_freq = fl.local_steps == 1 if high_freq is None else high_freq
        self.codec = codec if codec is not None else make_codec(
            getattr(fl, "quantize", "none"))
        if sampler is None:
            frac = float(getattr(fl, "sample_frac", 1.0))
            if frac < 1.0:
                weights = ([c.n for c in self.clients]
                           if getattr(fl, "sample_weighted", False) else None)
                sampler = ClientSampler([c.cid for c in self.clients],
                                        frac=frac, weights=weights,
                                        seed=fl.seed)
        self.sampler = sampler
        self.comm = CommLog()
        self.round = 0
        self.history: List[Dict[str, Any]] = []
        self.early_stopped: set = set()
        self.velocity = None  # FedAvgM server momentum state (beyond-paper)
        self.gradip_log: Dict[int, list] = {c.cid: [] for c in self.clients}
        # straggler uploads in flight: dicts of (arrive, cid, src_round,
        # gip_idx, gs) — part of the checkpointed state (DESIGN.md §11)
        self._pending: List[dict] = []
        self.last_round_info: Optional[dict] = None
        self._batch_runs: Dict[tuple, Callable] = {}
        # (T, group width) -> abstract (shape, dtype, sharding) arguments
        # of that group program's first call (``group_hlo_text``)
        self._group_args: Dict[tuple, tuple] = {}
        # (T, group width) -> the ZO route its program runs (there is one,
        # "ref"), and the host seconds of each round ``run`` completed
        self.zo_routes: Dict[tuple, str] = {}
        self.round_seconds: List[float] = []
        # per-client GradIP [T_cali] of the last calibrate_vp call
        self.vp_trajectories: Optional[List[np.ndarray]] = None

        def replay(keys, gs):
            return jax.vmap(lambda g: VP.reconstruct_delta(
                self.space, keys, g, self.fl.lr))(gs)

        self._recon = jax.jit(replay)
        # the round's device program after the replay (aggregate, then
        # update), and the space's index operands: a MaskedSpace's
        # per-leaf indices, committed once beside the parameters; the
        # dense spaces hold none
        self._update = jax.jit(self._aggregate_update)
        self._idx = (jax.device_put(space.idx_tree) if plan is None
                     else plan.place_replicated(space.idx_tree))

    # -- jitted T-step client group (one compile per distinct
    # (T, group width)) ----------------------------------------------
    def _batch_run_for(self, T: int, n_group: int, template_batches=None):
        """Jitted ``(params, keys [T], batches [K, T, b, ...]) ->
        (deltas [K, n], gs [K, T] or [K, T, n_dirs])`` for a group of
        ``n_group`` same-T clients.

        Clients are processed with ``jax.lax.map`` — each client's T-step
        loop runs as an *unbatched* program, so the per-client bits are
        independent of group width and of how the client axis is sharded
        (the mesh-parity invariant; DESIGN.md §9).  Under a ``plan`` the
        group is wrapped in ``shard_map`` (``FLShardPlan.shard_group``):
        client axis over the mesh batch axes, parameters gathered at round
        entry.  ``rule="tp"`` instead keeps GSPMD tensor-parallel compute
        (``compute_view``) — allclose-level parity only."""
        key = (T, n_group)
        if key not in self._batch_runs:
            obs.count("fl.programs_built")
            self.zo_routes[key] = "ref"
            # only rule "tp" runs the loop on GSPMD-sharded leaves; under
            # shard_group each device holds its clients' whole weights
            run = ZO.make_local_run(self.loss_fn, self.space, self.fl.eps,
                                    self.fl.lr,
                                    n_dirs=getattr(self.fl, "n_dirs", 1),
                                    sharded=(self.plan is not None
                                             and self.plan.rule == "tp"),
                                    quantize=self.codec.jax_spec())

            def group(params, keys, batches):
                zeros = jnp.zeros((self.space.n,), jnp.float32)
                return jax.lax.map(lambda b: run(params, keys, b, zeros),
                                   batches)

            if self.plan is None:
                self._batch_runs[key] = jax.jit(group)
            elif self.plan.rule == "tp":
                def group_tp(params, keys, batches):
                    return group(self.plan.compute_view(params), keys,
                                 batches)
                self._batch_runs[key] = jax.jit(group_tp)
            else:
                n_dirs = getattr(self.fl, "n_dirs", 1)
                self._batch_runs[key] = jax.jit(self.plan.shard_group(
                    group, template_batches, n_group,
                    out_ndims=(2, 3 if n_dirs > 1 else 2)))
        return self._batch_runs[key]

    def _aggregate_update(self, params, idx_tree, deltas, n_report,
                          velocity):
        """``(params, idx_tree, deltas [K, n], n_report, velocity) ->
        (leaves, velocity)``: the mean of the replayed deltas over the
        reporting count (a zero update when ``K`` is 0), FedAvgM momentum
        when ``fl.server_momentum`` is set, then ``space.add`` at the
        space's coordinates, all in one program.  It returns only the
        leaves the space holds coordinates in (``space.sizes``), so no
        other leaf is copied; the leaves it does not read are pruned from
        its arguments.

        The indices and the count are operands, never constants: the
        compiler cannot fold them, so the bits equal ``VP.aggregate`` and
        ``space.add`` run op by op.  One trace per distinct ``K`` (and
        once more when momentum's velocity first exists); the counter
        ``fl.update_programs`` counts the traces."""
        obs.count("fl.update_programs")
        agg = (VP.aggregate(deltas, n_report) if deltas.shape[0]
               else jnp.zeros((self.space.n,), jnp.float32))
        if self.fl.server_momentum > 0.0:
            velocity = (agg if velocity is None
                        else self.fl.server_momentum * velocity + agg)
            agg = velocity
        new = jax.tree.leaves(self.space.add(params, agg, idx_tree))
        return [l for l, m in zip(new, self.space.sizes) if m], velocity

    def _client_T(self, cid: int) -> int:
        return 1 if cid in self.early_stopped else self.fl.local_steps

    def _cohort(self, r: int) -> tuple:
        """Participating client ids for round ``r``: the whole fleet
        without a sampler, else the sampler's seeded draw — sorted and
        of fixed size, so every round reuses one compiled group program
        (the cohort is data, not shape)."""
        if self.sampler is None:
            return tuple(c.cid for c in self.clients)
        return self.sampler.cohort(r)

    @staticmethod
    def _stack(batch_list):
        return {k: jnp.asarray(np.stack([b[k] for b in batch_list]))
                for k in batch_list[0]}

    def _place_group(self, keys, batches, n_group: int):
        """Mesh route: commit the group's inputs — keys replicated, the
        stacked batches' client axis over ('pod','data')."""
        if self.plan is None:
            return keys, batches
        return (self.plan.place_replicated(keys),
                self.plan.place_client_batches(batches, n_group))

    def _call_group(self, key, grp, keys_d, batches):
        """Dispatch a group program; the abstract arguments of its first
        call (a committed argument's sharding too) are kept for
        :meth:`group_hlo_text`."""
        if key not in self._group_args:
            self._group_args[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding
                    if getattr(x, "committed", False) else None),
                (self.params, keys_d, batches))
        return grp(self.params, keys_d, batches)

    def group_hlo_text(self, T: int, width: int) -> str:
        """Optimized HLO text of the (T, width) client group program as the
        device runs it: lowered with the abstract arguments of its first
        call, which finds the executable that call compiled.  Instructions
        carry ``core/zo.py``'s ``zo.*`` scopes in their ``op_name``
        metadata, so a trace's op names join to them.  Call it outside
        timed work: where the executable is not found, it compiles."""
        key = (T, width)
        return self._batch_runs[key].lower(
            *self._group_args[key]).compile().as_text()

    # -- one federated round (Alg. 2 + the failure model) --------------------
    def run_round(self, gp_vec=None, faults=None):
        """Execute one round: group clients by local-step count T, run each
        group's local ZO loops (vmapped; sharded under a ``plan``), account
        the scalar uploads, reconstruct every client's virtual path from
        (seed list, scalars) gathered to the host, then aggregate and apply
        the update in one device program (:meth:`_aggregate_update`).

        ``gp_vec`` ([n] pre-training gradient): also log each client's
        GradIP trajectory for this round.  Returns {cid: gs [T] or
        [T, n_dirs]} — the scalars each client uploaded *this round*.

        ``faults`` (a :class:`repro.fault.RoundFaults`) injects the
        failure model:

        * ``drops`` — offline clients: no local steps, no traffic, data
          pointer frozen, an explicit ``None`` gap in ``gradip_log``.
        * ``late`` (cid -> delay) — stragglers: they run this round's
          local steps on its seeds/data, but the scalar upload lands
          ``delay`` rounds later.  Because the seed ladder derives every
          key from ``(fl.seed, round, T)``, the server replays the stale
          virtual path bit-exactly at arrival (``VP.reconstruct_delta``
          with the *source* round's keys).  Uplink bytes are counted at
          arrival — ``CommLog`` records traffic when it happens.
        * ``kill`` — SIGKILL the server mid-round (after client compute,
          before the update applies): the preemption the checkpoint/
          resume path recovers from.

        With a sampler (``fl.sample_frac < 1``) only the round's seeded
        cohort participates: fault events restrict to the cohort
        (``RoundFaults.restrict``), unsampled clients run nothing, move
        no bytes, keep their data pointers, and get an explicit ``None``
        GradIP gap.  Every upload crosses the wire through
        ``self.codec``: the server bills the *encoded* byte count and
        stores/replays the *decoded* scalars — bit-identical to what the
        client applied locally (exact-replay quantization in
        ``core/zo.py``), so the virtual path stays reconstructible from
        the compressed uplink.

        The round aggregates over whoever actually reported — prompt
        survivors plus stragglers landing this round — via the
        survivor-count-aware :func:`VP.aggregate`; a zero-reporter round
        applies a zero update.  Diagnostics land in
        ``self.last_round_info``."""
        up, down = self.comm.up_bytes, self.comm.down_bytes
        with obs.span("fl.round", round=self.round) as sp:
            gs_by_cid = self._round(gp_vec, faults)
            sp.attrs.update(up_bytes=self.comm.up_bytes - up,
                            down_bytes=self.comm.down_bytes - down)
        return gs_by_cid

    def _round(self, gp_vec, faults):
        """:meth:`run_round`'s body, its phases in ``fl.*`` spans."""
        from repro.fault.plan import NO_FAULTS
        f = faults if faults is not None else NO_FAULTS
        r = self.round
        with obs.span("fl.inputs"):
            cohort = self._cohort(r)
            in_cohort = set(cohort)
            f = f.restrict(in_cohort)
        if gp_vec is not None:
            for c in self.clients:
                if c.cid not in in_cohort:
                    self.gradip_log[c.cid].append(None)  # unsampled gap
        groups: Dict[int, List[Client]] = {}
        for c in self.clients:
            if c.cid in in_cohort:
                groups.setdefault(self._client_T(c.cid), []).append(c)
        # deterministic grouping: sorted-T iteration below, and each cohort
        # client in exactly one group — resume replay and the mesh-parity
        # harness must never depend on dict insertion order or see a
        # client twice
        cids = [c.cid for cs in groups.values() for c in cs]
        assert len(cids) == len(in_cohort) == len(set(cids)), \
            "each cohort client must appear in exactly one T-group"
        deltas, gs_by_cid, arrived = [], {}, []
        for T in sorted(groups):
            if gp_vec is not None:
                for c in groups[T]:
                    if c.cid in f.drops:
                        self.gradip_log[c.cid].append(None)  # explicit gap
            cs = [c for c in groups[T] if c.cid not in f.drops]
            if not cs:
                continue
            with obs.span("fl.inputs"):
                keys = S.round_keys(self.fl.seed, r, T)
                batches = self._stack([c.next_batches(T) for c in cs])
                grp = self._batch_run_for(T, len(cs),
                                          template_batches=batches)
                keys_d, batches = self._place_group(keys, batches, len(cs))
            # (1) clients run T local ZO steps; upload the scalars g_k^{1..T}
            with obs.span("fl.group"):
                _, gs = self._call_group((T, len(cs)), grp, keys_d, batches)
            with obs.span("fl.group_wait"):
                gs = np.asarray(gs)
            # (2) server reconstructs each client's virtual path from
            #     (seed list, scalars) — no data, no dense vectors.  The
            #     scalars are gathered to host first so replay/aggregation
            #     run identically under any mesh shape (DESIGN.md §9).
            # uplink: every scalar block crosses the wire through the
            # codec; the *decoded* values are what the server stores,
            # bills and replays (identical to the client's applied
            # values — exact-replay quantization), and the billed bytes
            # are the encoded wire size
            with obs.span("fl.uplink"):
                wires = [self.codec.encode(g) for g in gs]
                gs = np.stack([self.codec.decode(w) for w in wires])
            prompt = [i for i, c in enumerate(cs) if c.cid not in f.late]
            if prompt:
                with obs.span("fl.replay"):
                    deltas.append(np.asarray(self._recon(
                        keys, jnp.asarray(gs[np.asarray(prompt)]))))
            with obs.span("fl.uplink"):
                for i, c in enumerate(cs):
                    g = gs[i]
                    if c.cid in f.late:
                        # straggler: the downlink happened (it participated),
                        # the upload is in flight until its arrival round
                        self.comm.add(up=0, down=self._down_bytes(T))
                        gip_idx = -1
                        if gp_vec is not None:
                            self.gradip_log[c.cid].append(None)
                            gip_idx = len(self.gradip_log[c.cid]) - 1
                        self._pending.append(dict(
                            arrive=r + int(f.late[c.cid]), cid=c.cid,
                            src_round=r, gip_idx=gip_idx, gs=g))
                        continue
                    gs_by_cid[c.cid] = g
                    # upload = every projected-gradient scalar block (T with
                    # n_dirs=1, T*K multi-direction) at the codec's wire size
                    self.comm.add(up=wires[i].nbytes, down=self._down_bytes(T))
                    if gp_vec is not None:
                        with obs.span("fl.gradip"):
                            ips, _, _ = gradip_trajectory(
                                self.space, keys, jnp.asarray(_per_step(g)),
                                gp_vec)
                            self.gradip_log[c.cid].append(np.asarray(ips))
        # (2b) stragglers landing this round: replay their virtual path with
        # the *source* round's seed keys — exact, because the seed ladder is
        # a pure function of (fl.seed, round, T); fill the GradIP gap logged
        # at the source round (deterministic order: by source round then cid)
        due = sorted((p for p in self._pending if p["arrive"] <= r),
                     key=lambda p: (p["src_round"], p["cid"]))
        self._pending = [p for p in self._pending if p["arrive"] > r]
        for p in due:
            gs_l = np.asarray(p["gs"])
            src_keys = S.round_keys(self.fl.seed, p["src_round"],
                                    gs_l.shape[0])
            with obs.span("fl.replay"):
                deltas.append(np.asarray(self._recon(
                    src_keys, jnp.asarray(gs_l[None]))))
            self.comm.add(up=self.codec.nbytes(gs_l.size), down=0)
            if gp_vec is not None and p["gip_idx"] >= 0:
                with obs.span("fl.gradip"):
                    ips, _, _ = gradip_trajectory(
                        self.space, src_keys, jnp.asarray(_per_step(gs_l)),
                        gp_vec)
                    self.gradip_log[p["cid"]][p["gip_idx"]] = np.asarray(ips)
            arrived.append((p["cid"], p["src_round"], gs_l))
        if f.kill:
            from repro.fault import plan as _fault_plan
            _fault_plan.kill_now()  # mid-round: work done, update not applied
        # (3) aggregate the reconstructed sparse updates of whoever reported
        # (+ optional FedAvgM server momentum — beyond-paper) and apply
        # them: one transfer of the [K, n] stack, then one program
        # (a zero-survivor round applies a zero update through it)
        with obs.span("fl.aggregate"):
            stack = (np.concatenate(deltas, axis=0) if deltas
                     else np.zeros((0, self.space.n), np.float32))
            n_report = int(stack.shape[0])
            stack = (jax.device_put(stack) if self.plan is None
                     else self.plan.place_replicated(stack))
        with obs.span("fl.update"):
            live, self.velocity = self._update(
                self.params, self._idx, stack, np.float32(n_report),
                self.velocity)
            leaves, treedef = jax.tree.flatten(self.params)
            live = iter(live)
            self.params = treedef.unflatten(
                [next(live) if m else l
                 for l, m in zip(leaves, self.space.sizes)])
        self.round += 1
        self.last_round_info = dict(
            round=r, n_reporting=n_report, drops=sorted(f.drops),
            late=dict(f.late), arrived=arrived,
            pending=len(self._pending), cohort=list(cohort),
            n_unsampled=len(self.clients) - len(cohort))
        return gs_by_cid

    def _down_bytes(self, T: int) -> int:
        """Per-client downlink bytes for a T-step round (Alg. 2/3)."""
        if self.high_freq:
            # aggregated scalars + next seed; with the K-direction
            # estimator clients replay mean_k g_tk * z_tk, so all T*K
            # per-direction scalars must come down (mirrors the uplink)
            return 4 * T * getattr(self.fl, "n_dirs", 1) + 8
        return 4 * self.space.n  # sparse (or dense/LoRA) model refresh

    # -- calibration + VPCS (MEERKAT-VP, Alg. 1) ----------------------------
    def calibrate_vp(self, gp_vec, T_cali: Optional[int] = None):
        """Run the calibration phase (round index -1 in the seed ladder),
        analyze GradIP trajectories, flag extreme Non-IID clients for
        early stopping.

        ``gp_vec``: [n] pre-training gradient at the space coordinates;
        ``T_cali``: calibration steps (default
        ``fl.vp_calibration_steps``).  Returns (results
        [:class:`repro.core.vpcs.VPCSResult` per client], flagged client
        id list, trajectories [list of GradIP [T_cali] arrays])."""
        with obs.span("fl.vp_calibration"):
            T = T_cali or self.fl.vp_calibration_steps
            keys = S.round_keys(self.fl.seed, -1, T)
            batches = self._stack([c.next_batches(T) for c in self.clients])
            width = len(self.clients)
            grp = self._batch_run_for(T, width, template_batches=batches)
            keys_d, batches = self._place_group(keys, batches, width)
            _, gs = self._call_group((T, width), grp, keys_d, batches)
            trajs = []
            for c, g in zip(self.clients, np.asarray(gs)):
                ips, _, _ = gradip_trajectory(
                    self.space, keys, jnp.asarray(_per_step(g)), gp_vec)
                trajs.append(np.asarray(ips))
                c.ptr = 0  # calibration does not consume training order
            results, flagged = VPCS.select_clients(trajs, self.fl)
            self.early_stopped = set(flagged)
            self.vp_trajectories = trajs
            return results, flagged, trajs

    def early_stop_random(self, n: int, seed: int = 0):
        """Random-client-selection baseline: early-stop n random clients."""
        rng = np.random.default_rng(seed)
        ids = rng.choice([c.cid for c in self.clients], size=n, replace=False)
        self.early_stopped = set(int(i) for i in ids)

    # -- fault tolerance: snapshot / restore ---------------------------------
    def save_checkpoint(self, path: str) -> str:
        """Atomically snapshot the full server state (params, velocity,
        round, CommLog, GradIP trajectories + gaps, VPCS flags, client
        data pointers, straggler queue, history) to ``path``
        (``checkpoint/state.py``; bit-exact resume, any mesh plan)."""
        from repro.checkpoint.state import save_server_state
        return save_server_state(path, self)

    def load_checkpoint(self, path: str) -> dict:
        """Restore a :meth:`save_checkpoint` snapshot into this server
        (config-fingerprint checked; params re-placed per this server's
        ``plan``, so the checkpoint may come from a different mesh
        shape).  Returns the checkpoint meta dict."""
        from repro.checkpoint.state import restore_server_state
        return restore_server_state(path, self)

    # -- training loop -------------------------------------------------------
    def evaluate(self, batch) -> Dict[str, float]:
        """``eval_fn`` on the current parameters, as host floats.

        Under a ``plan`` the parameters are replicated first: left
        FSDP-sharded, the compiler splits the eval matmuls over sharded
        contraction dims and the metrics differ from the single-device
        run in the last bits (the mesh-parity invariant, DESIGN.md §9)."""
        params = (self.params if self.plan is None
                  else self.plan.place_replicated(self.params))
        return {k: float(v) for k, v in self.eval_fn(params, batch).items()}

    def run(self, rounds: int, eval_every: int = 0, eval_batch=None,
            gp_vec=None, verbose: bool = False, fault_plan=None,
            checkpoint_dir=None, checkpoint_every: int = 0):
        """Run ``rounds`` federated rounds; evaluate every ``eval_every``
        rounds with ``eval_fn(params, eval_batch)``.  Returns the history
        list of metric dicts (each tagged with its round index).

        ``fault_plan`` (a :class:`repro.fault.FaultPlan`) injects that
        plan's per-round drop/late/kill events.  With ``checkpoint_dir``
        set, the server snapshot is written to
        ``<dir>/ckpt_latest.msgpack`` every ``checkpoint_every`` rounds
        (after eval, so the history is captured); cadence and eval use
        the *global* round index, so a resumed run checkpoints and
        evaluates on the same schedule as an uninterrupted one."""
        import os
        from repro.checkpoint.state import LATEST_NAME
        for _ in range(rounds):
            faults = (fault_plan.round_faults(self.round)
                      if fault_plan is not None else None)
            t0 = time.perf_counter()
            self.run_round(gp_vec=gp_vec, faults=faults)
            jax.block_until_ready(self.params)
            self.round_seconds.append(time.perf_counter() - t0)
            if eval_every and self.round % eval_every == 0 \
                    and self.eval_fn is not None:
                m = self.evaluate(eval_batch)
                m["round"] = self.round
                self.history.append(m)
                if verbose:
                    print(f"  round {self.round}: " +
                          " ".join(f"{k}={v:.4f}" for k, v in m.items()
                                   if k != "round"))
            if checkpoint_dir and checkpoint_every \
                    and self.round % checkpoint_every == 0:
                self.save_checkpoint(os.path.join(checkpoint_dir,
                                                  LATEST_NAME))
        return self.history
