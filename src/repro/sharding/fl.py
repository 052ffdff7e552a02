"""Sharding plan for the federated ZO round (the mesh route of
``core/server.FederatedZO`` and ``core/fl_step``).

The round's distributed layout is deliberately simple, because the MEERKAT
step has no backward pass and its only cross-client communication is
scalar aggregation (the paper's point):

* **clients** (the leading ``[K]`` axis of every stacked batch) shard over
  the mesh batch axes — ``('pod', 'data')`` under ``rule="tp"``, the
  *whole* mesh under the default ``rule="fsdp"`` (ZO has no tensor
  parallelism to spend the ``'model'`` axis on, so it too becomes a
  client shard; rules.py docstring).  Pure data parallelism: each device
  runs its clients' full T-step local loops.
* **parameters** shard per ``sharding/rules.py``.  The default rule is
  ``"fsdp"`` (:func:`repro.sharding.rules.fsdp_only_specs`): every weight
  leaf is sharded over *all* mesh axes on its largest divisible dim and
  GSPMD all-gathers it at the point of use.  ZO runs no backward, so
  Megatron tensor parallelism (``rule="tp"``,
  :func:`repro.sharding.rules.param_specs`) only buys per-layer activation
  all-reduces the round does not need — and, crucially, row-parallel TP
  splits matmul contraction dims, which changes float summation order and
  breaks *bit* parity with the single-device path (DESIGN.md §9).  FSDP
  keeps every per-client matmul whole, so the sharded round is
  bit-identical to the unsharded one; the parity suite
  (``tools/fl_mesh_parity.py``) pins this down.
* **scalars** — per-step PRNG keys, the uploaded projected gradients
  ``g_k^t``, GradIP trajectories and the aggregated sparse update — stay
  replicated / host-side.  The server-side virtual-path replay therefore
  consumes bit-identical inputs regardless of mesh shape, which is why
  seed-replay reconstruction stays exact under sharding.

``FLShardPlan`` carries the mesh + rule and places concrete arrays;
``core/server.FederatedZO`` accepts one via ``plan=``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax

from repro.configs.base import MeshConfig
from repro.sharding.rules import fsdp_only_specs, param_specs

P = jax.sharding.PartitionSpec

PARAM_RULES = ("fsdp", "tp", "replicate")


@dataclasses.dataclass(frozen=True)
class FLShardPlan:
    """How one federated round maps onto a device mesh.

    ``mesh``     — a ``jax.sharding.Mesh`` (see ``launch/mesh.py``).
    ``mesh_cfg`` — its :class:`MeshConfig` (axis sizes/names).
    ``rule``     — parameter sharding rule: ``"fsdp"`` (default,
    bit-exact vs single device), ``"tp"`` (Megatron specs from
    ``rules.param_specs`` — allclose, not bit-exact: row-parallel
    contractions reorder float sums), or ``"replicate"``.
    """
    mesh: Any
    mesh_cfg: MeshConfig
    rule: str = "fsdp"

    def __post_init__(self):
        if self.rule not in PARAM_RULES:
            raise ValueError(
                f"rule must be one of {PARAM_RULES}, got {self.rule!r}")

    # -- basic wrappers ------------------------------------------------------
    @property
    def batch_axes(self):
        """Mesh axes acting as the FL-client/data axis.

        Under fleet-scale client sampling (DESIGN.md §12) this axis
        spans the round's **sampled cohort** (``m`` clients), not the
        full fleet ``K`` — divisibility and shard widths are governed by
        the cohort size the server actually runs per round.

        ``"fsdp"`` / ``"replicate"`` run no tensor parallelism, so *every*
        mesh axis is a data shard (the dry-run's ``zo_dp`` layout;
        rules.py docstring) — this is also what keeps the round bit-exact:
        no mesh axis ever splits a matmul contraction.  ``"tp"`` reserves
        the ``'model'`` axis for Megatron TP and shards clients over
        ``('pod', 'data')`` only."""
        if self.rule == "tp":
            return self.mesh_cfg.batch_axes
        return tuple(self.mesh_cfg.axis_names)

    @property
    def dp(self) -> int:
        """Data-parallel width: product of :attr:`batch_axes` sizes."""
        n = self.mesh_cfg.data * self.mesh_cfg.pods
        if self.rule != "tp":
            n *= self.mesh_cfg.model
        return n

    def named(self, spec: P) -> jax.sharding.NamedSharding:
        return jax.sharding.NamedSharding(self.mesh, spec)

    def replicated(self) -> jax.sharding.NamedSharding:
        return self.named(P())

    # -- parameter placement -------------------------------------------------
    def param_specs(self, params):
        """PartitionSpec pytree for ``params`` under :attr:`rule`."""
        if self.rule == "replicate":
            return jax.tree.map(lambda l: P(*([None] * l.ndim)), params)
        fn = fsdp_only_specs if self.rule == "fsdp" else param_specs
        return fn(None, params, self.mesh_cfg)

    def param_shardings(self, params):
        return jax.tree.map(self.named, self.param_specs(params),
                            is_leaf=lambda x: isinstance(x, P))

    def place_params(self, params):
        """Commit a concrete parameter pytree to the mesh per the rule."""
        return jax.device_put(params, self.param_shardings(params))

    def shard_group(self, body, template_batches, n_clients: int,
                    out_ndims=(2, 2)):
        """Wrap a client-group function in ``shard_map`` over this mesh.

        ``body(params, keys, batches) -> (deltas [K, n], gs [K, T, ...])``
        must process its clients with ``jax.lax.map`` (unbatched slices) —
        under ``shard_map`` each device then runs the *identical*
        per-client program on its slice of the client axis, which is what
        makes the sharded round bit-exact: no GSPMD cost-model choices, no
        batch-width-dependent matmul kernels (DESIGN.md §9).

        Parameters enter with ``in_specs=P()`` — the explicit ZeRO-3
        gather: stored FSDP-sharded between rounds, all-gathered once at
        round-body entry, amortized over the T local steps.  ``keys``
        replicate.  The client axis of ``batches`` and of both outputs
        shards over :attr:`batch_axes` when ``n_clients`` divides; a
        ragged fleet replicates (every device runs all clients).

        ``template_batches``: the stacked batch dict (for leaf ranks);
        ``out_ndims``: ranks of the (deltas, gs) outputs."""
        k_spec = self.batch_axes if n_clients % self.dp == 0 else None

        def kspec(ndim):
            return P(k_spec, *([None] * (ndim - 1)))

        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(None),
                      {k: kspec(v.ndim)
                       for k, v in template_batches.items()}),
            out_specs=tuple(kspec(nd) for nd in out_ndims),
            check_vma=False)

    def compute_view(self, params):
        """The in-graph view of the (sharded-at-rest) parameters that the
        vmapped client group computes with.

        ``"fsdp"``/``"replicate"``: constrain to replicated — ZeRO-3
        semantics, one all-gather of the weights per round body, amortized
        over the T local steps and 2T forwards.  This is what makes the
        sharded round *bit-exact*: left to its own cost model, GSPMD may
        instead split a matmul over an FSDP-sharded contraction dim
        (partial sums + all-reduce), which reorders float accumulation
        (DESIGN.md §9).  ``"tp"``: constrain to the Megatron specs —
        compute stays tensor-parallel (allclose-level parity only)."""
        if self.rule == "tp":
            specs = self.param_specs(params)
        else:
            specs = jax.tree.map(lambda l: P(*([None] * l.ndim)), params)
        return jax.tree.map(
            lambda a, s: jax.lax.with_sharding_constraint(a, self.named(s)),
            params, specs)

    def constrain_params_fn(self):
        """``params -> params`` re-applying the plan's weight shardings.

        For the non-vmapped production steps (``fl_step.make_fl_train_step``
        / ``make_fl_train_loop``): the sparse scatter erases GSPMD's weight
        shardings, so the step re-constrains after every perturb/update
        (DESIGN.md §perf)."""
        def cp(params):
            return jax.tree.map(
                lambda a, s: jax.lax.with_sharding_constraint(
                    a, self.named(s)),
                params, self.param_specs(params))
        return cp

    # -- batch placement -----------------------------------------------------
    def client_batch_spec(self, n_clients: int, ndim: int) -> P:
        """Spec for one stacked client-batch leaf ``[K, T, b, ...]``.

        The client axis ``K`` shards over :attr:`batch_axes` when
        divisible; otherwise the batch replicates (a ragged client fleet
        still runs, just without the data-parallel split)."""
        k_spec = self.batch_axes if n_clients % self.dp == 0 else None
        return P(k_spec, *([None] * (ndim - 1)))

    def place_client_batches(self, batches, n_clients: int):
        """Commit a stacked batch dict (leaves ``[K, T, b, ...]``) to the
        mesh, client axis over :attr:`batch_axes`."""
        return {k: jax.device_put(
                    v, self.named(self.client_batch_spec(n_clients, v.ndim)))
                for k, v in batches.items()}

    def place_replicated(self, x):
        """Commit an array (PRNG keys, scalars) replicated on the mesh."""
        return jax.device_put(x, self.replicated())

    # -- model context -------------------------------------------------------
    def shard_ctx(self, base_ctx):
        """A ``ShardCtx`` carrying this plan's mesh + batch axes, so model
        forwards apply their activation sharding constraints and
        ``resolve_attn_backend`` sees the sharded-mesh layout.

        Under ``"fsdp"``/``"replicate"`` the ``'model'`` axis is folded
        into ``batch_axes`` (``ShardCtx.attn_head_spec`` then emits no
        tensor-parallel activation specs), so no constraint ever splits a
        contraction dim — the bit-exactness invariant of DESIGN.md §9."""
        return dataclasses.replace(base_ctx, mesh=self.mesh,
                                   batch_axes=self.batch_axes)


def make_fl_plan(mesh_cfg: Optional[MeshConfig] = None, *,
                 spec: Optional[str] = None,
                 rule: str = "fsdp") -> FLShardPlan:
    """Build an :class:`FLShardPlan` from a :class:`MeshConfig` or a CLI
    mesh spec string (``"2x2"``; see ``launch/mesh.parse_mesh_spec``).

    The process must already have enough devices — on CPU hosts that means
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` was exported
    before the first jax import."""
    from repro.launch.mesh import make_mesh_from_config, parse_mesh_spec
    if (mesh_cfg is None) == (spec is None):
        raise ValueError("pass exactly one of mesh_cfg= or spec=")
    if mesh_cfg is None:
        mesh_cfg = parse_mesh_spec(spec)
    return FLShardPlan(make_mesh_from_config(mesh_cfg), mesh_cfg, rule)
