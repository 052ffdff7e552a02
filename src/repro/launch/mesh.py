"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
does not touch jax device state.  The dry-run forces 512 host devices via
XLA_FLAGS *before* any jax import; real deployments get the same meshes from
actual TPU topologies.  ``parse_mesh_spec`` maps the CLI syntax shared by
``launch/train.py`` / ``benchmarks/fl_scale_bench.py`` /
``tools/fl_mesh_parity.py`` onto a :class:`MeshConfig`.
"""
from __future__ import annotations

import jax

from repro.configs.base import MeshConfig


def parse_mesh_spec(spec: str) -> MeshConfig:
    """CLI mesh spec -> :class:`MeshConfig`.

    Accepted forms:

    * ``"DxM"``     — single pod, D 'data' x M 'model' devices (``"2x2"``)
    * ``"PxDxM"``   — multi-pod, P 'pod' x D 'data' x M 'model' (``"2x16x16"``)
    * ``"single"``  — the production 16x16 single-pod mesh (256 chips)
    * ``"multi"``   — the production 2x16x16 multi-pod mesh (512 chips)

    ``"1x1"`` is a valid degenerate mesh (1 device) used by the parity
    tests as the smallest sharded configuration.
    """
    named = {"single": MeshConfig(data=16, model=16, pods=1),
             "multi": MeshConfig(data=16, model=16, pods=2)}
    if spec in named:
        return named[spec]
    parts = spec.split("x")
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}: want DxM, PxDxM, "
                         f"or one of {sorted(named)}")
    if len(dims) == 2:
        return MeshConfig(data=dims[0], model=dims[1], pods=1)
    if len(dims) == 3:
        return MeshConfig(pods=dims[0], data=dims[1], model=dims[2])
    raise ValueError(f"bad mesh spec {spec!r}: want 2 or 3 'x'-separated dims")


def host_device_flag(n_devices: int) -> str:
    """The XLA flag forcing ``n_devices`` host (CPU) devices.

    Must be placed in ``XLA_FLAGS`` *before* the first jax import —
    callers that accept ``--mesh`` pre-parse argv for exactly this reason
    (see ``launch/train.py``)."""
    return f"--xla_force_host_platform_device_count={n_devices}"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    return make_mesh_from_config(mesh_config(multi_pod=multi_pod))


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(data=16, model=16, pods=2 if multi_pod else 1)


def make_mesh_from_config(mc: MeshConfig):
    devices = jax.devices()[:mc.n_devices]
    if len(devices) < mc.n_devices:
        raise RuntimeError(
            f"need {mc.n_devices} devices for mesh {mc.shape}; have "
            f"{len(devices)}. Set XLA_FLAGS={host_device_flag(mc.n_devices)} "
            "before importing jax (see launch/dryrun.py).")
    # Auto axes: shardings are propagated by GSPMD from the committed
    # inputs.  Left unset, jax.make_mesh builds Explicit axes, under which
    # the round's sparse scatters raise ShardingTypeError.
    return jax.make_mesh(mc.shape, mc.axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(mc.shape),
                         devices=devices)
