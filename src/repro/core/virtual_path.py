"""Server-side virtual-path reconstruction (paper Alg. 2 step 2).

Because the server holds the round's seed list and receives each client's
projected gradients ``{g_k^t}``, it can regenerate every ``z_t`` and replay
the client's local trajectory *exactly* — without any client data.  Since
updates only touch the masked coordinates, the server tracks the sparse
value vector (delta) instead of full weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def reconstruct_delta(space, keys, gs, lr: float, delta0=None):
    """Replay T local steps. gs: [T] (paper) or [T, K] (multi-direction ZO,
    K scalars per step); keys: [T]. Returns delta_T [n]."""
    if delta0 is None:
        delta0 = jnp.zeros((space.n,), jnp.float32)
    multi = gs.ndim == 2

    def step(delta, inp):
        key, g = inp
        if multi:
            dir_keys = jax.random.split(key, g.shape[0])
            zs = jax.vmap(space.sample_z)(dir_keys)
            upd = (g[:, None] * zs).mean(0)
        else:
            upd = g * space.sample_z(key)
        return delta - lr * upd, None

    delta_T, _ = jax.lax.scan(step, delta0, (keys, gs))
    return delta_T


def reconstruct_from_wire(space, keys, wire, codec, lr: float, delta0=None):
    """Replay a client's local trajectory directly from its **encoded
    uplink payload** — the fleet-scale server's entire per-client
    knowledge is (seed keys, wire bytes).

    In exact-replay mode (``core/quantize.py``: the client applies the
    wire-grid value at every local step, and on-grid values survive the
    codec bit-for-bit) ``codec.decode(wire)`` returns exactly the
    scalars the client's trajectory used, so this reconstruction is
    bit-identical to the client-side path even though only quantized
    bytes crossed the network."""
    return reconstruct_delta(space, keys,
                             jnp.asarray(codec.decode(wire), jnp.float32),
                             lr, delta0)


def reconstruct_grad_vecs(space, keys, gs):
    """The reconstructed ZO gradient vectors grad_hat_t = g_t * z_t.

    Returned as [T, n] (sparse-coordinate representation)."""

    def one(key, g):
        return g * space.sample_z(key)

    return jax.vmap(one)(keys, gs)


def aggregate(deltas, n_reporting=None):
    """FedAvg aggregation of reconstructed sparse client deltas: [K, n].

    ``n_reporting`` makes the normalization explicit for fault-tolerant
    rounds (FedMeZO-style: the mean is over whichever subset actually
    reported, so aggregation stays well-defined under client dropout).
    It defaults to ``deltas.shape[0]`` — plain FedAvg over the rows
    given — and must match it unless a caller deliberately rescales
    (e.g. normalizing by the full fleet to damp partial rounds).  A
    zero-survivor round has no rows to average: callers apply a zero
    update instead of calling this with an empty stack.

    Inside a jitted program ``n_reporting`` may be a traced operand (the
    server's update passes it so, not as a constant that the compiler
    could fold); the caller then keeps it positive."""
    n = deltas.shape[0] if n_reporting is None else n_reporting
    if deltas.shape[0] == 0 or (not isinstance(n, jax.core.Tracer)
                                and int(n) <= 0):
        raise ValueError(
            f"aggregate needs >= 1 reporting client (got rows="
            f"{deltas.shape[0]}, n_reporting={n_reporting}); zero-survivor "
            "rounds apply a zero update instead")
    return jnp.sum(deltas, axis=0) / n
