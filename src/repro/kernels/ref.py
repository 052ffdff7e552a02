"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gradip_reduce_ref(gp, z, g):
    return jnp.asarray(g, jnp.float32) * jnp.sum(
        gp.astype(jnp.float32) * z.astype(jnp.float32))


def mamba_scan_ref(dt, B_in, C_in, x, A):
    """Serial selective-scan oracle.  dt, x: [B,S,E]; B_in, C_in: [B,S,N];
    A: [E,N] -> (y [B,S,E], h_last [B,E,N])."""
    B, S, E = dt.shape
    N = B_in.shape[-1]

    def step(h, inp):
        dt_t, b_t, c_t, x_t = inp
        decay = jnp.exp(dt_t[..., None] * A)              # [B,E,N]
        h = decay * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("ben,bn->be", h, c_t)
        return h, y

    h0 = jnp.zeros((B, E, N), jnp.float32)
    xs = (dt.swapaxes(0, 1), B_in.swapaxes(0, 1), C_in.swapaxes(0, 1),
          x.swapaxes(0, 1))
    h_last, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), h_last


def decode_attention_ref(q, k, v, length, softcap: float = 0.0):
    """q: [B,KVH,G,dh]; k,v: [B,S,KVH,dh]; softmax over positions < length.

    ``length`` is a scalar or per-row [B] (continuous-batching slots)."""
    B, KVH, G, dh = q.shape
    S = k.shape[1]
    scale = dh ** -0.5
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    L = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    mask = jnp.arange(S)[None, None, None, :] < L[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)
