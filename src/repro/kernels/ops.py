"""Jit'd public wrappers around the Pallas kernels.

On this CPU container kernels always run in interpret mode; on a real TPU
set ``interpret=False`` (the default flips on TPU platforms automatically).
The flat-vector helper pads/reshapes 1-D inputs into the (R, 128) tile
layout the GradIP kernel expects.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention as \
    flash_attention_kernel
from repro.kernels.gradip_reduce import LANE, SUB, gradip_reduce


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile(v, block_r: int):
    """Pad a flat [N] vector to [R, 128] with R % block_r == 0."""
    pad = (-v.shape[0]) % (LANE * block_r)
    return jnp.pad(v, (0, pad)).reshape(-1, LANE)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def gradip_flat(gp_flat, z_flat, g, *, block_r: int = 256,
                interpret: bool | None = None):
    """GradIP = g * <gp, z> over flat sparse-coordinate vectors."""
    interpret = _default_interpret() if interpret is None else interpret
    return gradip_reduce(_tile(gp_flat, block_r), _tile(z_flat, block_r), g,
                         block_r=block_r, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "softcap",
                                             "interpret"))
def flash_decode(q, k, v, length, *, block_s: int = 512, softcap: float = 0.0,
                 interpret: bool | None = None):
    """GQA flash-decode attention; see decode_attention.py for layout.

    ``length`` may be a scalar or per-row [B].  Cache lengths that are not a
    block multiple are zero-padded up to one (the pad positions sit at
    ``pos >= S >= length`` and are always masked), so model-shaped caches
    of any capacity route through the kernel."""
    interpret = _default_interpret() if interpret is None else interpret
    S = k.shape[1]
    bs = min(block_s, -(-S // SUB) * SUB)  # small caches: one sublane-tiled block
    pad = (-S) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return decode_attention(q, k, v, length, block_s=bs, softcap=softcap,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "causal",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, lengths=None, *, window: int = 0,
                    softcap: float = 0.0, causal: bool = True,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """GQA flash-attention (differentiable); see flash_attention.py for the
    kernels — ``jax.grad`` through this wrapper runs the recompute-based
    backward Pallas kernels.

    Model layout in, model layout out: q [B, S, H, hd]; k, v [B, S, KV, hd]
    -> [B, S, H, hd] (H = KV * G, head h in group h // G — the same order
    ``jnp.repeat(k, G, axis=2)`` produces in the dense route).

    ``block_q``/``block_k`` default to the measured winner in the
    ``kernels.autotune`` table for this (S, head_dim, G) on this platform
    (falling back to 128x128 when untuned); pass them explicitly to pin a
    launch grid.  The lookup happens at trace time, so the choice is baked
    into the jitted computation.

    ``lengths`` ([B] int32 or None) masks right-padded keys.  Sequence
    lengths that are not a block multiple are zero-padded up to one: padded
    keys sit at positions >= S >= lengths so they are always masked, and
    padded query rows are trimmed from the output."""
    interpret = _default_interpret() if interpret is None else interpret
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if block_q is None or block_k is None:
        from repro.kernels import autotune
        tuned = autotune.best_blocks(S, hd, G, op="fwd")
        block_q = block_q or (tuned[0] if tuned else 128)
        block_k = block_k or (tuned[1] if tuned else 128)
    # small sequences: one sublane-tiled block per axis (mirrors flash_decode)
    s8 = -(-S // SUB) * SUB
    bq = min(block_q, s8)
    bk = min(block_k, s8)
    per = bq * bk // math.gcd(bq, bk)  # lcm: the pad covers both block sizes
    pad = (-S) % per
    if pad:
        cfgpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, cfgpad)
        k = jnp.pad(k, cfgpad)
        v = jnp.pad(v, cfgpad)
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    else:
        lengths = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                             (B,)), S)
    Sp = S + pad
    qg = q.reshape(B, Sp, KV, G, hd).transpose(0, 2, 1, 3, 4)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)
    out = flash_attention_kernel(qg, kg, vg, lengths, block_q=bq, block_k=bk,
                                 window=window, softcap=softcap,
                                 causal=causal, interpret=interpret)
    out = out.transpose(0, 2, 1, 3, 4).reshape(B, Sp, H, hd)
    return out[:, :S]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_scan_op(dt, B_in, C_in, x, A, *, interpret: bool | None = None):
    """Selective-scan kernel wrapper; picks kernel blocks fitting the shape.

    dt, x: [B,S,E]; B_in, C_in: [B,S,N]; A: [E,N] -> (y, h_last)."""
    from repro.kernels.mamba_scan import mamba_scan
    interpret = _default_interpret() if interpret is None else interpret
    B, S, E = dt.shape

    def fit(n, target):
        b = min(target, n)
        while n % b:
            b -= 1
        return b

    return mamba_scan(dt, B_in, C_in, x, A, e_block=fit(E, 128),
                      s_block=fit(S, 256), interpret=interpret)
