"""Flash-decode attention Pallas kernel (one query token, blocked KV).

Online-softmax accumulation over KV blocks with VMEM scratch for the running
max / normalizer / value accumulator.  GQA layout: queries are grouped per
KV head ([B, KVH, G, dh]); the kernel grid is (B, KVH, S_blocks) with the
KV-block axis innermost (sequential accumulation).

Serving contract (the hot path of ``models/layers.decode_self_attention``):

* ``length`` is per-batch-row ([B] int32) — each continuous-batching slot
  attends to its own valid prefix of the shared fixed-capacity cache.
* ``softcap`` (gemma2-style logit capping) is applied pre-masking, matching
  ``layers.softcap``.
* ``S`` must be a block multiple; ``ops.flash_decode`` pads arbitrary cache
  lengths (padded keys sit at positions >= S >= length, always masked).

Validated in interpret=True mode against the pure-jnp oracle in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(L_ref, q_ref, k_ref, v_ref, o_ref,
                        m_scr, l_scr, acc_scr, *, block_s: int, scale: float,
                        softcap: float):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # [G, dh]
    k = k_ref[0].astype(jnp.float32)             # [Sblk, dh]
    v = v_ref[0].astype(jnp.float32)             # [Sblk, dh]
    s = jnp.dot(q, k.T) * scale                  # [G, Sblk]
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    pos = i * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < L_ref[b], s, NEG_INF)

    m_prev = m_scr[...]                           # [G, 1]
    m_new = jnp.maximum(m_prev[:, 0], s.max(axis=-1))[:, None]
    alpha = jnp.exp(m_prev - m_new)               # [G, 1]
    p = jnp.exp(s - m_new)                        # [G, Sblk]
    l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(p, v)
    m_scr[...] = m_new

    @pl.when(i == pl.num_programs(2) - 1)
    def _final():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def decode_attention(q, k, v, length, *, block_s: int = 512,
                     softcap: float = 0.0, interpret: bool = True):
    """q: [B, KVH, G, dh]; k, v: [B, S, KVH, dh]; length: int or [B] int32
    (per-row valid KV prefix).

    Returns [B, KVH, G, dh] attention output (softmax over positions <
    length, with optional pre-mask tanh softcapping of the logits).

    The cache is read as ``[B, S, KVH*dh]`` (a free reshape) so each K/V
    block is a ``(block_s, dh)`` tile of one head: a legal TPU block when
    dh is a multiple of 128.  The per-row lengths sit whole in SMEM.
    """
    B, KVH, G, dh = q.shape
    S = k.shape[1]
    assert S % block_s == 0, (S, block_s)
    grid = (B, KVH, S // block_s)
    scale = dh ** -0.5
    L_arr = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (B,))
    kernel = functools.partial(_decode_attn_kernel, block_s=block_s,
                               scale=scale, softcap=float(softcap))
    kv_spec = pl.BlockSpec((1, block_s, dh), lambda b, h, i: (b, i, h))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, dh), lambda b, h, i: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, h, i: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # running max m
            pltpu.VMEM((G, 1), jnp.float32),   # normalizer l
            pltpu.VMEM((G, dh), jnp.float32),  # value accumulator
        ],
        interpret=interpret,
    )(L_arr, q, k.reshape(B, S, KVH * dh), v.reshape(B, S, KVH * dh))
