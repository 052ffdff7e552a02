"""Flash-attention Pallas kernels: blockwise online-softmax forward and a
recompute-based backward (``jax.custom_vjp``).

The training/prefill counterpart of ``decode_attention.py``: every MEERKAT
step pays 2*n_dirs full forwards (Eq. 1), so the attention forward is the
step-time and peak-memory bound at realistic sequence lengths.  The forward
streams K/V block by block with online-softmax accumulation in VMEM scratch
and never materializes an [S, S] score matrix.

GQA layout: queries are grouped per KV head ([B, KVH, S, G, dh] — no KV
repeat; the G query heads of a group share one K/V stream).  The forward
grid is (B, KVH, S/block_q, S/block_k) with the KV-block axis innermost
(sequential accumulation into the running max / normalizer / value scratch,
exactly the flash-decode recurrence).  The wrapper folds the G axis into
the query rows before the kernels see it ([B, KVH, S*G, dh], a free
reshape; row r is query r // G), so the score matmul is a single
[block_q*G, dh] x [dh, block_k] MXU contraction and no kernel reshapes
in-register.  Per-row lengths sit whole in SMEM.

Forward-attention contract (the hot path of ``models/layers`` routed via
``resolve_attn_backend``):

* causal masking, optionally banded to a sliding ``window`` (gemma2-style
  local layers);
* ``softcap`` tanh logit capping applied pre-masking (``layers.softcap``);
* ``lengths`` is per-batch-row ([B] int32) key validity for right-padded
  prefill — keys at positions >= lengths[b] are masked for every query, so
  a padded batched prefill matches prefilling each row alone;
* f32 accumulation regardless of operand dtype;
* KV blocks that are entirely masked (future of the causal frontier, behind
  the sliding-window band, or past the row's length) skip their compute
  under ``pl.when``;
* ``S`` must be a block multiple; ``ops.flash_attention`` pads arbitrary
  lengths (padded keys sit at positions >= S >= lengths, always masked, and
  padded query rows are trimmed).

Backward (the VJP): the forward saves only its output O and the per-row
logsumexp ``lse = m + log(l)`` — O(S*dh + S) residuals instead of the
O(S^2) score matrices a naive differentiable route stacks.  The backward
*recomputes* the score blocks from (q, k, lse) and accumulates

    p  = exp(s - lse)            (the already-normalized probabilities)
    dV = p^T @ dO
    dp = dO @ V^T
    ds = p * (dp - delta),  delta = rowsum(dO * O)
    dQ = ds @ K * scale,    dK = ds^T @ Q * scale

over two kernels: a dQ pass (grid (B, KVH, nq, nk), KV innermost,
accumulating the query block's dQ in VMEM scratch) and a dK/dV pass (grid
(B, KVH, nk, nq), query innermost, accumulating the KV block's dK/dV).
Both reuse the forward's block-pruning predicate, so fully-masked blocks
cost nothing in the backward either.  The tanh softcap backward folds in
as ``ds_raw = ds * (1 - (s_cap/cap)^2)``.  ``lengths`` is integer-typed
and gets a ``float0`` cotangent.

Validated in interpret=True mode against the dense / online jnp routes in
``models/layers`` (tests/test_attn_backends.py, tests/test_attn_vjp.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


class Static(NamedTuple):
    """Hashable non-diff config threaded through the custom_vjp."""
    G: int
    block_q: int
    block_k: int
    window: int
    softcap: float
    causal: bool
    interpret: bool


def _block_needed(L0, q0, k0, *, block_q, block_k, window, causal):
    """Forward/backward shared block-pruning predicate: does KV block at
    ``k0`` hold any live (query, key) pair for the query block at ``q0``?"""
    needed = k0 < L0
    if causal:
        needed &= k0 <= q0 + block_q - 1
    if window:
        needed &= (k0 + block_k - 1) > (q0 - window)
    return needed


def _valid_mask(L0, q0, k0, shape, *, G, window, causal):
    """[block_q*G, block_k] bool validity; row r <-> query q0 + r // G."""
    rows = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // G
    cols = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    valid = cols < L0
    if causal:
        valid &= cols <= rows
    if window:
        valid &= cols > rows - window
    return valid


# ------------------------------------------------------------- forward ----
def _flash_attn_kernel(L_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, block_q: int, block_k: int,
                       G: int, scale: float, softcap: float, window: int,
                       causal: bool):
    L0 = L_ref[pl.program_id(0)]
    i = pl.program_id(2)   # query block
    j = pl.program_id(3)   # KV block (innermost: sequential accumulation)
    q0 = i * block_q
    k0 = j * block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level pruning: a KV block with no live (query, key) pair
    # contributes nothing to the running stats — skip its matmuls.
    needed = _block_needed(L0, q0, k0, block_q=block_q,
                           block_k=block_k, window=window, causal=causal)

    @pl.when(needed)
    def _accumulate():
        q2 = q_ref[0, 0].astype(jnp.float32)     # [block_q*G, dh]
        k = k_ref[0, 0].astype(jnp.float32)      # [block_k, dh]
        v = v_ref[0, 0].astype(jnp.float32)      # [block_k, dh]
        s = jnp.dot(q2, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        valid = _valid_mask(L0, q0, k0, s.shape, G=G, window=window,
                            causal=causal)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]                       # [block_q*G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # mask p explicitly: on a fully-masked row m_new is still NEG_INF
        # and exp(s - m_new) would be 1, not 0
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        # per-row logsumexp residual: exp(s - lse) is the final normalized
        # probability, the only softmax state the backward needs
        lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def _row_specs(st: Static, dh: int, q_index, kv_index):
    """BlockSpecs of the G-folded layout: query rows, K/V, per-row stats."""
    rows = st.block_q * st.G
    return (pl.BlockSpec((1, 1, rows, dh), q_index),
            pl.BlockSpec((1, 1, st.block_k, dh), kv_index),
            pl.BlockSpec((1, 1, rows, 1), q_index))


def _fwd_call(st: Static, q, k, v, L_arr):
    """pallas_call for the forward on G-folded queries [B,KVH,S*G,dh];
    returns (out [B,KVH,S*G,dh], lse [B,KVH,S*G,1] f32)."""
    B, KVH, SG, dh = q.shape
    S = SG // st.G
    grid = (B, KVH, S // st.block_q, S // st.block_k)
    kernel = functools.partial(
        _flash_attn_kernel, block_q=st.block_q, block_k=st.block_k, G=st.G,
        scale=dh ** -0.5, softcap=float(st.softcap), window=int(st.window),
        causal=bool(st.causal))
    q_spec, kv_spec, lse_spec = _row_specs(
        st, dh, lambda b, h, i, j: (b, h, i, 0),
        lambda b, h, i, j: (b, h, j, 0))
    rows = st.block_q * st.G
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B, KVH, SG, dh), q.dtype),
                   jax.ShapeDtypeStruct((B, KVH, SG, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max m
            pltpu.VMEM((rows, 1), jnp.float32),   # normalizer l
            pltpu.VMEM((rows, dh), jnp.float32),  # value acc
        ],
        interpret=st.interpret,
    )(L_arr, q, k, v)


# ------------------------------------------------------------ backward ----
def _recompute_p_ds(L0, q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
                    q0, k0, *, block_k, G, scale, softcap, window, causal):
    """Shared backward block math: recompute p and ds for one
    (query-block, KV-block) tile.  Returns (p, ds, q, k, do), every
    operand f32; query rows arrive G-folded (row r <-> query q0 + r//G)."""
    q = q_ref[0, 0].astype(jnp.float32)          # [block_q*G, dh]
    k = k_ref[0, 0].astype(jnp.float32)          # [block_k, dh]
    v = v_ref[0, 0].astype(jnp.float32)          # [block_k, dh]
    do = do_ref[0, 0].astype(jnp.float32)        # [block_q*G, dh]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = _valid_mask(L0, q0, k0, s.shape, G=G, window=window,
                        causal=causal)
    # explicit zero where invalid: on fully-masked rows lse is ~NEG_INF and
    # exp(s - lse) would overflow / evaluate to 1 at masked s, not 0
    p = jnp.where(valid, jnp.exp(s - lse_ref[0, 0]), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0])
    if softcap:
        # s here is the *capped* logit: d tanh-cap/d raw = 1 - (s/cap)^2
        ds = ds * (1.0 - jnp.square(s / softcap))
    return p, ds, q, k, do


def _flash_attn_bwd_dq_kernel(L_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
                              do_ref, dq_ref, dq_scr, *, block_q: int,
                              block_k: int, G: int, scale: float,
                              softcap: float, window: int, causal: bool):
    L0 = L_ref[pl.program_id(0)]
    i = pl.program_id(2)   # query block
    j = pl.program_id(3)   # KV block (innermost: accumulate dq)
    q0 = i * block_q
    k0 = j * block_k

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    needed = _block_needed(L0, q0, k0, block_q=block_q,
                           block_k=block_k, window=window, causal=causal)

    @pl.when(needed)
    def _accumulate():
        _, ds, _, k, _ = _recompute_p_ds(
            L0, q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
            q0, k0, block_k=block_k, G=G, scale=scale,
            softcap=softcap, window=window, causal=causal)
        dq_scr[...] += jnp.dot(ds, k,
                               preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...]


def _flash_attn_bwd_dkv_kernel(L_ref, q_ref, k_ref, v_ref, lse_ref,
                               delta_ref, do_ref, dk_ref, dv_ref, dk_scr,
                               dv_scr, *, block_q: int, block_k: int, G: int,
                               scale: float, softcap: float, window: int,
                               causal: bool):
    L0 = L_ref[pl.program_id(0)]
    j = pl.program_id(2)   # KV block
    i = pl.program_id(3)   # query block (innermost: accumulate dk/dv)
    q0 = i * block_q
    k0 = j * block_k

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    needed = _block_needed(L0, q0, k0, block_q=block_q,
                           block_k=block_k, window=window, causal=causal)

    @pl.when(needed)
    def _accumulate():
        p, ds, q, _, do = _recompute_p_ds(
            L0, q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref,
            q0, k0, block_k=block_k, G=G, scale=scale,
            softcap=softcap, window=window, causal=causal)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dk_scr[...] += jnp.dot(ds.T, q,
                               preferred_element_type=jnp.float32) * scale

    @pl.when(i == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


def _bwd_kernel_args(st: Static, kernel, dh: int):
    return functools.partial(
        kernel, block_q=st.block_q, block_k=st.block_k, G=st.G,
        scale=dh ** -0.5, softcap=float(st.softcap), window=int(st.window),
        causal=bool(st.causal))


def _bwd_dq_call(st: Static, q, k, v, L_arr, lse, delta, do):
    B, KVH, SG, dh = q.shape
    S = SG // st.G
    grid = (B, KVH, S // st.block_q, S // st.block_k)
    q_spec, kv_spec, row_spec = _row_specs(
        st, dh, lambda b, h, i, j: (b, h, i, 0),
        lambda b, h, i, j: (b, h, j, 0))
    return pl.pallas_call(
        _bwd_kernel_args(st, _flash_attn_bwd_dq_kernel, dh),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, SG, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((st.block_q * st.G, dh), jnp.float32)],
        interpret=st.interpret,
    )(L_arr, q, k, v, lse, delta, do)


def _bwd_dkv_call(st: Static, q, k, v, L_arr, lse, delta, do):
    B, KVH, SG, dh = q.shape
    S = SG // st.G
    # query axis innermost: each KV block accumulates over all query blocks
    grid = (B, KVH, S // st.block_k, S // st.block_q)
    q_spec, kv_spec, row_spec = _row_specs(
        st, dh, lambda b, h, j, i: (b, h, i, 0),
        lambda b, h, j, i: (b, h, j, 0))
    return pl.pallas_call(
        _bwd_kernel_args(st, _flash_attn_bwd_dkv_kernel, dh),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, kv_spec, kv_spec, row_spec, row_spec, q_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, KVH, S, dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, KVH, S, dh), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((st.block_k, dh), jnp.float32),
                        pltpu.VMEM((st.block_k, dh), jnp.float32)],
        interpret=st.interpret,
    )(L_arr, q, k, v, lse, delta, do)


# ---------------------------------------------------------- custom VJP ----
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(st: Static, q, k, v, L_arr):
    out, _ = _fwd_call(st, q, k, v, L_arr)
    return out


def _flash_attention_fwd(st: Static, q, k, v, L_arr):
    out, lse = _fwd_call(st, q, k, v, L_arr)
    # residuals are O(S*dh) — no score matrices survive the forward
    return out, (q, k, v, L_arr, out, lse)


def _flash_attention_bwd(st: Static, res, do):
    q, k, v, L_arr, out, lse = res
    # delta = rowsum(dO * O): O(S*dh) elementwise work, done outside the
    # kernels so both backward passes read it as a [B,KVH,S*G,1] stream
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq = _bwd_dq_call(st, q, k, v, L_arr, lse, delta, do)
    dk, dv = _bwd_dkv_call(st, q, k, v, L_arr, lse, delta, do)
    # integer lengths take a float0 cotangent (non-differentiable operand)
    dL = np.zeros(np.shape(L_arr), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dL)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q, k, v, lengths, *, block_q: int = 128,
                    block_k: int = 128, window: int = 0, softcap: float = 0.0,
                    causal: bool = True, interpret: bool = True):
    """q: [B, KVH, S, G, dh]; k, v: [B, KVH, S, dh]; lengths: int or [B]
    int32 (per-row valid KV prefix).

    Returns [B, KVH, S, G, dh] attention output: for query position t,
    softmax over key positions p with p < lengths[b], p <= t (causal) and
    t - window < p (when window > 0), with optional pre-mask tanh
    softcapping of the logits and f32 accumulation.

    Differentiable: ``jax.grad`` through this function runs the
    recompute-based backward kernels (module docstring) — the forward saves
    only O and the per-row logsumexp.
    """
    B, KVH, S, G, dh = q.shape
    assert k.shape == (B, KVH, S, dh), (q.shape, k.shape)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    L_arr = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                             (B,))
    st = Static(G=G, block_q=int(block_q), block_k=int(block_k),
                window=int(window), softcap=float(softcap),
                causal=bool(causal), interpret=bool(interpret))
    # G folds into the query rows here, a free reshape, so no kernel
    # reshapes in-register (Mosaic refuses the backward's shape casts)
    out = _flash_attention(st, q.reshape(B, KVH, S * G, dh), k, v, L_arr)
    return out.reshape(B, KVH, S, G, dh)
