"""Composable transformer layers: norms, RoPE variants, GQA attention with
softcaps / sliding windows / qk-norm / biases, gated & plain MLPs.

All functions are pure; parameters are plain dict pytrees created in
``repro.models.init``.

Forward attention (training / prefill) runs through one dispatch point,
:func:`forward_attention`, selecting between three semantically identical
routes per ``ShardCtx.attn_backend`` (see :func:`resolve_attn_backend`):

* ``"pallas"`` — the blockwise online-softmax Pallas kernel
  (``kernels/flash_attention.py``), GQA-grouped, no [S, S] scores,
  differentiable via its recompute-based backward kernels;
* ``"online"`` — the pure-jnp online-softmax route (differentiable, carries
  no [S, S] scores either; the ``zo_dp`` sharded-training route);
* ``"dense"``  — materialized scores (q-block-chunked when ``attn_q_block``
  is set); the GSPMD-constrained reference route.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # large-negative for masking (bf16-safe)


# ---------------------------------------------------------------- norms ----
def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def apply_norm(x, p, kind: str, eps: float):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"], eps)
    return layernorm(x, p["scale"], p["bias"], eps)


def softcap(x, cap: float):
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ----------------------------------------------------------------- RoPE ----
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float, partial: float = 1.0):
    """x: [..., S, n_heads, head_dim]; positions: [..., S] int32.

    partial < 1 rotates only the first ``partial * head_dim`` dims
    (chatglm-style "2d" rope).
    """
    hd = x.shape[-1]
    rot = int(hd * partial)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta)  # [rot/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, rot/2]
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ------------------------------------------------------------ attention ----
def _project_qkv(x, p, cfg):
    """Return q [B,S,H,hd], k,v [B,S,KV,hd]."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if "lora_qa" in p:
        s = cfg.lora_alpha / cfg.lora_rank
        q = q + s * jnp.einsum("bsr,rh->bsh",
                               jnp.einsum("bsd,dr->bsr", x, p["lora_qa"]),
                               p["lora_qb"])
        v = v + s * jnp.einsum("bsr,rh->bsh",
                               jnp.einsum("bsd,dr->bsr", x, p["lora_va"]),
                               p["lora_vb"])
    q = q.reshape(B, S, cfg.n_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(cfg.n_heads, hd)
        k = k + p["bk"].reshape(cfg.n_kv_heads, hd)
        v = v + p["bv"].reshape(cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        with jax.named_scope("fwd.qk_norm"):
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_attention(q, k, v, mask, cfg, ctx=None):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KV,hd]; mask: [B|1, Sq, Sk] bool or None.

    Grouped-query layout: scores use [B, KV, G, Sq, Sk] (the ``bqkgd``
    grouping of :func:`grouped_gqa_attention`) so K/V are never repeated
    G-fold — a repeat materializes (and, tensor-parallel, all-gathers) a
    G-times-redundant K/V copy before the matmul.  The ctx head-sharding
    constraint stays: q is constrained at its full H heads, K/V at their
    stored KV heads, so tensor-parallel head sharding survives whenever the
    axis divides the respective head count (see sharding/rules.py)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    if ctx is not None:
        spec = ctx.attn_head_spec(B, Sq, H)
        if spec is not None:
            q = ctx.constrain(q, spec)
        kv_spec = ctx.attn_head_spec(B, k.shape[1], KV)
        if kv_spec is not None:
            k = ctx.constrain(k, kv_spec)
            v = ctx.constrain(v, kv_spec)
    qg = q.reshape(B, Sq, KV, G, hd)  # head h -> (kv h//G, g h%G)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cfg.attn_softcap)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(v.dtype)


def causal_mask(Sq: int, Sk: int, window: int = 0, offset: int = 0):
    """[1, Sq, Sk] causal (optionally banded) mask.

    ``offset`` is the absolute position of query 0 minus key 0 (for caches).
    """
    qi = jnp.arange(Sq)[:, None] + offset
    ki = jnp.arange(Sk)[None, :]
    m = ki <= qi
    if window:
        m &= ki > qi - window
    return m[None]


# ---------------------------------------- forward-attention dispatch ----
ATTN_BACKENDS = ("auto", "pallas", "online", "dense")

# below this the [S, S] score tile is cache/VMEM-resident and the dense
# route's single fused matmul wins; at and above it the blockwise routes
# avoid the O(S^2) materialization that dominates forward memory
ATTN_AUTO_MIN_S = 256

# without an autotune measurement, compiled hosts only *assume* the pallas
# kernel beats the online jnp route at large S: BENCH_attn-style probes
# showed the fixed-block kernel trailing online at moderate S (0.79x at
# S=256), so untuned "auto" stays on online below this
ATTN_PALLAS_MIN_S = 1024

_DIFFERENTIABLE_ATTN = contextvars.ContextVar("differentiable_attn",
                                              default=False)


@contextlib.contextmanager
def differentiable_attn():
    """Scope marking a ``jax.grad`` trace for :func:`resolve_attn_backend`
    (train/first_order, sensitivity-mask calibration, GradIP pre-training
    gradients enter it around their grad traces).  Every route is
    differentiable — the Pallas kernel carries a recompute-based backward
    (``kernels/flash_attention.py``) — so the scope no longer *forces* a
    jnp route; it selects the grad-appropriate one: under "auto" the
    kernel VJP is preferred at blockwise S because its O(S*dh) residuals
    bound backward memory where the jnp VJPs stack O(S^2)-class score
    residuals (DESIGN.md §10).  The resolve happens at trace time, so the
    choice is baked into the jitted computation."""
    tok = _DIFFERENTIABLE_ATTN.set(True)
    try:
        yield
    finally:
        _DIFFERENTIABLE_ATTN.reset(tok)


def resolve_attn_backend(backend, cfg, ctx=None, *, S: int = 0,
                         differentiable: Optional[bool] = None) -> str:
    """Map a requested forward-attention backend to 'pallas' | 'online' |
    'dense'.

    Explicit backends are honored as requested (the Pallas kernel now
    defines a VJP, so "pallas" is valid inside grad traces too).  "auto"
    resolves, in order:

    * the legacy ``ctx.online_attn`` flag -> "online";
    * a sharded mesh -> the jnp routes (the kernel carries no GSPMD
      sharding constraints): "dense" small-S, "online" blockwise;
    * S below ``ATTN_AUTO_MIN_S`` -> "dense" (the [S, S] tile is
      cache-resident and one fused matmul wins);
    * the measured ``kernels.autotune`` table, exact (op, S, head_dim, G,
      platform) key — op is "grad" inside :func:`differentiable_attn`
      scopes, "fwd" otherwise — so a populated table always picks the
      measured-fastest route, including online where pallas loses;
    * untuned grad traces -> "pallas": the recompute VJP bounds backward
      memory to O(S*dh) residuals (the jnp VJPs stack O(S^2)-class score
      residuals — the 186 MB first_order liveness peak, DESIGN.md §10);
    * untuned forwards -> "online" when interpreting (off-TPU the kernel
      runs in the Pallas interpreter, the slowest route by far) or for
      head dims off the 128-lane tile; compiled, "pallas" only from
      ``ATTN_PALLAS_MIN_S`` up — fixed-block probes showed online winning
      at moderate S, so unmeasured hosts don't assume the kernel wins."""
    backend = backend or "auto"
    if backend not in ATTN_BACKENDS:
        raise ValueError(
            f"attn backend must be one of {ATTN_BACKENDS}, got {backend!r}")
    if differentiable is None:
        differentiable = _DIFFERENTIABLE_ATTN.get()
    if backend != "auto":
        return backend
    if ctx is not None and getattr(ctx, "online_attn", False):
        return "online"  # legacy zo_dp flag, kept as an explicit route
    if ctx is not None and ctx.mesh is not None:
        return "dense" if (not S or S < ATTN_AUTO_MIN_S) else "online"
    if S and S < ATTN_AUTO_MIN_S:
        return "dense"
    from repro.kernels.ops import _default_interpret
    if S:
        from repro.kernels import autotune
        route = autotune.fastest_route(
            S, cfg.resolved_head_dim, cfg.n_heads // cfg.n_kv_heads,
            op="grad" if differentiable else "fwd")
        if route is not None:
            return route
    if differentiable:
        if not _default_interpret() and cfg.resolved_head_dim % 128:
            return "online"  # kernel tiling does not cover this head_dim
        return "pallas"
    if _default_interpret() or cfg.resolved_head_dim % 128:
        return "online"
    return "pallas" if S >= ATTN_PALLAS_MIN_S else "online"


def forward_attention(q, k, v, cfg, ctx=None, *, window: int = 0,
                      kv_mask=None, lengths=None, q_block: int = 0,
                      kv_block: int = 0, unroll: bool = False, backend=None):
    """Unified forward-attention entry: q [B,S,H,hd]; k,v [B,S,KV,hd] ->
    [B,S,H,hd], causal (optionally banded to ``window``).

    Every training / prefill attention call routes through here (the ZO
    loss forwards inherit the route through the model's ctx).  Right-padded
    batches express key validity as per-row ``lengths`` [B] and/or
    ``kv_mask`` [B, 1, Sk]; all three backends honor both."""
    B, S, H, hd = q.shape
    be = resolve_attn_backend(
        backend or (getattr(ctx, "attn_backend", None)
                    if ctx is not None else None),
        cfg, ctx, S=S)
    if be == "pallas":
        from repro.kernels.ops import flash_attention
        L = lengths
        if L is None and kv_mask is not None:
            # right-pad contract: the mask is a per-row valid key prefix
            L = kv_mask.reshape(B, S).sum(-1).astype(jnp.int32)
        out = flash_attention(q, k, v, L, window=window,
                              softcap=cfg.attn_softcap)
        return out.astype(v.dtype)
    if be == "online":
        # default q tile of 128 keeps every score tile strictly smaller
        # than [S, S] at any routed S (>= ATTN_AUTO_MIN_S under "auto")
        return online_gqa_attention(
            q, k, v, cfg, window=window,
            q_block=q_block or min(128, S),
            kv_block=min(kv_block
                         or (getattr(ctx, "kv_block", 512)
                             if ctx is not None else 512), S),
            unroll=unroll, lengths=lengths,
            kv_mask=None if kv_mask is None else kv_mask.reshape(B, S))
    if kv_mask is None and lengths is not None:
        l_arr = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                                 (B,))
        kv_mask = (jnp.arange(S)[None, :] < l_arr[:, None])[:, None, :]
    return blocked_gqa_attention(q, k, v, cfg, ctx, window=window,
                                 q_block=q_block, unroll=unroll,
                                 kv_mask=kv_mask)


def self_attention(x, p, cfg, positions, *, local: bool, mask_extra=None,
                   ctx=None, lengths=None):
    """Full training/prefill self-attention. x: [B,S,D] -> [B,S,D].

    Routes through :func:`forward_attention` (``ctx.attn_backend``);
    ``mask_extra`` — an arbitrary [B|1,S,S] mask — only has a dense
    expression and pins the dense route."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    if cfg.rope_style != "none":
        partial = cfg.rope_partial_factor if cfg.rope_style == "partial" else 1.0
        q = apply_rope(q, positions, cfg.rope_theta, partial)
        k = apply_rope(k, positions, cfg.rope_theta, partial)
    window = cfg.sliding_window if local else 0
    if mask_extra is not None:
        mask = causal_mask(S, S, window) & mask_extra
        if lengths is not None:
            l_arr = jnp.broadcast_to(
                jnp.asarray(lengths, jnp.int32).reshape(-1), (B,))
            mask = mask & (jnp.arange(S)[None, :]
                           < l_arr[:, None])[:, None, :]
        out = gqa_attention(q, k, v, mask, cfg, ctx)
    else:
        out = forward_attention(q, k, v, cfg, ctx, window=window,
                                lengths=lengths)
    return jnp.einsum("bsx,xe->bse", out.reshape(B, S, -1), p["wo"])


def blocked_gqa_attention(q, k, v, cfg, ctx, *, window: int, q_block: int,
                          unroll: bool = False, kv_mask=None):
    """Query-block-chunked causal attention: scores are materialized per
    block [B,H,q_block,Sk] instead of [B,H,S,S].  Falls back to one full
    block when q_block does not apply.

    ``kv_mask``: [B, 1, Sk] bool key-validity (right-padded prefill masks
    its pad keys here), ANDed into the causal mask."""
    B, S, H, hd = q.shape
    if not q_block or S % q_block or S <= q_block:
        mask = causal_mask(S, S, window)
        if kv_mask is not None:
            mask = mask & kv_mask
        return gqa_attention(q, k, v, mask, cfg, ctx)
    nb = S // q_block
    qb = q.reshape(B, nb, q_block, H, hd).swapaxes(0, 1)

    def blk(qi, off):
        mask = causal_mask(q_block, S, window, offset=off)
        if kv_mask is not None:
            mask = mask & kv_mask
        return gqa_attention(qi, k, v, mask, cfg, ctx)

    if unroll:
        outs = [blk(qb[i], i * q_block) for i in range(nb)]
        return jnp.concatenate(outs, axis=1)
    outs = jax.lax.map(lambda t: blk(t[0], t[1]),
                       (qb, jnp.arange(nb) * q_block))
    return outs.swapaxes(0, 1).reshape(B, S, H, hd)


def online_gqa_attention(q, k, v, cfg, *, window: int = 0,
                         q_block: int = 512, kv_block: int = 512,
                         unroll: bool = False, lengths=None, kv_mask=None):
    """Flash-style causal attention: online-softmax over KV blocks, grouped
    query (no KV repeat).  Never materializes [S, S] scores — the working
    set per (q_block, kv_block) tile is O(q_block * kv_block), so the HBM
    traffic drops from O(H*S^2) to O(S*d) (§Perf pair 2, iteration 2).

    q: [B,S,H,hd]; k,v: [B,S,KV,hd] -> [B,S,H,hd].  Semantically identical
    to gqa_attention with a causal (optionally banded) mask.

    ``S`` need not be a block multiple: inputs are zero-padded up to one
    (padded keys are masked through the key-validity stream, padded query
    rows trimmed from the output).  ``lengths`` ([B] int32) and/or
    ``kv_mask`` ([B, S] bool) mask right-padded keys, so batched
    right-padded prefill/training can take this route instead of falling
    back to dense attention.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    q_block = max(1, min(q_block, S))
    kv_block = max(1, min(kv_block, S))
    per = q_block * kv_block // math.gcd(q_block, kv_block)
    pad = (-S) % per
    kvv = None if kv_mask is None else jnp.asarray(kv_mask, bool)
    if lengths is not None:
        l_arr = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                                 (B,))
        lm = jnp.arange(S)[None, :] < l_arr[:, None]
        kvv = lm if kvv is None else (kvv & lm)
    if pad:
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(x, padw) for x in (q, k, v))
        if kvv is None:
            kvv = jnp.broadcast_to(jnp.arange(S + pad)[None, :] < S,
                                   (B, S + pad))
        else:
            kvv = jnp.pad(kvv, ((0, 0), (0, pad)))
    Sp = S + pad
    nq, nk = Sp // q_block, Sp // kv_block
    qg = q.reshape(B, nq, q_block, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kv_block, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_block, KV, hd).transpose(1, 0, 2, 3, 4)
    kvb = (None if kvv is None
           else kvv.reshape(B, nk, kv_block).transpose(1, 0, 2))
    ki_base = jnp.arange(kv_block)[None, :]
    qi_base = jnp.arange(q_block)[:, None]

    def q_chunk(args):
        qb, q0 = args[0], args[1]  # [B,q_block,KV,G,hd], scalar offset

        def kv_step(carry, inp):
            m, l, acc = carry
            kb, vb, k0 = inp[0], inp[1], inp[2]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cfg.attn_softcap)
            valid = (k0 + ki_base) <= (q0 + qi_base)
            if window:
                valid &= (k0 + ki_base) > (q0 + qi_base - window)
            valid = valid[None, None, None, :, :]
            if kvb is not None:
                valid = valid & inp[3][:, None, None, None, :]  # [B,kv_block]
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            # mask p explicitly: on a fully-masked row m_new is still
            # NEG_INF and exp(s - m_new) would be 1, not 0
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, hd), jnp.float32)
        offs = jnp.arange(nk) * kv_block
        xs = (ks, vs, offs) if kvb is None else (ks, vs, offs, kvb)
        if unroll:
            carry = (m0, l0, a0)
            for i in range(nk):
                carry, _ = kv_step(carry, tuple(x[i] for x in xs))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B,KV,G,q_block,hd]
        return out

    if unroll:
        outs = jnp.stack([q_chunk((qg[i], jnp.asarray(i * q_block)))
                          for i in range(nq)])
    else:
        outs = jax.lax.map(q_chunk, (qg, jnp.arange(nq) * q_block))
    # [nq,B,KV,G,q_block,hd] -> [B, nq*q_block, KV*G, hd]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sp, H, hd)
    return out[:, :S].astype(v.dtype)


def self_attention_chunked(x, p, cfg, positions, *, local: bool, q_block: int,
                           unroll: bool = False, ctx=None, lengths=None):
    """Query-block-chunked causal self-attention; semantically identical to
    :func:`self_attention`, threading ``q_block`` into whichever backend
    :func:`forward_attention` resolves (the dense route chunks its scores
    per q_block, the online/pallas routes tile by it)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    if cfg.rope_style != "none":
        partial = cfg.rope_partial_factor if cfg.rope_style == "partial" else 1.0
        q = apply_rope(q, positions, cfg.rope_theta, partial)
        k = apply_rope(k, positions, cfg.rope_theta, partial)
    window = cfg.sliding_window if local else 0
    out = forward_attention(q, k, v, cfg, ctx, window=window, q_block=q_block,
                            unroll=unroll, lengths=lengths)
    return jnp.einsum("bsx,xe->bse", out.reshape(B, S, -1), p["wo"])


def bidir_attention(x, p, cfg, ctx=None):
    """Encoder (non-causal) self-attention."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    out = gqa_attention(q, k, v, None, cfg, ctx)
    return jnp.einsum("bsx,xe->bse", out.reshape(B, S, -1), p["wo"])


def cross_attention(x, enc_kv, p, cfg, ctx=None):
    """Decoder cross-attention. enc_kv: (k,v) each [B,Senc,KV,hd]."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k, v = enc_kv
    out = gqa_attention(q, k, v, None, cfg, ctx)
    return jnp.einsum("bsx,xe->bse", out.reshape(B, S, -1), p["wo"])


def encode_kv(enc_out, p, cfg):
    """Precompute cross-attention K/V from encoder output."""
    B, Se, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = jnp.einsum("bsd,dh->bsh", enc_out, p["wk"]).reshape(B, Se, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", enc_out, p["wv"]).reshape(B, Se, cfg.n_kv_heads, hd)
    return k, v


# -------------------------------------------------- decode-mode attention ----
def grouped_gqa_attention(q, k, v, valid, cfg, ctx=None):
    """Decode attention with the query grouped per KV head — no KV repeat.

    q: [B,Sq,H,hd]; k,v: [B,W,KV,hd]; valid: [B|1,Sq,W] bool.

    ``gqa_attention`` originally repeated K/V to H heads before the
    matmul, which for a 32k decode cache materializes (and,
    tensor-parallel, all-gathers) a G-times-redundant [B,W,KV,G,hd]
    tensor (§Perf iteration 1); this grouped variant predates — and
    motivated — the same ``bqkgd`` layout now used there.  Grouping
    the *query* keeps cache-sized tensors at their stored shape;
    with the cache sequence-sharded over 'model', scores come out
    W-sharded, the softmax lowers to cheap stat all-reduces, and the output
    contraction partial-sums into one [B,KV,G,hd]-sized all-reduce."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scale = hd ** -0.5
    # bf16 operands + f32 accumulation via preferred_element_type: avoids
    # materializing cache-sized f32 converts (§Perf iteration 2) and is the
    # TPU-native MXU mode.
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = softcap(scores, cfg.attn_softcap)
    if valid is not None:
        scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(v.dtype)


DECODE_BACKENDS = ("auto", "pallas", "ref")


def resolve_decode_backend(backend, cfg, ctx=None) -> str:
    """Map a requested decode-attention backend to 'pallas' | 'ref'.

    "auto" prefers the Pallas flash-decode kernel (interpret mode
    off-TPU, see kernels/ops.py) and falls back to the grouped jnp path
    for layouts the kernel does not cover — a sharded mesh (the jnp path
    carries the GSPMD sharding constraints) or, compiled on a real TPU, a
    head_dim off the 128-lane tile."""
    backend = backend or "auto"
    if backend not in DECODE_BACKENDS:
        raise ValueError(
            f"decode backend must be one of {DECODE_BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    if ctx is not None and ctx.mesh is not None:
        return "ref"
    from repro.kernels.ops import _default_interpret
    if not _default_interpret() and cfg.resolved_head_dim % 128:
        return "ref"
    return "pallas"


def decode_self_attention(x1, p, cfg, cache_k, cache_v, cur_pos, *,
                          local: bool, ctx=None):
    """One-token decode. x1: [B,1,D]; cache_k/v: [B,W,KV,hd] (rolling when
    local); cur_pos: scalar or per-row [B] (continuous-batching slots each
    sit at their own position).  Returns (out [B,1,D], new_k, new_v).

    Routes through the Pallas flash-decode kernel or the grouped jnp path
    per ``ctx.decode_backend`` (see :func:`resolve_decode_backend`)."""
    B = x1.shape[0]
    hd = cfg.resolved_head_dim
    W = cache_k.shape[1]
    q, k, v = _project_qkv(x1, p, cfg)  # [B,1,H,hd], [B,1,KV,hd]
    pos_vec = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32).reshape(-1),
                               (B,))
    if cfg.rope_style != "none":
        partial = cfg.rope_partial_factor if cfg.rope_style == "partial" else 1.0
        pos = pos_vec[:, None]
        q = apply_rope(q, pos, cfg.rope_theta, partial)
        k = apply_rope(k, pos, cfg.rope_theta, partial)
    rolling = bool(local and cfg.sliding_window)
    slot = jnp.mod(pos_vec, W) if rolling else jnp.minimum(pos_vec, W - 1)
    # cast to the cache dtype BEFORE the update: rope upcasts k to f32, and
    # dynamic_update_slice would promote the *entire cache* to f32 per layer
    # (a full-cache convert round-trip; §Perf iteration 3)
    upd = jax.vmap(
        lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, axis=0))
    cache_k = upd(cache_k, k.astype(cache_k.dtype), slot)
    cache_v = upd(cache_v, v.astype(cache_v.dtype), slot)
    backend = resolve_decode_backend(
        getattr(ctx, "decode_backend", None) if ctx is not None else None,
        cfg, ctx)
    if backend == "pallas":
        # both cache layouts expose a per-row valid *prefix*: global caches
        # hold positions [0, pos], a full rolling buffer holds all W slots
        lengths = jnp.minimum(pos_vec + 1, W) if rolling else pos_vec + 1
        from repro.kernels.ops import flash_decode
        KV = cfg.n_kv_heads
        qg = q[:, 0].reshape(B, KV, cfg.n_heads // KV, hd)
        out = flash_decode(qg, cache_k, cache_v, lengths,
                           softcap=cfg.attn_softcap)
        out = out.reshape(B, 1, cfg.n_heads, hd).astype(cache_v.dtype)
    else:
        ki = jnp.arange(W)[None, None, :]  # [1,1,W]
        pv = pos_vec[:, None, None]
        if rolling:
            valid = (ki <= slot[:, None, None]) | (pv >= W)
        else:
            valid = ki <= pv
        out = grouped_gqa_attention(q, cache_k, cache_v, valid, cfg, ctx)
    out = jnp.einsum("bsx,xe->bse", out.reshape(B, 1, -1), p["wo"])
    return out, cache_k, cache_v


# ------------------------------------------------------------------ MLP ----
def mlp(x, p, cfg):
    if cfg.act == "gelu_plain":
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]))
    else:
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(jnp.einsum("bsd,df->bsf", x, p["w1"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w3"])
    return jnp.einsum("bsf,fd->bsd", h, p["w2"])
