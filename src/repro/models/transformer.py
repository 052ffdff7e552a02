"""Model application: training forward, prefill, and one-token decode, for
every architecture family, scanning over stacked layer periods.

The training forward names its parts with ``jax.named_scope`` — ``fwd.attn``
(the attention mixer; ``fwd.qk_norm`` inside it where the model norms q and
k, in ``layers._project_qkv``), ``fwd.mlp`` (the dense feed-forward) and
``fwd.head`` (the final norm and the output head) — so that, nested in the
ZO loop's ``zo.forward``, each compiled instruction's ``op_name`` says which
part of the forward it belongs to.  Scopes change metadata only.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL

P = jax.sharding.PartitionSpec


@dataclass(frozen=True)
class ShardCtx:
    """How model code should shard / chunk. ``mesh=None`` -> pure jnp."""
    mesh: Any = None
    batch_axes: Tuple[str, ...] = ()
    model_axis: str = "model"
    use_sharded_moe: bool = False
    attn_q_block: int = 0       # 0 -> full attention
    mamba_chunk: int = 64
    mlstm_block: int = 0
    scan_unroll: int = 1
    unroll_chunks: bool = False  # python-loop inner chunk loops (cost analysis)
    seq_shard: bool = False     # long-context decode: shard cache on seq
    remat: bool = False
    online_attn: bool = False   # flash-style online-softmax attention
    kv_block: int = 512         # KV block for online_attn
    mamba_mode: str = "scan"    # scan | kernel | stub (see ssm.mamba_forward)
    # decode-attention route (layers.resolve_decode_backend): "auto" runs the
    # Pallas flash-decode kernel when the layout supports it (interpret mode
    # off-TPU), "ref" the grouped jnp path (the only sharded-mesh choice)
    decode_backend: str = "auto"  # auto | pallas | ref
    # forward-attention route for training / prefill
    # (layers.resolve_attn_backend): "auto" consults the measured
    # kernels.autotune table, else heuristics — dense small-S, the
    # blockwise jnp online-softmax or the Pallas kernel at larger S; grad
    # traces prefer the kernel's recompute VJP (bounded backward memory)
    attn_backend: str = "auto"  # auto | pallas | online | dense

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def constrain(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def attn_head_spec(self, B: int, S: int, H: int):
        """Spec for [B, S, H, hd] attention tensors (None -> no constraint)."""
        if self.mesh is None:
            return None
        # pure-DP ZO mode folds 'model' into batch_axes — no TP dims left
        tp_free = self.model_axis not in self.batch_axes
        tp = int(self.mesh.shape[self.model_axis]) if tp_free else 1
        dp = self.dp_size
        h = self.model_axis if (tp_free and H % tp == 0) else None
        if self.seq_shard:
            s = self.batch_axes if (S > 1 and S % dp == 0) else None
            return P(None, s, h, None)
        b = self.batch_axes if B % dp == 0 else None
        s = None
        if tp_free and h is None and S > 1 and S % tp == 0:
            s = self.model_axis
        return P(b, s, h, None)

    def act_spec(self, B: int):
        if self.mesh is None:
            return None
        if self.seq_shard or B % max(self.dp_size, 1):
            return P(None, self.batch_axes, None)  # shard sequence
        return P(self.batch_axes, None, None)


DEFAULT_CTX = ShardCtx()


def _sinusoid(S, D, offset=0):
    """[..., S, D] sinusoidal table; ``offset`` is a scalar or a per-row [B]
    vector (continuous-batching decode, where every slot sits at its own
    absolute position)."""
    off = jnp.asarray(offset, jnp.float32)
    pos = jnp.arange(S, dtype=jnp.float32) + off[..., None]  # [..., S]
    dim = jnp.arange(0, D, 2, dtype=jnp.float32)
    ang = pos[..., None] / jnp.power(10_000.0, dim / D)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe[..., :D]


def _maybe_posenc(x, cfg, offset=0):
    """Learned-free sinusoidal absolute positions for rope-less attention
    archs (whisper).  SSM/hybrid archs need none."""
    if cfg.rope_style == "none" and (cfg.encoder is not None
                                     or cfg.frontend == "audio_stub"):
        return x + _sinusoid(x.shape[1], x.shape[2], offset).astype(x.dtype)
    return x


# ------------------------------------------------------------- embedding --
def embed_input(params, batch, cfg: ModelConfig):
    """Assemble the input sequence [B, S_total, D] from tokens + frontend."""
    tok = params["embed"][batch["tokens"]]
    if cfg.embed_scale:
        tok = tok * jnp.asarray(cfg.d_model ** 0.5, tok.dtype)
    if cfg.frontend == "vision_stub":
        x = jnp.concatenate([batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
    else:
        x = tok
    return x


def unembed(x, params, cfg: ModelConfig):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    return L.softcap(logits.astype(jnp.float32), cfg.final_softcap)


# ------------------------------------------------------------ layer apply --
def _mixer_fwd(x, lp, mixer, cfg, ctx, positions, enc_kv):
    h = L.apply_norm(x, lp["norm"], cfg.norm, cfg.norm_eps)
    if mixer in ("attn", "local_attn"):
        local = mixer == "local_attn"
        with jax.named_scope("fwd.attn"):
            if ctx.attn_q_block and x.shape[1] % ctx.attn_q_block == 0 \
                    and x.shape[1] > ctx.attn_q_block:
                y = L.self_attention_chunked(
                    h, lp, cfg, positions, local=local,
                    q_block=ctx.attn_q_block, unroll=ctx.unroll_chunks,
                    ctx=ctx)
            else:
                y = L.self_attention(h, lp, cfg, positions, local=local,
                                     ctx=ctx)
    elif mixer == "mamba":
        y = SSM.mamba_forward(h, lp, cfg.ssm, chunk=ctx.mamba_chunk,
                              unroll=ctx.unroll_chunks, mode=ctx.mamba_mode)
    elif mixer == "mlstm":
        y = XL.mlstm_forward(h, lp, cfg.xlstm, block=ctx.mlstm_block,
                             unroll=ctx.unroll_chunks)
    elif mixer == "slstm":
        y = XL.slstm_forward(h, lp, cfg.xlstm)
    else:
        raise ValueError(mixer)
    if cfg.post_norms and "post_norm" in lp:
        y = L.apply_norm(y, lp["post_norm"], cfg.norm, cfg.norm_eps)
    x = x + y
    if enc_kv is not None and mixer in ("attn", "local_attn") and "cross" in lp:
        h = L.apply_norm(x, lp["cross"]["norm"], cfg.norm, cfg.norm_eps)
        x = x + L.cross_attention(h, enc_kv, lp["cross"], cfg, ctx)
    return x


def _ffn_fwd(x, lp, ffn, cfg, ctx, token_valid=None):
    """``token_valid``: decode-time [B] mask keeping inactive slots out of
    MoE capacity dispatch (see moe.moe_dense_ref)."""
    aux = jnp.zeros((), jnp.float32)
    if ffn == "none":
        return x, aux
    h = L.apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
    if ffn == "dense":
        with jax.named_scope("fwd.mlp"):
            y = L.mlp(h, lp, cfg)
    else:
        y, aux = MOE.moe_ffn(h, lp, cfg.moe, cfg.act, ctx, valid=token_valid)
    if cfg.post_norms and "post_norm2" in lp:
        y = L.apply_norm(y, lp["post_norm2"], cfg.norm, cfg.norm_eps)
    return x + y, aux


def apply_layer(x, lp, mixer, ffn, cfg, ctx, positions, enc_kv=None):
    x = _mixer_fwd(x, lp, mixer, cfg, ctx, positions, enc_kv)
    return _ffn_fwd(x, lp, ffn, cfg, ctx)


# ------------------------------------------------------------ full stacks --
def stack_forward(x, stack, pattern, cfg, ctx, positions, enc_kv=None):
    spec = ctx.act_spec(x.shape[0])

    def body(carry, pp):
        xx, aux = carry
        for i, (mixer, ffn) in enumerate(pattern):
            fn = apply_layer
            if ctx.remat:
                fn = jax.checkpoint(apply_layer,
                                    static_argnums=(2, 3, 4, 5))
            xx, a = fn(xx, pp[f"p{i}"], mixer, ffn, cfg, ctx, positions, enc_kv)
            aux = aux + a
        if spec is not None:
            xx = ctx.constrain(xx, spec)
        return (xx, aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack,
                               unroll=ctx.scan_unroll)
    return x, aux


def encoder_forward(params, audio_embeds, cfg, ctx):
    enc = params["encoder"]
    x = audio_embeds + _sinusoid(audio_embeds.shape[1],
                                 cfg.d_model).astype(audio_embeds.dtype)

    def body(carry, pp):
        xx, _ = carry
        lp = pp["p0"]
        h = L.apply_norm(xx, lp["norm"], cfg.norm, cfg.norm_eps)
        xx = xx + L.bidir_attention(h, lp, cfg, ctx)
        xx, _ = _ffn_fwd(xx, lp, "dense", cfg, ctx)
        return (xx, jnp.zeros((), jnp.float32)), None

    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             enc["stack"], unroll=ctx.scan_unroll)
    return L.apply_norm(x, enc["final_norm"], cfg.norm, cfg.norm_eps)


def forward(params, batch, cfg: ModelConfig, ctx: ShardCtx = DEFAULT_CTX):
    """Training forward: returns (logits [B, S_tokens, V], aux_loss)."""
    x = embed_input(params, batch, cfg)
    x = _maybe_posenc(x, cfg)
    spec = ctx.act_spec(x.shape[0])
    if spec is not None:
        x = ctx.constrain(x, spec)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    enc_kv = None
    if cfg.encoder is not None:
        enc_out = encoder_forward(params, batch["audio_embeds"].astype(x.dtype),
                                  cfg, ctx)
        enc_kv = enc_out  # per-layer K/V projected inside apply via lp: see below
    x, aux = _stack_with_cross(x, params["stack"], cfg, ctx, positions, enc_kv)
    with jax.named_scope("fwd.head"):
        x = L.apply_norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
        logits = unembed(x, params, cfg)
    if cfg.frontend == "vision_stub":
        logits = logits[:, -batch["tokens"].shape[1]:]
    return logits, aux


def _stack_with_cross(x, stack, cfg, ctx, positions, enc_out):
    """Like stack_forward but projects per-layer cross K/V from enc_out."""
    if enc_out is None:
        return stack_forward(x, stack, cfg.layer_pattern, cfg, ctx, positions)
    spec = ctx.act_spec(x.shape[0])

    def body(carry, pp):
        xx, aux = carry
        for i, (mixer, ffn) in enumerate(cfg.layer_pattern):
            lp = pp[f"p{i}"]
            kv = L.encode_kv(enc_out, lp["cross"], cfg) if "cross" in lp else None
            xx = _mixer_fwd(xx, lp, mixer, cfg, ctx, positions, kv)
            xx, a = _ffn_fwd(xx, lp, ffn, cfg, ctx)
            aux = aux + a
        if spec is not None:
            xx = ctx.constrain(xx, spec)
        return (xx, aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack,
                               unroll=ctx.scan_unroll)
    return x, aux


# ------------------------------------------------------------------ loss --
def lm_loss(params, batch, cfg: ModelConfig, ctx: ShardCtx = DEFAULT_CTX,
            aux_weight: float = 0.01, per_example: bool = False):
    """Next-token cross-entropy (mean over non-pad positions)."""
    logits, aux = forward(params, batch, cfg, ctx)
    targets = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    # vocab-sharding-friendly CE: one-hot select fuses into the reduction,
    # so sharded-V logits never get all-gathered (unlike take_along_axis).
    lse = jax.nn.logsumexp(lg, axis=-1)
    vocab_iota = jnp.arange(lg.shape[-1])[None, None, :]
    tgt = jnp.sum(jnp.where(vocab_iota == targets[..., None], lg, 0.0),
                  axis=-1)
    nll = lse - tgt
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        per_ex = (nll * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)
    else:
        per_ex = nll.mean(-1)
    if per_example:
        return per_ex + aux_weight * aux
    return per_ex.mean() + aux_weight * aux
