"""Dense decoder-only transformers (Qwen2, Qwen3): the benchmark's own
weights, FLOP count and plain reference.

The configuration file gives the model as published, in the keys of its
Hugging Face ``config.json``.  Everything here reads those keys and imports
nothing of the program:

* :func:`weight_shapes` — the parameter layout the program takes (layers
  stacked on a leading axis, RMSNorm gains stored as offsets from 1);
* :func:`make_weights` — random weights from a seed, made on the device
  in one jitted call, in the configuration's dtype;
* :func:`forward_flops` — the FLOPs one forward requires;
* :func:`classify_loss` — the plain float32 forward and the loss the
  federated task reads (the class logits at the last position);
* :func:`lm_loss` — the same forward and the next-token loss of the
  pre-training batches the sensitivity mask is calibrated on.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # float8_e4m3fn


def dims(c: dict) -> dict:
    """The widths the other functions use, from the published keys."""
    H = c["num_attention_heads"]
    return dict(
        L=c["num_hidden_layers"], D=c["hidden_size"], F=c["intermediate_size"],
        H=H, KV=c["num_key_value_heads"], V=c["vocab_size"],
        hd=c.get("head_dim") or c["hidden_size"] // H,
        eps=c["rms_norm_eps"], theta=float(c["rope_theta"]),
        bias=bool(c.get("attention_bias", c.get("model_type") == "qwen2")),
        qk_norm=c.get("model_type") == "qwen3",
        tied=bool(c["tie_word_embeddings"]))


def program_mismatches(cfg, c: dict, dtype: str) -> list:
    """Fields of the program's ModelConfig that differ from the published
    keys and the configuration's stored ``dtype`` (the registry entry plus
    the file's ``program`` overrides must be the model as published, apart
    from what ``reduced`` lists)."""
    d = dims(c)
    want = dict(dtype=dtype, n_layers=d["L"], d_model=d["D"], d_ff=d["F"],
                n_heads=d["H"], n_kv_heads=d["KV"], vocab=d["V"],
                resolved_head_dim=d["hd"], norm_eps=d["eps"], rope_theta=d["theta"], qkv_bias=d["bias"],
                qk_norm=d["qk_norm"], tie_embeddings=d["tied"], act="silu",
                norm="rmsnorm", layer_pattern=(("attn", "dense"),),
                attn_softcap=0.0, final_softcap=0.0, rope_style="full",
                embed_scale=False, post_norms=False, lora_rank=0)
    return [f"{k}: program {getattr(cfg, k)!r}, stated {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]


def weight_shapes(c: dict) -> dict:
    d = dims(c)
    L, D, F, H, KV, hd = d["L"], d["D"], d["F"], d["H"], d["KV"], d["hd"]
    lay = {"norm": {"scale": (L, D)}, "wq": (L, D, H * hd),
           "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
           "wo": (L, H * hd, D), "norm2": {"scale": (L, D)},
           "w1": (L, D, F), "w3": (L, D, F), "w2": (L, F, D)}
    if d["bias"]:
        lay.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    if d["qk_norm"]:
        lay.update(q_norm=(L, hd), k_norm=(L, hd))
    out = {"embed": (d["V"], D), "final_norm": {"scale": (D,)},
           "stack": {"p0": lay}}
    if not d["tied"]:
        out["lm_head"] = (D, d["V"])
    return out


def _std(path: str, L: int) -> float:
    # Out projections start smaller, as in GPT-2's scaled init; norm gain
    # offsets and biases are small and non-zero, so the reference sees
    # whether the program applies them.
    if path.endswith(("'wo']", "'w2']")):
        return 0.02 / math.sqrt(2 * L)
    return 0.02


def make_weights(c: dict, seed: int, dtype: str):
    """Random weights from ``seed``, on the device, in one jitted call."""
    shapes = weight_shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    L = dims(c)["L"]
    specs = [(jax.tree_util.keystr(p), s) for p, s in leaves]

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            out.append((_std(path, L) * jax.random.normal(k, shape))
                       .astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.key(seed))


def forward_flops(c: dict, seq_len: int) -> float:
    """FLOPs one forward of one sequence requires for the federated task:
    2 per matmul weight per token, causal attention at 4 * H * hd per key
    attended, and the output head at the one position the loss reads."""
    d = dims(c)
    D, F, H, KV, hd = d["D"], d["F"], d["H"], d["KV"], d["hd"]
    matmul = D * H * hd * 2 + D * KV * hd * 2 + 3 * D * F
    keys = (seq_len + 1) / 2
    per_token = d["L"] * (2 * matmul + 4 * H * hd * keys)
    return per_token * seq_len + 2 * D * d["V"]


# ---------------------------------------------------------------- reference
def _round_fp8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, gain_offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain_offset)


def _rope(x, theta):
    """Rotary embedding, rotate-half convention; x: [B, S, h, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer(d, fp8, x, w):
    w = _f32(w)  # the weights may be stored in a narrower dtype
    B, S, _ = x.shape
    H, KV, hd = d["H"], d["KV"], d["hd"]
    h = _rms(x, w["norm"]["scale"], d["eps"])
    q = _mm("bsd,de->bse", h, w["wq"], fp8)
    k = _mm("bsd,de->bse", h, w["wk"], fp8)
    v = _mm("bsd,de->bse", h, w["wv"], fp8)
    if d["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q, k, v = (t.reshape(B, S, -1, hd) for t in (q, k, v))
    if d["qk_norm"]:
        q = _rms(q, w["q_norm"], d["eps"])
        k = _rms(k, w["k_norm"], d["eps"])
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    k = jnp.repeat(k, H // KV, axis=2)  # query head h reads kv head h // G
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + _mm("bse,ed->bsd", o.reshape(B, S, H * hd), w["wo"], fp8)
    h = _rms(x, w["norm2"]["scale"], d["eps"])
    g = _mm("bsd,df->bsf", h, w["w1"], fp8)
    u = _mm("bsd,df->bsf", h, w["w3"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w2"], fp8), None


@partial(jax.jit, static_argnames=("c", "n_classes", "fp8"))
def classify_loss(weights, tokens, labels, *, c, n_classes: int,
                  fp8: bool = False):
    """Mean cross-entropy of the class logits (vocabulary ids
    ``0..n_classes-1``) at the last position, in float32 at HIGHEST
    precision; ``fp8`` rounds every matmul operand to float8 e4m3 with a
    per-tensor scale (the control).  ``c`` is the published config as a
    hashable tuple of items; ``weights`` are float32."""
    d = dims(dict(c))
    x = weights["embed"][tokens]
    x, _ = jax.lax.scan(partial(_layer, d, fp8), x, weights["stack"]["p0"])
    last = _rms(x[:, -1], weights["final_norm"]["scale"], d["eps"])
    head = (weights["embed"][:n_classes].T if d["tied"]
            else weights["lm_head"][:, :n_classes])
    logits = _mm("bd,dc->bc", last, head, fp8)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def lm_loss(weights, tokens, *, c, fp8: bool = False):
    """Mean next-token cross-entropy over every position but the last, in
    float32 at HIGHEST precision (``fp8`` as in :func:`classify_loss`).
    ``weights`` may hold leaves in their stored dtype: each is widened to
    float32 where it is read."""
    d = dims(dict(c))
    x = weights["embed"][tokens].astype(jnp.float32)
    # Rematerialised, so the gradient keeps one layer's input per layer
    # and not every layer's float32 weights and activations.
    x, _ = jax.lax.scan(jax.checkpoint(partial(_layer, d, fp8)), x,
                        weights["stack"]["p0"])
    x = _rms(x[:, :-1], _f32(weights["final_norm"]["scale"]), d["eps"])
    head = _f32(weights["embed"].T if d["tied"] else weights["lm_head"])
    logp = jax.nn.log_softmax(_mm("bsd,dv->bsv", x, head, fp8), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
