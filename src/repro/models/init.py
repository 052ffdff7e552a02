"""Parameter initialization for every architecture family.

Layer parameters are *stacked over periods*: for each position ``i`` in
``cfg.layer_pattern`` the subtree ``stack['p{i}']`` has a leading
``n_periods`` axis, so the forward pass can ``lax.scan`` over periods.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.ssm import _dt_rank


def _norm_p(cfg, d, n=None, kind=None):
    kind = kind or cfg.norm
    shape = (n, d) if n else (d,)
    p = {"scale": jnp.zeros(shape) if kind == "rmsnorm" else jnp.ones(shape)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros(shape)
    return p


class _KeyGen:
    """Split-on-call key stream; ``dtype`` is the dtype weights are
    created in, cast leaf by leaf so no full-model f32 copy is ever held."""

    def __init__(self, key, dtype):
        self.key = key
        self.dtype = dtype

    def __call__(self):
        self.key, k = jax.random.split(self.key)
        return k


def _dense(kg, shape, std=0.02, n=None):
    shape = (n, *shape) if n else shape
    return (jax.random.normal(kg(), shape) * std).astype(kg.dtype)


def _attn_params(kg, cfg: ModelConfig, n: int, cross: bool = False):
    D = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "norm": _norm_p(cfg, D, n),
        "wq": _dense(kg, (D, H * hd), n=n),
        "wk": _dense(kg, (D, KV * hd), n=n),
        "wv": _dense(kg, (D, KV * hd), n=n),
        "wo": _dense(kg, (H * hd, D), std=out_std, n=n),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((n, H * hd))
        p["bk"] = jnp.zeros((n, KV * hd))
        p["bv"] = jnp.zeros((n, KV * hd))
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((n, hd))
        p["k_norm"] = jnp.zeros((n, hd))
    if cfg.post_norms and not cross:
        p["post_norm"] = _norm_p(cfg, D, n)
    if cfg.lora_rank and not cross:
        r = cfg.lora_rank
        p["lora_qa"] = _dense(kg, (D, r), n=n)
        p["lora_qb"] = jnp.zeros((n, r, H * hd))
        p["lora_va"] = _dense(kg, (D, r), n=n)
        p["lora_vb"] = jnp.zeros((n, r, KV * hd))
    return p


def _mlp_params(kg, cfg: ModelConfig, n: int):
    D, F = cfg.d_model, cfg.d_ff
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "norm2": _norm_p(cfg, D, n),
        "w1": _dense(kg, (D, F), n=n),
        "w2": _dense(kg, (F, D), std=out_std, n=n),
    }
    if cfg.act != "gelu_plain":
        p["w3"] = _dense(kg, (D, F), n=n)
    if cfg.post_norms:
        p["post_norm2"] = _norm_p(cfg, D, n)
    return p


def _moe_params(kg, cfg: ModelConfig, n: int):
    m = cfg.moe
    D, F, E = cfg.d_model, m.d_ff_expert, m.n_experts
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    p = {
        "norm2": _norm_p(cfg, D, n),
        "router": _dense(kg, (D, E), n=n),
        "w1": _dense(kg, (E, D, F), n=n),
        "w3": _dense(kg, (E, D, F), n=n),
        "w2": _dense(kg, (E, F, D), std=out_std, n=n),
    }
    if m.n_shared_experts:
        Fs = F * m.n_shared_experts
        p["sw1"] = _dense(kg, (D, Fs), n=n)
        p["sw3"] = _dense(kg, (D, Fs), n=n)
        p["sw2"] = _dense(kg, (Fs, D), std=out_std, n=n)
    return p


def _mamba_params(kg, cfg: ModelConfig, n: int):
    s = cfg.ssm
    D = cfg.d_model
    E = s.expand * D
    N = s.d_state
    r = _dt_rank(D, s)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (E, 1))
    return {
        "norm": _norm_p(cfg, D, n),
        "in_proj": _dense(kg, (D, 2 * E), n=n),
        "conv_w": _dense(kg, (s.d_conv, E), std=0.2, n=n),
        "conv_b": jnp.zeros((n, E)),
        "x_proj": _dense(kg, (E, r + 2 * N), n=n),
        "dt_proj": _dense(kg, (r, E), std=r ** -0.5, n=n),
        "dt_bias": jnp.tile(jnp.log(jnp.expm1(jnp.full((E,), 0.01)))[None], (n, 1)),
        "A_log": jnp.tile(jnp.log(A)[None], (n, 1, 1)),
        "D": jnp.ones((n, E)),
        "out_proj": _dense(kg, (E, D), std=out_std, n=n),
    }


def _mlstm_params(kg, cfg: ModelConfig, n: int):
    x = cfg.xlstm
    D = cfg.d_model
    E = int(x.proj_factor_mlstm * D)
    H = x.n_heads
    dh = E // H
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "norm": _norm_p(cfg, D, n),
        "up_proj": _dense(kg, (D, 2 * E), n=n),
        "wq": _dense(kg, (E, E), n=n),
        "wk": _dense(kg, (E, E), n=n),
        "wv": _dense(kg, (E, E), n=n),
        "w_i": _dense(kg, (E, H), std=0.01, n=n),
        "b_i": jnp.zeros((n, H)),
        "w_f": _dense(kg, (E, H), std=0.01, n=n),
        "b_f": jnp.full((n, H), 3.0),  # forget-gate bias -> remember
        "gn_scale": jnp.ones((n, H, dh)),
        "down_proj": _dense(kg, (E, D), std=out_std, n=n),
    }


def _slstm_params(kg, cfg: ModelConfig, n: int):
    x = cfg.xlstm
    D = cfg.d_model
    E = D
    H = x.n_heads
    dh = E // H
    F = int(x.proj_factor_slstm * E)
    F -= F % 2
    out_std = 0.02 / math.sqrt(2 * cfg.n_layers)
    return {
        "norm": _norm_p(cfg, D, n),
        "w_gates": _dense(kg, (D, 4 * E), n=n),
        "b_gates": jnp.concatenate(
            [jnp.zeros((n, E)), jnp.full((n, E), 3.0), jnp.zeros((n, 2 * E))],
            axis=-1),
        "r_gates": _dense(kg, (H, dh, 4, dh), std=dh ** -0.5, n=n),
        "up_proj": _dense(kg, (E, 2 * F), n=n),
        "down_proj": _dense(kg, (F, D), std=out_std, n=n),
    }


def _stack_params(kg, cfg: ModelConfig, pattern, n_periods: int,
                  with_cross: bool = False):
    stack = {}
    for i, (mixer, ffn) in enumerate(pattern):
        lp = {}
        if mixer in ("attn", "local_attn"):
            lp.update(_attn_params(kg, cfg, n_periods))
            if with_cross:
                lp["cross"] = dict(_attn_params(kg, cfg, n_periods, cross=True),
                                   norm=_norm_p(cfg, cfg.d_model, n_periods))
        elif mixer == "mamba":
            lp.update(_mamba_params(kg, cfg, n_periods))
        elif mixer == "mlstm":
            lp.update(_mlstm_params(kg, cfg, n_periods))
        elif mixer == "slstm":
            lp.update(_slstm_params(kg, cfg, n_periods))
        else:
            raise ValueError(mixer)
        if ffn == "dense":
            lp.update(_mlp_params(kg, cfg, n_periods))
        elif ffn == "moe":
            lp.update(_moe_params(kg, cfg, n_periods))
        stack[f"p{i}"] = lp
    return stack


def init_params(key, cfg: ModelConfig, dtype=None):
    """Initialize the full parameter pytree for ``cfg`` in ``dtype``
    (default: the configuration's own ``cfg.dtype``)."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    kg = _KeyGen(key, dtype)
    params = {
        "embed": _dense(kg, (cfg.vocab, cfg.d_model)),
        "stack": _stack_params(kg, cfg, cfg.layer_pattern, cfg.n_periods,
                               with_cross=cfg.encoder is not None),
        "final_norm": _norm_p(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(kg, (cfg.d_model, cfg.vocab))
    if cfg.encoder is not None:
        params["encoder"] = {
            "stack": _stack_params(kg, cfg, (("attn", "dense"),),
                                   cfg.encoder.n_layers),
            "final_norm": _norm_p(cfg, cfg.d_model),
        }
    return jax.tree.map(lambda a: a.astype(dtype), params)


def abstract_params(cfg: ModelConfig, dtype=None):
    """ShapeDtypeStruct pytree (no allocation) — used by the dry-run."""
    return jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg, dtype=dtype))


def param_count(cfg: ModelConfig) -> int:
    tree = abstract_params(cfg)
    return int(sum(math.prod(l.shape) for l in jax.tree.leaves(tree)))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: only top-k + shared experts count)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    n_moe_layers = cfg.n_periods * sum(1 for _, f in cfg.layer_pattern if f == "moe")
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    inactive = n_moe_layers * per_expert * (m.n_experts - m.top_k)
    return total - inactive
