"""Public model facade + per-shape input specs (incl. frontend stubs)."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import decode as D
from repro.models import transformer as T
from repro.models.init import (abstract_params, active_param_count,
                               init_params, param_count)


class Model:
    """Thin stateless facade bundling config + apply functions."""

    def __init__(self, cfg: ModelConfig, ctx: T.ShardCtx = T.DEFAULT_CTX):
        self.cfg = cfg
        self.ctx = ctx

    def init(self, key, dtype=None):
        """Random weights in ``dtype`` (default ``cfg.dtype``)."""
        return init_params(key, self.cfg, dtype=dtype)

    def abstract_params(self, dtype=None):
        return abstract_params(self.cfg, dtype=dtype)

    def forward(self, params, batch):
        return T.forward(params, batch, self.cfg, self.ctx)

    def loss(self, params, batch, per_example: bool = False):
        return T.lm_loss(params, batch, self.cfg, self.ctx,
                         per_example=per_example)

    def prefill(self, params, batch, S_max: int = 0, lengths=None):
        return D.prefill(params, batch, self.cfg, self.ctx, S_max=S_max,
                         lengths=lengths)

    def decode_step(self, params, token, cache, active=None):
        return D.decode_step(params, token, cache, self.cfg, self.ctx,
                             active=active)

    def init_cache(self, B: int, S_max: int, dtype=jnp.bfloat16):
        return D.init_cache(self.cfg, B, S_max, dtype)

    def abstract_cache(self, B: int, S_max: int, dtype=jnp.bfloat16):
        return D.abstract_cache(self.cfg, B, S_max, dtype)

    @property
    def n_params(self):
        return param_count(self.cfg)

    @property
    def n_active_params(self):
        return active_param_count(self.cfg)


def input_specs(cfg: ModelConfig, shape: InputShape,
                dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a given shape.

    * train / prefill: tokens [B, S] (+ frontend embeds)
    * decode: token [B] (the cache is built separately)
    """
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        specs = {"token": sds((B,), jnp.int32)}
    else:
        specs = {"tokens": sds((B, S), jnp.int32)}
    if shape.kind != "decode":
        if cfg.frontend == "audio_stub":
            nf = cfg.encoder.n_frames if cfg.encoder else 1500
            specs["audio_embeds"] = sds((B, nf, cfg.d_model), dtype)
        elif cfg.frontend == "vision_stub":
            specs["patch_embeds"] = sds((B, cfg.n_patches, cfg.d_model), dtype)
    elif cfg.frontend == "audio_stub":
        # decode for enc-dec needs nothing extra: cross K/V live in the cache
        pass
    return specs


def concrete_inputs(cfg: ModelConfig, shape: InputShape, key=None,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Random concrete inputs matching :func:`input_specs` (smoke tests)."""
    key = key if key is not None else jax.random.key(0)
    specs = input_specs(cfg, shape, dtype=dtype)
    out = {}
    for name, s in specs.items():
        key, k = jax.random.split(key)
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab)
        else:
            out[name] = jax.random.normal(k, s.shape, dtype)
    return out
