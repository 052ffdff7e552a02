"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

Interpret mode (every other kernel test) cannot see Mosaic's tiling,
layout or scalar-memory rules.  These tests hand the real compiler a
described ``v5e:2x2`` topology and Qwen2-1.5B's shapes (head_dim 128,
12 query heads over 2 KV heads so G = 6, batch 4) and check that each
kernel lowers to a ``tpu_custom_call``.  Nothing runs: a compile that
passes here is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import functools
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.dispatch import _TILE  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.init import param_count  # noqa: E402

CFG = get_config("qwen2-1.5b")
B, S = 4, 2048
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_hlo(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def test_zo_flat_kernels_compile(one_chip):
    # a flat vector the size of the tied embedding, the largest leaf (the
    # whole model's flat route needs ~23 GB and is refused for HBM: auto
    # sends full-width groups down the pytree route, core/dispatch.py)
    n_pad = -(-CFG.vocab * CFG.d_model // _TILE) * _TILE
    w = _sds((n_pad,), jnp.bfloat16, one_chip)
    z = _sds((n_pad,), jnp.float32, one_chip)
    eps = _sds((), jnp.float32, one_chip)
    _compile_hlo(lambda w, z, e: ops.zo_dual_perturb_flat(
        w, z, None, e, interpret=False), w, z, eps)
    _compile_hlo(lambda w, z, e: ops.zo_fused_update_flat(
        w, z, None, e, interpret=False), w, z, eps)


def test_gradip_reduce_compiles(one_chip):
    n = round(param_count(CFG) * 1e-3)  # the mask at density 1e-3
    gp = _sds((n,), jnp.float32, one_chip)
    g = _sds((), jnp.float32, one_chip)
    _compile_hlo(functools.partial(ops.gradip_flat, interpret=False),
                 gp, gp, g)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles(one_chip, direction):
    q = _sds((B, S, H, HD), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KV, HD), jnp.bfloat16, one_chip)
    lengths = _sds((B,), jnp.int32, one_chip)
    attn = functools.partial(ops.flash_attention, block_q=128, block_k=128,
                             interpret=False)
    if direction == "forward":
        _compile_hlo(attn, q, kv, kv, lengths)
        return

    def loss(q, k, v, lengths):
        return jnp.sum(attn(q, k, v, lengths).astype(jnp.float32))

    hlo = _compile_hlo(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, lengths)
    # forward + the dQ and dK/dV passes
    assert hlo.count("tpu_custom_call") >= 3


def test_flash_decode_compiles(one_chip):
    q = _sds((B, KV, H // KV, HD), jnp.bfloat16, one_chip)
    cache = _sds((B, S, KV, HD), jnp.bfloat16, one_chip)
    lengths = _sds((B,), jnp.int32, one_chip)
    _compile_hlo(functools.partial(ops.flash_decode, interpret=False),
                 q, cache, cache, lengths)


def test_shapes_are_qwen2_1_5b():
    assert (H, KV, HD, H // KV) == (12, 2, 128, 6)
    assert np.isclose(param_count(CFG), 1.54e9, rtol=0.01)
