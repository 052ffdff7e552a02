"""GradIP blocked-reduction Pallas kernel.

GradIP_t = g_t * <gp, z_t> over the sparse coordinates (Definition 2.3).
The dot product is a grid-sequential reduction into one resident f32
(8, 128) output tile: each block's products are folded onto the tile
with vector adds, so the kernel never stores a scalar to VMEM (Mosaic
refuses that).  The wrapper sums the tile and multiplies by g.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUB = 8
BLOCK_R = 256


def _gradip_kernel(gp_ref, z_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    prod = gp_ref[...].astype(jnp.float32) * z_ref[...].astype(jnp.float32)
    out_ref[...] += prod.reshape(-1, SUB, LANE).sum(axis=0)


def gradip_reduce(gp, z, g, *, block_r: int = BLOCK_R, interpret: bool = True):
    """gp, z: [R, 128]; g: scalar. Returns g * sum(gp * z) as f32 scalar."""
    R, C = gp.shape
    assert C == LANE and R % block_r == 0 and block_r % SUB == 0, (
        gp.shape, block_r)
    spec = pl.BlockSpec((block_r, LANE), lambda i: (i, 0))
    tile = pl.pallas_call(
        _gradip_kernel,
        grid=(R // block_r,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((SUB, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUB, LANE), jnp.float32),
        interpret=interpret,
    )(gp, z)
    return jnp.asarray(g, jnp.float32) * jnp.sum(tile)
