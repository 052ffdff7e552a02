"""Block-wise placement of sparse values into a leaf, in place.

The ZO loop's in-place perturb (``core/zo.py:_run_inplace``) writes
``n`` values at static, sorted, distinct positions of a weight leaf viewed
as ``[M, 128]`` rows of its (8, 128) tiles.  A point scatter does that one
value at a time.  This kernel rewrites the leaf one ``[bm, 128]`` row block
at a time instead, and only the blocks that hold positions: the leaf is
aliased to the output, so a block that no grid step names keeps its memory
as it is.

* The positions are cut, in order, into chunks of ``K``; each grid step is
  one (row block, chunk) pair that intersect, in block order, so a block
  holding more than ``K`` positions takes several consecutive steps and
  stays in VMEM between them.  The pairs are scalar-prefetched; each
  chunk's positions and values are ``[1, K]`` rows that BlockSpecs bring
  in, so no DMA is unaligned.
* Placement is two or four one-hot contractions on the MXU, one per byte
  of the value's bits: ``[bm, K]`` row one-hot against ``[128, K]`` (lane
  one-hot x byte), bf16 in, f32 out.  A byte is an integer up to 256 and
  each output element has at most one non-zero term, so the sums are exact
  integers; the top byte carries +1 to mark where a value landed.  The
  bits are put back together as integers, so every value, -0, NaN and
  infinities included, lands bit for bit as a scatter would write it.

:func:`plan` builds the static tables from the concrete positions once;
:func:`wins` is the cost model that decides whether the kernel beats a
point scatter for a leaf, with constants read on one TPU v5e.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _default_interpret

LANE = 128
# the block shape, tuned on one TPU v5e over bm 64-512 and K 256-1024 on
# the two cells' hot leaves: (256, 512) placed them fastest (2.66 ms for
# 1,393,103 values in Qwen2-1.5B's attention output, 3.14 ms for 1,196,777
# in Qwen3-4B's at 10 layers; the point scatter 14.36 and 12.73 ms)
BLOCK_ROWS = 256   # bm: rows of 128 values a grid step rewrites
CHUNK = 512        # K: positions a grid step places

# the cost model, one TPU v5e: a point scatter writes a value in about
# 10 ns; a grid step of this kernel costs STEP_S plus its one-hot MXU
# work, bm * K * bytes * MAC_S (fit to the same sweep: 0.33 us a step at
# bm * K * bytes = 65,536, 0.56 at 262,144, 0.90 at 524,288)
POINT_S = 10e-9
STEP_S = 0.24e-6
MAC_S = 1.25e-12


class Plan(NamedTuple):
    """Static tables of one leaf's placement.

    ``steps``/``blk``/``chk``: the grid's (row block, chunk) pairs, in
    order; ``pos``: the positions, ``-1`` past the end, ``[chunks, 1, K]``."""
    n: int
    rows: int
    bm: int
    k: int
    blk: np.ndarray
    chk: np.ndarray
    pos: np.ndarray

    @property
    def steps(self) -> int:
        return len(self.blk)


def plan(pos: np.ndarray, rows: int) -> Plan | None:
    """Tables for sorted, distinct positions ``pos`` of an ``[rows, 128]``
    view, or None where blocks of ``BLOCK_ROWS`` (or all ``rows``, if
    fewer) do not tile it."""
    pos = np.asarray(pos, np.int64)
    bm, k = min(BLOCK_ROWS, rows), CHUNK
    if rows % bm or bm % 16 or rows * LANE >= 2 ** 31 or not len(pos):
        return None
    n = len(pos)
    block = pos // LANE // bm
    chunk = np.arange(n) // k
    new = np.ones(n, bool)
    new[1:] = (block[1:] != block[:-1]) | (chunk[1:] != chunk[:-1])
    table = np.full(-(-n // k) * k, -1, np.int32)
    table[:n] = pos
    return Plan(n, rows, bm, k, block[new].astype(np.int32),
                chunk[new].astype(np.int32), table.reshape(-1, 1, k))


def wins(p: Plan | None, dtype) -> bool:
    """Whether the kernel is expected to place ``p``'s values faster than
    a point scatter (16- and 32-bit values only)."""
    nbytes = jnp.dtype(dtype).itemsize
    if p is None or nbytes not in (2, 4):
        return False
    mac = p.bm * p.k * nbytes * MAC_S
    return p.steps * (STEP_S + mac) < p.n * POINT_S


def _to_bits(v):
    """Values -> their bits as int32 (zero-extended below 32 bits)."""
    width = jnp.dtype(v.dtype).itemsize
    if width == 4:
        return jax.lax.bitcast_convert_type(v, jnp.int32)
    if width == 2:
        return jax.lax.bitcast_convert_type(v, jnp.uint16).astype(jnp.int32)
    raise ValueError(f"no block placement for {v.dtype}")


def _kernel(blk_ref, chk_ref, pos_ref, bits_ref, w_ref, out_ref, *, bm,
            nbytes):
    del chk_ref
    s = pl.program_id(0)
    b = blk_ref[s]

    @pl.when(jnp.logical_or(s == 0, b != blk_ref[jnp.maximum(s - 1, 0)]))
    def _():
        out_ref[...] = w_ref[...]

    pos = pos_ref[...]                       # [1, K]
    k = pos.shape[-1]
    row = (pos >> 7) - b * bm                # outside [0, bm): not here
    rows = (jax.lax.broadcasted_iota(jnp.int32, (bm, k), 0) == row
            ).astype(jnp.bfloat16)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (LANE, k), 0) == (pos & 127)
    bits = bits_ref[...]
    got = []
    for i in range(nbytes):
        byte = (bits >> (8 * i)) & 255
        if i == nbytes - 1:
            byte = byte + 1                  # marks a placed value
        rhs = jnp.where(lanes, byte.astype(jnp.float32), 0.0)
        got.append(jax.lax.dot_general(
            rows, rhs.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32))
    hit = got[-1] > 0
    got[-1] = got[-1] - 1
    placed = got[0]
    for i in range(1, nbytes):
        placed = placed | (got[i] << (8 * i))
    if nbytes == 2:
        placed = placed << 16   # a 16-bit float: the top half of its f32
    # select in f32, which holds every 16- or 32-bit float exactly
    w = out_ref[...]
    out_ref[...] = jnp.where(
        hit, jax.lax.bitcast_convert_type(placed, jnp.float32),
        w.astype(jnp.float32)).astype(w.dtype)


def place(w2d, vals, p: Plan, *, interpret: bool | None = None):
    """``w2d`` ``[rows, 128]`` with ``vals`` (``[n]``, ``w2d``'s dtype, in
    the order of ``p``'s positions) written at ``p``'s positions; ``w2d``
    is updated in place where XLA can donate it."""
    interpret = _default_interpret() if interpret is None else interpret
    rows, lane = w2d.shape
    assert (rows, lane) == (p.rows, LANE) and vals.shape == (p.n,)
    nbytes = jnp.dtype(w2d.dtype).itemsize
    chunks = p.pos.shape[0]
    bits = jnp.pad(_to_bits(vals), (0, chunks * p.k - p.n))
    row_spec = pl.BlockSpec((None, 1, p.k), lambda s, blk, chk: (chk[s], 0, 0))
    w_spec = pl.BlockSpec((p.bm, LANE), lambda s, blk, chk: (blk[s], 0))
    block_bytes = p.bm * LANE * nbytes
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, bm=p.bm, nbytes=nbytes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(p.steps,),
            in_specs=[row_spec, row_spec, w_spec], out_specs=w_spec),
        out_shape=jax.ShapeDtypeStruct(w2d.shape, w2d.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * p.steps * p.bm * p.k * LANE * nbytes,
            bytes_accessed=p.steps * (2 * block_bytes + 8 * p.k),
            transcendentals=0),
        interpret=interpret,
    )(jnp.asarray(p.blk), jnp.asarray(p.chk), jnp.asarray(p.pos),
      bits.reshape(chunks, 1, p.k), w2d)
