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
import re

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


def _step_loop_ops(hlo: str):
    """(opcode, element count) of each instruction in a while loop that
    runs inside another: the ZO step loop inside the group's map over
    clients, the forward's layer loops and all they call.  A tuple result
    counts its first element.  An async copy between memory spaces (a
    ``copy-start`` whose source or destination is in another space than
    HBM: the compiler's prefetch of a weight into on-chip memory, or its
    eviction) is named ``copy-start:memory-space``."""
    comps, body, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = comps.setdefault(head.group(2), [])
            entry = entry or (head.group(2) if head.group(1) else None)
        elif body is not None and line.startswith("  "):
            body.append(line)
    callees = {c: {n for l in ls for n in re.findall(r"%([\w.\-]+)", l)
                   if n in comps and n != c} for c, ls in comps.items()}
    loops = {c: {b for l in ls for b in re.findall(r"body=%([\w.\-]+)", l)}
             for c, ls in comps.items()}

    def reach(c, seen):
        if c not in seen:
            seen.add(c)
            for d in callees[c]:
                reach(d, seen)
        return seen

    def bodies_under(c):
        return {b for d in reach(c, set()) for b in loops[d]}

    inner = set()
    for b in bodies_under(entry):
        for nested in bodies_under(b) - {b}:
            reach(nested, inner)
    ops = []
    for c in inner:
        for l in comps[c]:
            shape = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]", l)
            op = re.search(r" ([a-z][\w-]*)\(%", l)
            if shape and op:
                name = op.group(1)
                if name == "copy-start" and "S(" in l[:op.start()]:
                    name += ":memory-space"
                ops.append((name, int(np.prod(
                    [int(d) for d in shape.group(1).split(",") if d]))))
    return ops


def test_zo_group_perturbs_in_place(one_chip):
    """The client group program the server builds (4 clients x 5 ZO steps
    on the ``ref`` route, a random mask at density 1e-3) at Qwen2-1.5B's
    widths, two layers deep (the leaves are stacked, and their minor dims
    set the layout).  The in-place perturb leaves no whole-leaf copy or
    relayout of a masked leaf, and no sort of a leaf's indices, inside the
    step loop."""
    from repro.core import make_local_run
    from repro.core.masks import random_mask
    from repro.data.synthetic import TaskSpec, make_task_fns
    from repro.models import Model

    K, T, b, S = 4, 5, 16, 256
    model = Model(CFG.replace(n_layers=2))
    abstract = model.abstract_params()
    space = random_mask(abstract, density=1e-3, seed=0, balanced=False)
    loss, _, _ = make_task_fns(model, TaskSpec(
        vocab=CFG.vocab, n_classes=4, seq_len=S))
    run = make_local_run(loss, space, 1e-3, 5e-2, backend="ref")

    def group(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(lambda bt: run(params, keys, bt, zeros), batches)

    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          abstract)
    keys = jax.ShapeDtypeStruct((T,), jax.random.key(0).dtype,
                                sharding=one_chip)
    batches = {"tokens": _sds((K, T, b, S), jnp.int32, one_chip),
               "label": _sds((K, T, b), jnp.int32, one_chip)}
    hlo = jax.jit(group).lower(params, keys, batches).compile().as_text()
    masked = {int(np.prod(p.shape)) for p, i in zip(
        jax.tree.leaves(abstract), jax.tree.leaves(space.idx_tree))
        if i.shape[0]}
    ops = _step_loop_ops(hlo)
    assert any(op == "scatter" for op, _ in ops)  # the loop was found
    whole = [(op, n) for op, n in ops
             if op in ("copy", "copy-start", "reshape") and n in masked]
    assert not whole
    # no sort of a leaf's indices: the one sort is the step's permutation
    # of delta +- eps z into the scatters' order, over all n coordinates
    assert [n for op, n in ops if op == "sort"] == [space.n]


def test_shapes_are_qwen2_1_5b():
    assert (H, KV, HD, H // KV) == (12, 2, 128, 6)
    assert np.isclose(param_count(CFG), 1.54e9, rtol=0.01)
