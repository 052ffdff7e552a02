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
    eviction) is named ``copy-start:memory-space``, and a Pallas kernel's
    custom call ``tpu_custom_call``."""
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
                if 'custom_call_target="tpu_custom_call"' in l:
                    name = "tpu_custom_call"
                ops.append((name, int(np.prod(
                    [int(d) for d in shape.group(1).split(",") if d]))))
    return ops


def _hot_mask(abstract, hot: float, rest: float):
    """A mask as calibration chooses one: the attention output ``wo`` at
    density ``hot``, every other leaf at ``rest``."""
    from repro.core import MaskedSpace
    rng = np.random.default_rng(0)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    idx = []
    for path, leaf in flat:
        d = hot if jax.tree_util.keystr(path).endswith("['wo']") else rest
        size = int(np.prod(leaf.shape))
        idx.append(jnp.asarray(np.sort(rng.choice(
            size, max(1, round(size * d)), replace=False)), jnp.int32))
    return MaskedSpace(jax.tree_util.tree_unflatten(treedef, idx))


def _check_group_in_place(one_chip, cfg, hot=None):
    """The client group program the server builds (4 clients x 5 ZO steps
    in place, a random mask at density 1e-3, or ``hot``'s
    (density of ``wo``, of the rest)) at ``cfg``'s widths, two layers deep
    (the leaves are stacked, and their minor dims set the layout).  The
    in-place perturb leaves no whole-leaf copy or relayout of a masked
    leaf, and no sort of a leaf's indices, inside the step loop.  Each leaf
    the block kernel places (``kernels/block_place.py``, as the rule in
    ``_run_inplace`` routes under a TPU compile) is written by two
    Pallas custom calls a step and by no scatter; the counters split the
    coordinates as the rule does."""
    from repro import obs
    from repro.core import make_local_run
    from repro.core.masks import random_mask
    from repro.core.zo import _tile_positions, _tileable
    from repro.data.synthetic import TaskSpec, make_task_fns
    from repro.kernels import block_place
    from repro.models import Model

    K, T, b, S = 4, 5, 16, 256
    model = Model(cfg.replace(n_layers=2))
    abstract = model.abstract_params()
    space = (random_mask(abstract, density=1e-3, seed=0, balanced=False)
             if hot is None else _hot_mask(abstract, *hot))
    loss, _, _ = make_task_fns(model, TaskSpec(
        vocab=cfg.vocab, n_classes=4, seq_len=S))
    run = make_local_run(loss, space, 1e-3, 5e-2)

    def group(params, keys, batches):
        zeros = jnp.zeros((space.n,), jnp.float32)
        return jax.lax.map(lambda bt: run(params, keys, bt, zeros), batches)

    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                          abstract)
    keys = jax.ShapeDtypeStruct((T,), jax.random.key(0).dtype,
                                sharding=one_chip)
    batches = {"tokens": _sds((K, T, b, S), jnp.int32, one_chip),
               "label": _sds((K, T, b), jnp.int32, one_chip)}
    names = ("zo.perturb_inplace", "zo.perturb.block_coords",
             "zo.perturb.point_coords")
    before = [obs.totals()["counters"].get(k, 0) for k in names]
    hlo = jax.jit(group).lower(params, keys, batches).compile().as_text()
    live = [(p, np.asarray(i)) for p, i in zip(
        jax.tree.leaves(abstract), jax.tree.leaves(space.idx_tree))
        if i.shape[0]]
    blocks = [(p, i) for p, i in live if _tileable(p.shape) and
              block_place.wins(block_place.plan(
                  np.sort(_tile_positions(i, p.shape)), p.size // 128),
                  p.dtype)]
    masked = {int(np.prod(p.shape)) for p, _ in live}
    ops = _step_loop_ops(hlo)
    # the loop was found
    assert any(op in ("scatter", "tpu_custom_call") for op, _ in ops)
    whole = [(op, n) for op, n in ops
             if op in ("copy", "copy-start", "reshape") and n in masked]
    assert not whole
    # no sort of a leaf's indices: the one sort is the step's permutation
    # of delta +- eps z into the scatters' order, over all n coordinates
    assert [n for op, n in ops if op == "sort"] == [space.n]
    for size in {p.size for p, _ in blocks}:
        writes = [op for op, n in ops if n == size]
        n_blocks = sum(p.size == size for p, _ in blocks)
        n_points = sum(p.size == size for p, _ in live) - n_blocks
        assert writes.count("scatter") == 2 * n_points
        assert writes.count("tpu_custom_call") == 2 * n_blocks
    n_block = sum(len(i) for _, i in blocks)
    after = [obs.totals()["counters"].get(k, 0) for k in names]
    assert np.subtract(after, before).tolist() == [
        1, n_block, space.n - n_block]
    return n_block / space.n


def test_zo_group_perturbs_in_place(one_chip):
    """Qwen2-1.5B: 12 query heads over 2 KV heads, q/k/v biases."""
    _check_group_in_place(one_chip, CFG)


def test_zo_group_perturbs_in_place_qwen3(one_chip):
    """Qwen3-4B: a query wider than d_model ([L, 2560, 4096]), and the
    [L, 128] q_norm/k_norm leaves, which no (8, 128) tile covers, carried
    untiled."""
    _check_group_in_place(one_chip, get_config("qwen3-4b"))


@pytest.mark.parametrize("arch,hot,rest,share", [
    ("qwen2-1.5b", 0.0211, 1.02e-4, 0.7), ("qwen3-4b", 0.0114, 1.56e-4, 0.6)])
def test_zo_group_places_hot_leaf_by_blocks(one_chip, monkeypatch, arch, hot,
                                            rest, share):
    """Compiled for the TPU (the platform test steered here, where the
    compiler runs without a chip), the group program places ``wo``, which
    holds most coordinates as in the cells' masks (PERF.md section 5), with
    the block kernel inside the step loop; the densities are the cells'
    (``wo``'s share of the mask is smaller at two layers)."""
    from repro.core import zo
    from repro.kernels import block_place
    monkeypatch.setattr(zo, "_default_interpret", lambda: False)
    monkeypatch.setattr(block_place, "_default_interpret", lambda: False)
    assert _check_group_in_place(one_chip, get_config(arch),
                                 (hot, rest)) > share


def test_block_place_compiles(one_chip):
    """The kernel alone on Qwen2-1.5B's attention-output leaf at its 28
    layers, with the coordinates its calibrated mask holds there."""
    from repro.kernels import block_place
    rows = CFG.n_layers * CFG.d_model * CFG.d_model // 128
    n = 1_393_103
    pos = np.sort(np.random.default_rng(0).choice(rows * 128, n,
                                                  replace=False))
    p = block_place.plan(pos, rows)
    assert block_place.wins(p, jnp.bfloat16)
    hlo = _compile_hlo(lambda w, v: block_place.place(w, v, p,
                                                      interpret=False),
                       _sds((rows, 128), jnp.bfloat16, one_chip),
                       _sds((n,), jnp.bfloat16, one_chip))
    assert hlo.count("tpu_custom_call") == 1


def test_shapes_are_qwen2_1_5b():
    assert (H, KV, HD, H // KV) == (12, 2, 128, 6)
    assert np.isclose(param_count(CFG), 1.54e9, rtol=0.01)
