"""Kernel-dispatch layer: flat backing + pallas-vs-ref backend parity.

The fused flat route (core/dispatch.py -> kernels/zo_update.py) must be a
drop-in replacement for the pytree ``space.add`` reference route on every
hot-path entry point, including multi-direction estimation (n_dirs > 1) and
flat sizes that are not multiples of the kernels' block_r * 128 tile
(the ops.py padding path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DenseSpace, LoRASpace, get_backing, random_mask,
                        resolve_backend, round_keys)
from repro.core.fl_step import make_fl_round_step, make_fl_train_step
from repro.core.virtual_path import reconstruct_delta
from repro.core.zo import local_step, make_local_run, projected_gradient


def vec_params(key, sizes=((24,), (4, 6))):
    ks = jax.random.split(key, len(sizes))
    return {f"p{i}": jax.random.normal(k, s)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def total_size(params):
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


def vec_loss(params, batch):
    # mean keeps the loss O(1) at every size: (l+ - l-) / 2eps amplifies f32
    # rounding of the loss ~500x, so parity needs a well-conditioned problem
    v = jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(params)])
    return 0.5 * jnp.mean((v - batch["target"]) ** 2)


def vec_per_example(params, batch):
    v = jnp.concatenate([l.reshape(-1) for l in jax.tree.leaves(params)])
    return 0.5 * jnp.mean((v[None, :] - batch["target"]) ** 2, axis=-1)


# --------------------------------------------------------- flat backing -----

def test_flatten_unflatten_roundtrip_is_exact():
    params = vec_params(jax.random.key(0), sizes=((7, 11), (33,), ()))
    space = random_mask(params, density=0.3, seed=1)
    b = get_backing(space, params)
    assert b.n_flat == total_size(params)
    # through the space-level flat API (delegates to the cached backing)
    out = space.unflatten(space.flatten(params), params)
    for a, c in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_full_coverage_mask_local_run_shapes():
    """density=1.0 makes a MaskedSpace whose flat backing is the identity;
    the pallas route must still return [n]-shaped deltas (regression: the
    identity restrict once leaked the tile-padded [n_pad] vector)."""
    params = vec_params(jax.random.key(30))
    space = random_mask(params, density=1.0, seed=0)
    b = get_backing(space, params)
    assert b.identity and b.n_pad > space.n
    keys = round_keys(0, 0, 2)
    batches = {"target": jax.random.normal(jax.random.key(31),
                                           (2, total_size(params)))}
    run = jax.jit(make_local_run(vec_loss, space, 1e-3, 1e-2,
                                 backend="pallas"))
    d_T, gs = run(params, keys, batches, jnp.zeros((space.n,), jnp.float32))
    assert d_T.shape == (space.n,)
    d_srv = reconstruct_delta(space, keys, gs, 1e-2)
    np.testing.assert_allclose(np.asarray(d_T), np.asarray(d_srv), atol=1e-6)


def test_expand_restrict_roundtrip_and_mask():
    params = vec_params(jax.random.key(1))
    space = random_mask(params, density=0.25, seed=2)
    b = get_backing(space, params)
    v = jax.random.normal(jax.random.key(3), (space.n,))
    dense = b.expand(v)
    np.testing.assert_array_equal(np.asarray(b.restrict(dense)),
                                  np.asarray(v))
    assert float(np.sum(b.mask)) == space.n
    # expand only writes the masked coordinates
    assert int((np.asarray(dense) != 0).sum()) <= space.n


def test_dense_space_backing_is_identity():
    params = vec_params(jax.random.key(2))
    space = DenseSpace(params)
    b = get_backing(space, params)
    assert b.identity
    v = jax.random.normal(jax.random.key(4), (space.n,))
    dense = np.asarray(b.expand(v))
    np.testing.assert_array_equal(dense[:space.n], np.asarray(v))
    # the tile-alignment tail is zero so kernels never see garbage
    assert not dense[space.n:].any()


def test_lora_space_backing_covers_only_lora_leaves():
    params = {"w": jnp.ones((4, 4)), "lora_a": jnp.ones((4, 2)),
              "lora_b": jnp.ones((2, 4))}
    space = LoRASpace(params)
    b = get_backing(space, params)
    assert space.n == 16 and b.n_flat == 32
    dense = b.expand(jnp.ones((space.n,)))
    # the w block (leaf order is sorted keys: lora_a, lora_b, w) stays zero
    assert float(jnp.sum(dense)) == 16.0
    np.testing.assert_array_equal(np.asarray(b.restrict(dense)),
                                  np.ones(16, np.float32))


def test_backing_cached_per_layout():
    params = vec_params(jax.random.key(5))
    space = random_mask(params, density=0.5, seed=0)
    assert get_backing(space, params) is get_backing(space, params)


# ----------------------------------------------------- backend resolution ---

def test_auto_prefers_pallas_and_falls_back():
    params = vec_params(jax.random.key(6))
    space = random_mask(params, density=0.5, seed=0)
    b = get_backing(space, params)
    assert resolve_backend(None, b) == "pallas"
    assert resolve_backend("auto", b) == "pallas"
    assert resolve_backend("ref", b) == "ref"
    # sharded steps never take the flat route (GSPMD reshape hazard)
    assert resolve_backend("auto", b, sharded=True) == "ref"
    with pytest.raises(ValueError):
        resolve_backend("cuda", b)


def test_auto_budget_follows_device_reported_memory(monkeypatch):
    from repro.core import dispatch
    params = vec_params(jax.random.key(6))
    space = random_mask(params, density=0.5, seed=0)
    b = get_backing(space, params)
    need = dispatch.FLAT_ROUTE_BYTES_PER_COORD * b.n_pad

    class Device:  # what a TPU reports: limit and bytes already in use
        def __init__(self, free):
            self.free = free

        def memory_stats(self):
            return {"bytes_limit": self.free + 1000, "bytes_in_use": 1000}

    monkeypatch.setattr(dispatch.jax, "devices", lambda: [Device(need)])
    assert resolve_backend("auto", b) == "pallas"
    assert resolve_backend("auto", b, dense_carry=2) == "ref"
    assert resolve_backend("pallas", b, dense_carry=2) == "pallas"
    monkeypatch.setattr(dispatch.jax, "devices", lambda: [Device(need - 1)])
    assert resolve_backend("auto", b) == "ref"


def test_auto_falls_back_on_mixed_dtypes():
    params = {"a": jnp.ones((8,), jnp.float32),
              "b": jnp.ones((8,), jnp.bfloat16)}
    space = random_mask(params, density=0.5, seed=0)
    b = get_backing(space, params)
    assert not b.supported
    assert resolve_backend("auto", b) == "ref"


# ------------------------------------------------------- step parity --------

# sizes chosen to exercise the (R, 128) padding path: sub-lane (48),
# non-multiple-of-128 (5000), and > one 256*128 block (40_000)
PARITY_SIZES = [((24,), (4, 6)), ((40, 125), (3,)), ((163, 245), (65,))]


@pytest.mark.parametrize("sizes", PARITY_SIZES)
@pytest.mark.parametrize("n_dirs", [1, 3])
def test_local_step_parity(sizes, n_dirs):
    params = vec_params(jax.random.key(7), sizes=sizes)
    n_total = total_size(params)
    space = random_mask(params, density=0.2, seed=3)
    batch = {"target": jax.random.normal(jax.random.key(8), (n_total,))}
    delta = 0.01 * jax.random.normal(jax.random.key(9), (space.n,))
    out = {}
    for be in ("ref", "pallas"):
        out[be] = local_step(vec_loss, params, space, delta,
                             jax.random.key(10), 1e-3, 1e-2, batch,
                             n_dirs=n_dirs, backend=be)
    np.testing.assert_allclose(np.asarray(out["ref"][0]),
                               np.asarray(out["pallas"][0]),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out["ref"][1]),
                               np.asarray(out["pallas"][1]),
                               rtol=1e-2, atol=5e-3)


@pytest.mark.parametrize("n_dirs", [1, 2])
def test_local_run_parity_and_virtual_path_exactness(n_dirs):
    """The pallas T-step loop matches ref AND stays exactly reconstructible
    from the uploaded scalars (paper Alg. 2 step 2)."""
    T, lr = 4, 1e-2
    params = vec_params(jax.random.key(11))
    space = random_mask(params, density=0.4, seed=4)
    keys = round_keys(5, 0, T)
    batches = {"target": jax.random.normal(jax.random.key(12),
                                           (T, total_size(params)))}
    delta0 = jnp.zeros((space.n,), jnp.float32)
    runs = {be: jax.jit(make_local_run(vec_loss, space, 1e-3, lr,
                                       n_dirs=n_dirs, backend=be))
            for be in ("ref", "pallas")}
    d_ref, g_ref = runs["ref"](params, keys, batches, delta0)
    d_pal, g_pal = runs["pallas"](params, keys, batches, delta0)
    if n_dirs > 1:
        assert g_pal.shape == (T, n_dirs)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pal),
                               rtol=1e-2, atol=5e-3)
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_pal),
                               rtol=1e-3, atol=1e-4)
    # exactness vs the server-side replay of the *pallas* scalars
    d_srv = reconstruct_delta(space, keys, g_pal, lr)
    np.testing.assert_allclose(np.asarray(d_pal), np.asarray(d_srv),
                               atol=1e-6)


def test_full_coverage_permuted_mask_is_not_identity():
    """A mask covering every coordinate in a *permuted* order must not take
    the identity shortcut — expand/restrict have to honor the index order
    (regression: n == N alone used to be treated as identity)."""
    from repro.core import MaskedSpace

    params = {"a": jnp.arange(8.0), "b": jnp.arange(6.0).reshape(2, 3)}
    perm_a = jnp.asarray([3, 0, 7, 1, 5, 2, 6, 4], jnp.int32)
    perm_b = jnp.asarray([5, 2, 0, 4, 1, 3], jnp.int32)
    space = MaskedSpace({"a": perm_a, "b": perm_b})
    b = get_backing(space, params)
    assert space.n == b.n_flat and not b.identity
    v = jnp.arange(1.0, space.n + 1.0)
    dense = b.expand(v)
    # value v[i] must land at the permuted position, not position i
    np.testing.assert_array_equal(np.asarray(dense)[np.asarray(perm_a)],
                                  np.asarray(v[:8]))
    np.testing.assert_array_equal(np.asarray(b.restrict(dense)),
                                  np.asarray(v))
    batch = {"target": jnp.zeros(space.n)}
    out = {be: local_step(vec_loss, params, space, jnp.zeros((space.n,)),
                          jax.random.key(0), 1e-3, 1e-2, batch, backend=be)
           for be in ("ref", "pallas")}
    np.testing.assert_allclose(np.asarray(out["ref"][0]),
                               np.asarray(out["pallas"][0]),
                               rtol=1e-3, atol=1e-4)


def test_projected_gradient_parity():
    params = vec_params(jax.random.key(13))
    space = DenseSpace(params)
    batch = {"target": jnp.zeros(total_size(params))}
    z = space.sample_z(jax.random.key(14))
    delta = jnp.zeros((space.n,))
    g_ref = projected_gradient(vec_loss, params, space, delta, z, 1e-4,
                               batch, backend="ref")
    g_pal = projected_gradient(vec_loss, params, space, delta, z, 1e-4,
                               batch, backend="pallas")
    assert abs(float(g_ref) - float(g_pal)) < 1e-3 * max(1.0,
                                                         abs(float(g_ref)))


@pytest.mark.parametrize("sizes", PARITY_SIZES)
def test_fl_train_step_parity(sizes):
    n_clients, bs = 4, 2
    params = vec_params(jax.random.key(15), sizes=sizes)
    space = random_mask(params, density=0.2, seed=6)
    batch = {"target": jax.random.normal(jax.random.key(16),
                                         (n_clients * bs,
                                          total_size(params)))}
    out = {}
    for be in ("ref", "pallas"):
        step = jax.jit(make_fl_train_step(vec_per_example, space, eps=1e-3,
                                          lr=1e-2, n_clients=n_clients,
                                          backend=be))
        out[be] = step(params, jax.random.key(17), batch)
    np.testing.assert_allclose(np.asarray(out["ref"][1]),
                               np.asarray(out["pallas"][1]),
                               rtol=1e-2, atol=5e-3)
    for a, b in zip(jax.tree.leaves(out["ref"][0]),
                    jax.tree.leaves(out["pallas"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    assert np.allclose(float(out["ref"][2]["loss"]),
                       float(out["pallas"][2]["loss"]), rtol=1e-4)


@pytest.mark.parametrize("backend,stack_forwards",
                         [("pallas", True), ("pallas", False),
                          ("pallas", None), ("ref", None)])
def test_fl_train_loop_parity(backend, stack_forwards):
    """The scanned burst == folding make_fl_train_step, on the ref-route
    scan (the bench's naive baseline) and both fused forward strategies
    (stacked vmap / sequential) plus the auto pick."""
    from repro.core.fl_step import make_fl_train_loop

    n_clients, bs, n_steps = 4, 2, 3
    params = vec_params(jax.random.key(40), sizes=((48,), (8, 12)))
    space = random_mask(params, density=0.2, seed=41)
    batches = {"target": jax.random.normal(
        jax.random.key(42), (n_steps, n_clients * bs, total_size(params)))}
    kw = dict(eps=1e-3, lr=1e-2, n_clients=n_clients)
    key = jax.random.key(43)

    loop = jax.jit(make_fl_train_loop(vec_per_example, space, n_steps=n_steps,
                                      backend=backend,
                                      stack_forwards=stack_forwards, **kw))
    p_loop, gs_loop, m_loop = loop(params, key, batches)

    # fold the single-step factory over the same keys/batches
    step = jax.jit(make_fl_train_step(vec_per_example, space, backend="ref",
                                      **kw))
    p, gs = params, []
    for t, k in enumerate(jax.random.split(key, n_steps)):
        p, g_cl, m = step(p, k, jax.tree.map(lambda x: x[t], batches))
        gs.append(np.asarray(g_cl))
    np.testing.assert_allclose(np.asarray(gs_loop), np.stack(gs),
                               rtol=1e-2, atol=5e-3)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p_loop)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    assert np.allclose(float(m["loss"]), float(m_loop["loss"]), rtol=1e-4)


def test_fl_round_step_parity_vmapped_clients():
    T, K = 3, 2
    params = vec_params(jax.random.key(18))
    space = random_mask(params, density=0.3, seed=7)
    keys = round_keys(8, 0, T)
    batches = {"target": jax.random.normal(jax.random.key(19),
                                           (K, T, total_size(params)))}
    out = {}
    for be in ("ref", "pallas"):
        step = jax.jit(make_fl_round_step(vec_loss, space, eps=1e-3, lr=1e-2,
                                          T=T, backend=be))
        out[be] = step(params, keys, batches)
    np.testing.assert_allclose(np.asarray(out["ref"][1]),
                               np.asarray(out["pallas"][1]),
                               rtol=1e-2, atol=5e-3)
    for a, b in zip(jax.tree.leaves(out["ref"][0]),
                    jax.tree.leaves(out["pallas"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_pallas_updates_only_masked_coords():
    """Off-mask coordinates survive the fused update bitwise."""
    params = vec_params(jax.random.key(20))
    space = random_mask(params, density=0.1, seed=9)
    b = get_backing(space, params)
    batch = {"target": jnp.zeros(total_size(params))}
    delta, _ = local_step(vec_loss, params, space,
                          jnp.zeros((space.n,)), jax.random.key(21),
                          1e-3, 1e-2, batch, backend="pallas")
    step = jax.jit(make_fl_train_step(vec_per_example, space, eps=1e-3,
                                      lr=1e-2, n_clients=1,
                                      backend="pallas"))
    new_params, _, _ = step(params, jax.random.key(22),
                            {"target": jnp.zeros((2, total_size(params)))})
    w0 = np.asarray(b.flatten(params))
    w1 = np.asarray(b.flatten(new_params))
    off = np.asarray(b.mask) == 0.0
    np.testing.assert_array_equal(w0[off], w1[off])
