"""Pallas kernel sweeps: shapes x dtypes, allclose vs the ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]
SIZES = [1024, 4096, 40_000, 262_144]


@pytest.mark.parametrize("n", SIZES)
def test_gradip_sweep(n):
    key = jax.random.key(n + 13)
    gp = jax.random.normal(key, (n,))
    z = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    out = ops.gradip_flat(gp, z, 1.7)
    want = ref.gradip_reduce_ref(gp, z, 1.7)
    assert abs(float(out) - float(want)) < 5e-4 * max(1.0, abs(float(want)))


@pytest.mark.parametrize("B,KVH,G,dh,S,L", [
    (1, 1, 1, 64, 512, 512),
    (2, 2, 4, 64, 1024, 700),
    (2, 4, 2, 128, 2048, 1),
    (1, 8, 8, 128, 1024, 1023),
])
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_decode_sweep(B, KVH, G, dh, S, L, dtype):
    key = jax.random.key(B * S)
    q = jax.random.normal(key, (B, KVH, G, dh), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KVH, dh), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KVH, dh), dtype)
    out = ops.flash_decode(q, k, v, L, block_s=256)
    want = ref.decode_attention_ref(q, k, v, L)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_flash_decode_matches_model_attention():
    """The kernel agrees with the model's decode attention math (GQA)."""
    from repro.models.layers import gqa_attention
    from repro.configs.tiny import TINY
    B, KV, G, hd, S, L = 2, 2, 2, 32, 256, 100
    key = jax.random.key(0)
    q4 = jax.random.normal(key, (B, 1, KV * G, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    valid = (jnp.arange(S) < L)[None, None, :]
    want = gqa_attention(q4, k, v, valid, TINY)[:, 0]  # [B, H, hd]
    # kernel layout: [B, KVH, G, dh]; heads grouped kv-major (repeat semantics)
    qk = q4[:, 0].reshape(B, KV, G, hd)
    out = ops.flash_decode(qk, k, v, L, block_s=64)
    np.testing.assert_allclose(np.asarray(out.reshape(B, KV * G, hd)),
                               np.asarray(want.reshape(B, KV * G, hd)),
                               atol=2e-5)


# ------------------------------------------------------- mamba scan ---------
@pytest.mark.parametrize("B,S,E,N,eb,sb", [
    (1, 256, 128, 8, 128, 128),
    (2, 512, 256, 16, 128, 256),
    (1, 384, 128, 16, 64, 128),
    (2, 256, 512, 4, 256, 64),
])
def test_mamba_scan_sweep(B, S, E, N, eb, sb):
    from repro.kernels.mamba_scan import mamba_scan
    key = jax.random.key(B * 1000 + S)
    ks = jax.random.split(key, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, E))) * 0.1
    Bi = jax.random.normal(ks[1], (B, S, N))
    Ci = jax.random.normal(ks[2], (B, S, N))
    x = jax.random.normal(ks[3], (B, S, E))
    A = -jnp.exp(jax.random.normal(ks[4], (E, N)))
    y, h = mamba_scan(dt, Bi, Ci, x, A, e_block=eb, s_block=sb,
                      interpret=True)
    yr, hr = ref.mamba_scan_ref(dt, Bi, Ci, x, A)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_mamba_kernel_mode_matches_scan_mode():
    """mamba_forward(mode='kernel') == mode='scan' on a reduced config."""
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.ssm import mamba_forward

    cfg = get_config("jamba-1.5-large-398b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    # find a mamba layer's params in the stacked tree
    stack = params["stack"]
    mamba_lp = None
    for k in stack:
        if "in_proj" in stack[k] and "A_log" in stack[k]:
            mamba_lp = jax.tree.map(lambda l: l[0], stack[k])
            break
    assert mamba_lp is not None, list(stack)
    x = jax.random.normal(jax.random.key(1), (2, 256, cfg.d_model))
    y_scan = mamba_forward(x, mamba_lp, cfg.ssm, mode="scan")
    y_kern = mamba_forward(x, mamba_lp, cfg.ssm, mode="kernel")
    np.testing.assert_allclose(np.asarray(y_scan, np.float32),
                               np.asarray(y_kern, np.float32),
                               atol=5e-3, rtol=5e-3)


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("layout", ["spread", "one_block", "skewed"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_block_place_matches_scatter_bitwise(dtype, layout):
    """The block kernel writes the bits a point scatter writes, -0, NaN and
    infinities included, and leaves every other value as it was."""
    from repro.kernels import block_place
    rows = 4 * block_place.BLOCK_ROWS
    rng = np.random.default_rng(len(layout))
    block = block_place.BLOCK_ROWS * block_place.LANE
    if layout == "spread":
        pos = rng.choice(rows * 128, 900, replace=False)
    elif layout == "one_block":
        pos = 2 * block + rng.choice(block, 40, replace=False)
    else:
        pos = np.concatenate([rng.choice(block, 1300, replace=False),
                              3 * block + rng.choice(block, 7, replace=False)])
    pos = np.sort(pos)
    p = block_place.plan(pos, rows)
    w = jax.random.normal(jax.random.key(1), (rows, 128)).astype(dtype)
    vals = jax.random.normal(jax.random.key(2), (len(pos),)).astype(dtype)
    vals = vals.at[:4].set(jnp.asarray([-0.0, jnp.nan, jnp.inf, -jnp.inf],
                                       dtype))
    want = w.reshape(-1).at[jnp.asarray(pos)].set(vals).reshape(rows, 128)
    got = jax.jit(lambda w, v: block_place.place(w, v, p))(w, vals)
    np.testing.assert_array_equal(_bits(got), _bits(want))
