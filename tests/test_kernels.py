"""Pallas kernel sweeps (interpret mode) vs the pure-jnp oracles."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# --------------------------------------------------------------- mandelbrot
@pytest.mark.parametrize("side,bm,bn", [(64, 32, 32), (128, 64, 128),
                                        (96, 32, 96)])
@pytest.mark.parametrize("max_iters", [16, 100])
def test_mandelbrot_matches_ref(side, bm, bn, max_iters):
    xs = jnp.linspace(-2.0, 1.0, side)
    ys = jnp.linspace(-1.5, 1.5, side)
    cr, ci = jnp.meshgrid(xs, ys)
    got = ops.mandelbrot(cr, ci, max_iters=max_iters, bm=bm, bn=bn)
    want = ref.mandelbrot(cr, ci, max_iters)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32
    # sanity: set interior exists and has max count
    assert int(got.max()) == max_iters


# --------------------------------------------------------------- spin image
def _edge_cloud(ctr, nrm, np_pts, na, nb, amax, bmax):
    """Cloud points on the (alpha, beta) bin edges of the oriented points
    (point i on those of oriented point i % Bo).  For an oriented point in
    general position each lands within a rounding of its edge, on either
    side, so that only the same operations in the same order bin it
    alike; for one at the origin along z, with edges on dyadic fractions,
    the points and their cylinder coordinates are exact."""
    rng = np.random.default_rng(np_pts)
    c = np.asarray(ctr, np.float64)
    n = np.asarray(nrm, np.float64)
    u = np.cross(n, [0.0, 1.0, 0.0])
    u /= np.linalg.norm(u, axis=-1, keepdims=True)
    v = np.cross(n, u)
    own = np.arange(np_pts) % len(c)
    turn = np.array([[1, 0], [0, 1], [-1, 0], [0, -1]])[
        rng.integers(0, 4, np_pts)]
    a = rng.integers(0, na + 1, np_pts)[:, None] * amax / na
    b = rng.integers(0, nb + 1, np_pts)[:, None] * (2 * bmax) / nb - bmax
    pts = (c[own] + b * n[own]
           + a * (turn[:, :1] * u[own] + turn[:, 1:] * v[own]))
    return jnp.asarray(pts, jnp.float32)


_SPIN_CASES = [(257, 3, 64), (1024, 7, 256), (100, 1, 128),
               # FAC chunk sizes: pad rows, several row blocks, and a
               # partial last block of 1,024 points
               (1300, 1, 1024), (1300, 8, 1024), (1300, 9, 1024),
               (1300, 39, 1024)]


@pytest.mark.parametrize("np_pts,bo,block_p,edges", [
    pytest.param(*c, False, id="-".join(map(str, c))) for c in _SPIN_CASES
] + [pytest.param(2048, 3, 512, True, id="2048-3-512-edges")])
@pytest.mark.parametrize("na,nb", [(32, 16), (64, 64)])
def test_spin_image_matches_ref(np_pts, bo, block_p, edges, na, nb):
    k = jax.random.PRNGKey(np_pts + bo)
    k1, k2, k3 = jax.random.split(k, 3)
    pts = jax.random.normal(k1, (np_pts, 3), jnp.float32)
    ctr = jax.random.normal(k2, (bo, 3), jnp.float32) * 0.2
    nrm = jax.random.normal(k3, (bo, 3), jnp.float32)
    nrm = nrm / jnp.linalg.norm(nrm, axis=-1, keepdims=True)
    kw = dict(n_alpha=na, n_beta=nb, alpha_max=2.5, beta_max=2.5)
    if edges:   # oriented point 0 at the origin along z: exact edges
        ctr = ctr.at[0].set(0.0)
        nrm = nrm.at[0].set(jnp.array([0.0, 0.0, 1.0]))
        pts = _edge_cloud(ctr, nrm, np_pts, na, nb, 2.5, 2.5)
    got = ops.spin_image(pts, ctr, nrm, block_p=block_p, **kw)
    # the oracle compiled as the interpreted kernel is, so that both take
    # the same floating-point contractions
    want = jax.jit(functools.partial(ref.spin_image, **kw))(pts, ctr, nrm)
    # counts: the kernel's histogram is the reference's, bin for bin
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # histogram mass = number of in-range points, never more than Np
    assert float(got.sum()) <= bo * np_pts


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,S,D,bq,bk", [
    (2, 128, 32, 64, 64), (1, 256, 64, 128, 64), (3, 64, 16, 64, 64),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, D, bq, bk, causal, dtype):
    k = jax.random.PRNGKey(B * S + D)
    k1, k2, k3 = jax.random.split(k, 3)
    q = jax.random.normal(k1, (B, S, D), dtype)
    kk = jax.random.normal(k2, (B, S, D), dtype)
    v = jax.random.normal(k3, (B, S, D), dtype)
    got = ops.flash_attention(q, kk, v, causal=causal, bq=bq, bk=bk)
    want = ref.attention(q, kk, v, causal=causal)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_attention_mixed_dv():
    """MLA-style: qk dim != v dim."""
    k = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(k, 3)
    q = jax.random.normal(k1, (2, 128, 48))
    kk = jax.random.normal(k2, (2, 128, 48))
    v = jax.random.normal(k3, (2, 128, 32))
    got = ops.flash_attention(q, kk, v, causal=True, bq=64, bk=64)
    want = ref.attention(q, kk, v, causal=True, scale=48 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_mha_flash_wrapper():
    k = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(k, 3)
    q = jax.random.normal(k1, (2, 128, 4, 32))
    kk = jax.random.normal(k2, (2, 128, 4, 32))
    v = jax.random.normal(k3, (2, 128, 4, 32))
    got = ops.mha_flash(q, kk, v)
    for h in range(4):
        want = ref.attention(q[:, :, h], kk[:, :, h], v[:, :, h])
        np.testing.assert_allclose(np.asarray(got[:, :, h]),
                                   np.asarray(want), atol=1e-5)


# ----------------------------------------------------------------- wkv6
@pytest.mark.parametrize("T,dk,dv,chunk", [
    (64, 16, 16, 16), (128, 32, 32, 32), (96, 8, 24, 32), (32, 64, 64, 32),
])
def test_wkv6_matches_sequential_ref(T, dk, dv, chunk):
    k = jax.random.PRNGKey(T + dk)
    ks = jax.random.split(k, 5)
    r = jax.random.normal(ks[0], (T, dk))
    kk = jax.random.normal(ks[1], (T, dk))
    v = jax.random.normal(ks[2], (T, dv))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (T, dk)) * 0.5 - 1.0))
    u = jax.random.normal(ks[4], (dk,))
    s0 = jnp.zeros((dk, dv))
    got_y, got_s = ops.wkv6(r, kk, v, w, u, s0, chunk=chunk)
    want_y, want_s = ref.wkv6(r, kk, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-4, rtol=1e-3)


def test_wkv6_nonzero_initial_state():
    T, dk, dv = 32, 16, 16
    k = jax.random.PRNGKey(9)
    ks = jax.random.split(k, 6)
    r = jax.random.normal(ks[0], (T, dk))
    kk = jax.random.normal(ks[1], (T, dk))
    v = jax.random.normal(ks[2], (T, dv))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (T, dk)) * 0.3))
    u = jax.random.normal(ks[4], (dk,))
    s0 = jax.random.normal(ks[5], (dk, dv))
    got_y, _ = ops.wkv6(r, kk, v, w, u, s0, chunk=16)
    want_y, _ = ref.wkv6(r, kk, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=2e-4, rtol=1e-3)


def test_wkv6_chunked_jnp_twin():
    """models.rwkv6.wkv6_chunked is the same math as the kernel."""
    from repro.models.rwkv6 import wkv6_chunked
    T, dk, dv = 64, 16, 16
    k = jax.random.PRNGKey(3)
    ks = jax.random.split(k, 5)
    r = jax.random.normal(ks[0], (T, dk))
    kk = jax.random.normal(ks[1], (T, dk))
    v = jax.random.normal(ks[2], (T, dv))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (T, dk)) * 0.4))
    u = jax.random.normal(ks[4], (dk,))
    s0 = jnp.zeros((dk, dv))
    y1, s1 = wkv6_chunked(r, kk, v, w, u, s0, chunk=16)
    y2, s2 = ops.wkv6(r, kk, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=2e-4, rtol=1e-3)
