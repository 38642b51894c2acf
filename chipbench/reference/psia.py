"""Plain jnp reference of PSIA's spin images.  It imports nothing of the
program and takes nothing the program made: the cloud and the oriented
points are made again here from the seeds the configuration states.

Spin image (Johnson 1997): for an oriented point (center p, unit normal
n), every cloud point x is binned in cylinder coordinates
``beta = n . (x - p)`` and ``alpha = sqrt(|x - p|^2 - beta^2)`` into an
(n_beta, n_alpha) histogram over ``[0, alpha_max) x [-beta_max,
beta_max)``.  The histogram is a sum of one-hot rows, in float32 (exact
for counts under 2^24).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 80          # oriented points per reference call


def inputs(cfg: dict):
    pts = jax.random.normal(jax.random.PRNGKey(cfg["cloud_seed"]),
                            (cfg["cloud_n"], 3), jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(cfg["points_seed"]))
    n = cfg["n_tasks"]
    ctr = jax.random.normal(k1, (n, 3), jnp.float32) * 0.5
    nrm = jax.random.normal(k2, (n, 3), jnp.float32)
    nrm = nrm / jnp.linalg.norm(nrm, axis=-1, keepdims=True)
    return pts, ctr, nrm


@functools.partial(jax.jit, static_argnames=("n_alpha", "n_beta",
                                             "alpha_max", "beta_max",
                                             "dtype"))
def spin_images(pts, ctr, nrm, *, n_alpha, n_beta, alpha_max, beta_max,
                dtype):
    """(Np, 3), (B, 3), (B, 3) -> (B, n_beta, n_alpha) float32; the
    cylinder coordinates are computed in ``dtype``."""
    pts, ctr, nrm = (a.astype(dtype) for a in (pts, ctr, nrm))
    d = pts[None, :, :] - ctr[:, None, :]
    beta = jnp.sum(d * nrm[:, None, :], axis=-1)
    r2 = jnp.sum(d * d, axis=-1)
    alpha = jnp.sqrt(jnp.maximum(r2 - beta * beta, 0.0))
    ai = jnp.floor(alpha / alpha_max * n_alpha).astype(jnp.int32)
    bi = jnp.floor((beta + beta_max) / (2 * beta_max)
                   * n_beta).astype(jnp.int32)
    valid = (ai >= 0) & (ai < n_alpha) & (bi >= 0) & (bi < n_beta)
    a_oh = jax.nn.one_hot(jnp.where(valid, ai, 0), n_alpha,
                          dtype=jnp.float32) * valid[..., None]
    b_oh = jax.nn.one_hot(jnp.where(valid, bi, 0), n_beta,
                          dtype=jnp.float32) * valid[..., None]
    return jnp.einsum("bpj,bpa->bja", b_oh, a_oh,
                      precision=jax.lax.Precision.HIGHEST)


def _kw(cfg: dict, dtype) -> dict:
    return dict(n_alpha=cfg["n_alpha"], n_beta=cfg["n_beta"],
                alpha_max=cfg["alpha_max"], beta_max=cfg["beta_max"],
                dtype=dtype)


def chunk_fn(cfg: dict, dtype=jnp.float32):
    """The reference in the program's place: rows [start, stop),
    ``BLOCK`` oriented points a call so that any chunk fits."""
    pts, ctr, nrm = inputs(cfg)
    ctr, nrm = (jnp.pad(a, ((0, BLOCK), (0, 0))) for a in (ctr, nrm))
    kw = _kw(cfg, dtype)

    def fn(start: int, stop: int) -> np.ndarray:
        return np.concatenate([
            np.asarray(spin_images(pts, ctr[s:s + BLOCK], nrm[s:s + BLOCK],
                                   **kw))[:stop - s]
            for s in range(start, stop, BLOCK)])
    return fn


def compute(cfg: dict, dtype=jnp.float32) -> np.ndarray:
    """All ``n_tasks`` spin images."""
    return chunk_fn(cfg, dtype)(0, cfg["n_tasks"])
