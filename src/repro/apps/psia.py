"""PSIA — parallel spin-image application (paper Table 1: N=20,000, LOW
task-time variance).

A task = one oriented point's spin image over the cloud (Eleliemy et al.
2016/2017).  Every task bins the same number of cloud points, so task
times are near-uniform (variance only from cache/bin effects) — the
paper's low-variance counterpart to Mandelbrot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import spin_image as spin_image_kernel
from repro.runtime.backends import chunk_to_host

PAPER_N = 20_000           # oriented points (tasks)
CLOUD = 16_384             # cloud points binned per task
N_ALPHA = N_BETA = 64
BLOCK_P = 1024             # cloud points binned per kernel grid step


@functools.lru_cache(maxsize=2)
def cloud(n: int = CLOUD, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    pts = jax.random.normal(key, (n, 3), jnp.float32)
    return pts


@functools.lru_cache(maxsize=2)
def oriented_points(n: int = PAPER_N, seed: int = 1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    ctr = jax.random.normal(k1, (n, 3), jnp.float32) * 0.5
    nrm = jax.random.normal(k2, (n, 3), jnp.float32)
    nrm = nrm / jnp.linalg.norm(nrm, axis=-1, keepdims=True)
    return ctr, nrm


def task_times(n_tasks: int = PAPER_N, *, cloud_n: int = CLOUD,
               time_per_point: float = 1.7e-5, jitter: float = 0.05,
               seed: int = 0) -> np.ndarray:
    """Near-uniform per-task durations (low variance, as in the paper).

    time_per_point is calibrated so a task ~ 0.28 s and the P=256 parallel
    time ~ 22 s — the paper's Fig. 3 PSIA scale, which matters because the
    perturbation experiments inject ABSOLUTE 10 s message delays."""
    rng = np.random.default_rng(seed)
    base = cloud_n * time_per_point
    return base * (1.0 + jitter * rng.standard_normal(n_tasks)).clip(0.5)


@functools.partial(jax.jit, static_argnames=("n_alpha", "n_beta"))
def _spin_images(pts, ctr, nrm, ids, *, n_alpha: int, n_beta: int):
    return spin_image_kernel(
        pts, ctr[ids], nrm[ids], n_alpha=n_alpha, n_beta=n_beta,
        alpha_max=3.0, beta_max=3.0, block_p=BLOCK_P)


def compute_tasks(task_ids, *, n: int = PAPER_N, cloud_n: int = CLOUD,
                  n_alpha: int = N_ALPHA, n_beta: int = N_BETA
                  ) -> np.ndarray:
    """Compute spin images for a chunk of oriented points (runtime tasks)
    as one device program: gather and kernel compile once per chunk
    size."""
    def dispatch():
        ctr, nrm = oriented_points(n)
        return _spin_images(cloud(cloud_n), ctr, nrm, jnp.asarray(task_ids),
                            n_alpha=n_alpha, n_beta=n_beta)
    return chunk_to_host(dispatch)
