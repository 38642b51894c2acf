"""PSIA spin-image Pallas kernel (the paper's low-variance application).

Spin image (Johnson'97): for an oriented point (center p, normal n) and a
cloud X, bin every x in cylinder coordinates
    beta  = n . (x - p)           (signed height)
    alpha = sqrt(|x-p|^2 - beta^2) (radius)
into an (n_beta, n_alpha) histogram.

HARDWARE ADAPTATION (DESIGN.md §2): the CPU/GPU formulation is a
scatter-add histogram — hostile to the TPU (no fast scatter, MXU idle).
We reformulate binning as ONE-HOT MATMUL: for a block of P points build
one-hot bin matrices B1 (P, n_beta), A1 (P, n_alpha) on the VPU and
accumulate `image += B1^T @ A1` on the MXU.  The histogram becomes a
(n_beta, P) x (P, n_alpha) matmul per block — the idiomatic TPU histogram.

Grid: (n_centers / ROWS, n_point_blocks); each step bins one block of
the cloud for ROWS oriented points.  The point-block axis is sequential
("arbitrary") with the ROWS images accumulated in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.dispatch import pallas_call


ROWS = 8        # oriented points per grid step: one f32 sublane tile


def _kernel(pts_ref, ctr_ref, nrm_ref, out_ref, acc, *,
            n_alpha: int, n_beta: int, alpha_max: float, beta_max: float,
            n_points: int, block_p: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    pts = pts_ref[...]                       # (block_p, 3)
    # padding rows (beyond n_points) are invalid
    pid = j * block_p + jnp.arange(block_p)
    a_bins = jnp.arange(n_alpha)[None, :]
    b_bins = jnp.arange(n_beta)[None, :]

    def row(r, carry):                       # one oriented point
        ctr = ctr_ref[r]                     # (1, 3)
        nrm = nrm_ref[r]                     # (1, 3)
        d = pts - ctr
        beta = jnp.sum(d * nrm, axis=-1)     # (block_p,)
        r2 = jnp.sum(d * d, axis=-1)
        alpha = jnp.sqrt(jnp.maximum(r2 - beta * beta, 0.0))
        ai = jnp.floor(alpha / alpha_max * n_alpha).astype(jnp.int32)
        bi = jnp.floor((beta + beta_max) / (2 * beta_max)
                       * n_beta).astype(jnp.int32)
        valid = ((ai >= 0) & (ai < n_alpha) & (bi >= 0) & (bi < n_beta)
                 & (pid < n_points))
        vf = valid.astype(jnp.float32)[:, None]
        a_oh = (a_bins == jnp.where(valid, ai, 0)[:, None]
                ).astype(jnp.float32) * vf   # (P, n_alpha)
        b_oh = (b_bins == jnp.where(valid, bi, 0)[:, None]
                ).astype(jnp.float32) * vf   # (P, n_beta)
        acc[r] += jax.lax.dot_general(
            b_oh, a_oh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (n_beta, n_alpha), MXU
        return carry

    jax.lax.fori_loop(0, ROWS, row, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc[...]


@functools.partial(jax.jit, static_argnames=(
    "n_alpha", "n_beta", "alpha_max", "beta_max", "block_p"))
def spin_image(points: jax.Array, centers: jax.Array, normals: jax.Array,
               *, n_alpha: int = 64, n_beta: int = 64,
               alpha_max: float = 1.0, beta_max: float = 1.0,
               block_p: int = 512) -> jax.Array:
    """points: (Np,3) f32; centers/normals: (Bo,3) -> (Bo,n_beta,n_alpha).

    Oriented points go ROWS to a grid step (padded up to a multiple of
    ROWS; pad rows are computed and dropped), so the (ROWS, 3) blocks
    meet the TPU's (8, 128) tiling for any Bo."""
    Np = points.shape[0]
    Bo = centers.shape[0]
    block_p = min(block_p, max(8, Np))
    pad = (-Np) % block_p
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nblocks = pts.shape[0] // block_p
    rpad = ((0, (-Bo) % ROWS), (0, 0))
    # (Bo, 1, 3): the row loop indexes the leading dim of the block
    ctr, nrm = (jnp.pad(t, rpad)[:, None, :] for t in (centers, normals))
    out = pallas_call(
        functools.partial(_kernel, n_alpha=n_alpha, n_beta=n_beta,
                          alpha_max=alpha_max, beta_max=beta_max,
                          n_points=Np, block_p=block_p),
        grid=(ctr.shape[0] // ROWS, nblocks),
        in_specs=[
            pl.BlockSpec((block_p, 3), lambda b, j: (j, 0)),
            pl.BlockSpec((ROWS, 1, 3), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((ROWS, 1, 3), lambda b, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, n_beta, n_alpha),
                               lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ctr.shape[0], n_beta, n_alpha),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((ROWS, n_beta, n_alpha), jnp.float32)],
    )(pts, ctr, nrm)
    return out[:Bo]
