"""PSIA spin-image Pallas kernel (the paper's low-variance application).

Spin image (Johnson'97): for an oriented point (center p, normal n) and a
cloud X, bin every x in cylinder coordinates
    beta  = n . (x - p)           (signed height)
    alpha = sqrt(|x-p|^2 - beta^2) (radius)
into an (n_beta, n_alpha) histogram.

HARDWARE ADAPTATION (DESIGN.md §2): the CPU/GPU formulation is a
scatter-add histogram — hostile to the TPU (no fast scatter, MXU idle).
We reformulate binning as ONE-HOT MATMUL: for a block of P points build
one-hot bin matrices B1 (n_beta, P), A1 (n_alpha, P) on the VPU and
accumulate `image += B1 @ A1^T` on the MXU.  The histogram becomes a
(n_beta, P) x (P, n_alpha) matmul per block — the idiomatic TPU histogram.

Layout: every per-pair quantity is lane-dense.  The cloud is read as
(3, P) rows x, y, z with the points on lanes; a grid step takes ROWS
oriented points as (ROWS, 1) columns, so the cylinder coordinates of all
ROWS x P pairs are (ROWS, P) arrays computed once.  Each row's one-hots
have the bins on sublanes and the points on lanes.  They go to the MXU as
bfloat16: 0 and 1 are exact, every product is 0 or 1 and the float32 sums
are exact below 2^24, so the image equals the float32 histogram bit for
bit.  The coordinates and the image stay float32.

Grid: (n_centers / ROWS, n_point_blocks); each step bins one block of
the cloud for ROWS oriented points.  The point-block axis is sequential
("arbitrary") with the ROWS images accumulated in the resident output
block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.dispatch import pallas_call


ROWS = 8        # oriented points per grid step: one f32 sublane tile
LANES = 128


def _kernel(pts_ref, ctr_ref, nrm_ref, out_ref, ai_ref, bi_ref, *,
            n_alpha: int, n_beta: int, alpha_max: float, beta_max: float,
            n_points: int, block_p: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (1, block_p) cloud rows against (ROWS, 1) oriented-point columns:
    # every pair below is a (ROWS, block_p) array
    x, y, z = (pts_ref[k:k + 1, :] for k in range(3))
    ctr, nrm = ctr_ref[...], nrm_ref[...]
    dx, dy, dz = (p - ctr[:, k:k + 1] for k, p in enumerate((x, y, z)))
    nx, ny, nz = (nrm[:, k:k + 1] for k in range(3))
    beta = (dx * nx + dy * ny) + dz * nz
    r2 = (dx * dx + dy * dy) + dz * dz
    alpha = jnp.sqrt(jnp.maximum(r2 - beta * beta, 0.0))
    ai = jnp.floor(alpha / alpha_max * n_alpha).astype(jnp.int32)
    bi = jnp.floor((beta + beta_max) / (2 * beta_max)
                   * n_beta).astype(jnp.int32)
    valid = (ai >= 0) & (ai < n_alpha) & (bi >= 0) & (bi < n_beta)
    if n_points % block_p:              # padding points are invalid
        pid = j * block_p + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_p), 1)
        valid &= pid < n_points
    ai_ref[...] = ai
    bi_ref[...] = jnp.where(valid, bi, -1)   # an invalid pair bins nowhere

    a_bins = jax.lax.broadcasted_iota(jnp.int32, (n_alpha, 1), 0)
    b_bins = jax.lax.broadcasted_iota(jnp.int32, (n_beta, 1), 0)

    def row(r, carry):                       # one oriented point
        a_oh = (a_bins == ai_ref[pl.ds(r, 1), :]).astype(jnp.bfloat16)
        b_oh = (b_bins == bi_ref[pl.ds(r, 1), :]).astype(jnp.bfloat16)
        # exact at any precision: DEFAULT keeps a process-wide
        # "highest" from asking Mosaic for an fp32 contraction of bf16
        out_ref[r] += jax.lax.dot_general(
            b_oh, a_oh, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)  # (n_beta, n_alpha), MXU
        return carry

    jax.lax.fori_loop(0, ROWS, row, 0)


@functools.partial(jax.jit, static_argnames=(
    "n_alpha", "n_beta", "alpha_max", "beta_max", "block_p"))
def spin_image(points: jax.Array, centers: jax.Array, normals: jax.Array,
               *, n_alpha: int = 64, n_beta: int = 64,
               alpha_max: float = 1.0, beta_max: float = 1.0,
               block_p: int = 512) -> jax.Array:
    """points: (Np,3) f32; centers/normals: (Bo,3) -> (Bo,n_beta,n_alpha).

    The cloud is binned block_p points a grid step (the last block padded
    with invalid points); it is handed to the kernel as (3, Np) rows.
    Oriented points go ROWS to a grid step (padded up to a multiple of
    ROWS; pad rows are computed and dropped), so the (ROWS, 3) blocks
    meet the TPU's (8, 128) tiling for any Bo."""
    Np = points.shape[0]
    Bo = centers.shape[0]
    block_p = min(block_p, pl.cdiv(Np, LANES) * LANES)
    pts = jnp.pad(points.T, ((0, 0), (0, (-Np) % block_p)))
    nblocks = pts.shape[1] // block_p
    rpad = ((0, (-Bo) % ROWS), (0, 0))
    ctr, nrm = (jnp.pad(t, rpad) for t in (centers, normals))
    out = pallas_call(
        functools.partial(_kernel, n_alpha=n_alpha, n_beta=n_beta,
                          alpha_max=alpha_max, beta_max=beta_max,
                          n_points=Np, block_p=block_p),
        grid=(ctr.shape[0] // ROWS, nblocks),
        in_specs=[
            pl.BlockSpec((3, block_p), lambda b, j: (0, j)),
            pl.BlockSpec((ROWS, 3), lambda b, j: (b, 0)),
            pl.BlockSpec((ROWS, 3), lambda b, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, n_beta, n_alpha),
                               lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ctr.shape[0], n_beta, n_alpha),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((ROWS, block_p), jnp.int32)] * 2,
        name="spin_image",
    )(pts, ctr, nrm)
    return out[:Bo]
