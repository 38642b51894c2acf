"""Mandelbrot escape-time Pallas kernel.

The paper's high-task-time-variance application (Table 1: N=262,144
iterations with "high variability among iterations") — variance comes from
the escape-time loop: interior points burn max_iters, exterior escape
early.  The rDLB experiments schedule *rows/tiles* of this grid as tasks.

TPU mapping: grid over (M/bm, N/bn) VMEM tiles, both axes parallel; the
escape loop is a fori_loop over fused VPU ops on the whole (bm, bn) tile.
Escaped lanes are frozen (masked select) — no divergence penalty on the
VPU, and no NaN pollution from diverged z values.  Tile 256x256 f32 ~
256 KB/operand in VMEM: far under the 16 MB budget, big enough to amortize
grid overhead.  The int32 escape counter starts from the input tile, not
from a constant: Mosaic refuses to relayout a loop-carried vector that
starts replicated (a splat) and leaves the loop unreplicated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import pallas_call


def _kernel(cr_ref, ci_ref, out_ref, *, max_iters: int):
    cr = cr_ref[...]
    ci = ci_ref[...]
    zr = jnp.zeros_like(cr)
    zi = jnp.zeros_like(ci)
    cnt = (cr * 0.0).astype(jnp.int32)      # not a splat: see docstring

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        escaped = zr2 + zi2 > 4.0
        nzr = zr2 - zi2 + cr
        nzi = 2.0 * zr * zi + ci
        zr = jnp.where(escaped, zr, nzr)       # freeze escaped lanes
        zi = jnp.where(escaped, zi, nzi)
        cnt = cnt + jnp.where(escaped, 0, 1).astype(jnp.int32)
        return zr, zi, cnt

    _, _, cnt = jax.lax.fori_loop(0, max_iters, body, (zr, zi, cnt))
    out_ref[...] = cnt


@functools.partial(jax.jit, static_argnames=("max_iters", "bm", "bn"))
def mandelbrot(c_real: jax.Array, c_imag: jax.Array, *,
               max_iters: int = 256, bm: int = 256,
               bn: int = 256) -> jax.Array:
    """Escape counts for a (M, N) grid of complex c values."""
    M, N = c_real.shape
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    return pallas_call(
        functools.partial(_kernel, max_iters=max_iters),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        name="mandelbrot",
    )(c_real, c_imag)
