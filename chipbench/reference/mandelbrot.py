"""Plain jnp reference of the Mandelbrot escape counts.  It imports
nothing of the program: the grid is made again here from the region,
side and iteration count the configuration states.

Escape count of c: the number of iterations z <- z^2 + c, from z = 0,
before |z|^2 exceeds 4 (``max_iters`` if it never does).  Tasks are
``tile x tile`` tiles in row-major order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def grid(cfg: dict):
    x0, x1, y0, y1 = cfg["region"]
    side = cfg["side"]
    return jnp.meshgrid(jnp.linspace(x0, x1, side),
                        jnp.linspace(y0, y1, side))


@functools.partial(jax.jit, static_argnames=("max_iters", "dtype"))
def escape_counts(cr, ci, *, max_iters, dtype):
    cr, ci = cr.astype(dtype), ci.astype(dtype)

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        escaped = zr2 + zi2 > 4.0
        nzr = zr2 - zi2 + cr
        nzi = 2.0 * zr * zi + ci
        zr = jnp.where(escaped, zr, nzr)
        zi = jnp.where(escaped, zi, nzi)
        return zr, zi, cnt + jnp.where(escaped, 0, 1).astype(jnp.int32)

    z = jnp.zeros_like(cr)
    _, _, cnt = jax.lax.fori_loop(
        0, max_iters, body, (z, z, jnp.zeros(cr.shape, jnp.int32)))
    return cnt


def _tiles(img: np.ndarray, cfg: dict) -> np.ndarray:
    side, tile = cfg["side"], cfg["tile"]
    per_row = side // tile
    return (img.reshape(per_row, tile, per_row, tile).transpose(0, 2, 1, 3)
            .reshape(per_row * per_row, tile, tile))


def compute(cfg: dict, dtype=jnp.float32) -> np.ndarray:
    """Every tile's escape counts: (n_tasks, tile, tile) int32."""
    cr, ci = grid(cfg)
    img = np.asarray(escape_counts(cr, ci, max_iters=cfg["max_iters"],
                                   dtype=dtype))
    return _tiles(img, cfg)


def chunk_fn(cfg: dict, dtype=jnp.float32):
    """The reference in the program's place: tiles [start, stop)."""
    tiles = compute(cfg, dtype)
    return lambda start, stop: tiles[start:stop]
