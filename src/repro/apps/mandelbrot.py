"""Mandelbrot application (paper Table 1: N=262,144, HIGH task-time
variance).

The paper schedules the 512x512 = 262,144 pixel iterations as independent
tasks.  Two faces here:

  * ``task_times()`` — per-task nominal durations for the discrete-event
    simulator, derived from the REAL escape counts of the assigned region
    (time proportional to iterations executed) — this reproduces the
    paper's variance structure instead of assuming a distribution;
  * ``compute_tile()/compute_tiles()`` — the actual JAX/Pallas compute of
    a chunk of ``tile x tile`` tasks (one-pixel tasks at ``tile=1``, the
    paper's), used by the runtime (rDLB re-executing real tasks after
    injected failures, asserting the final image is loss-less).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import mandelbrot as mandelbrot_kernel
from repro.runtime.backends import chunk_to_host

REGION = (-2.0, 0.6, -1.3, 1.3)        # the classic view
PAPER_N = 262_144                      # 512 x 512
SIDE = 512
MAX_ITERS = 256
LANES = 128
MIN_PIXELS = 8 * LANES                 # one (8, 128) block of the chip


def grid(side: int = SIDE):
    x0, x1, y0, y1 = REGION
    xs = jnp.linspace(x0, x1, side)
    ys = jnp.linspace(y0, y1, side)
    cr, ci = jnp.meshgrid(xs, ys)
    return cr, ci


@functools.lru_cache(maxsize=4)
def escape_counts(side: int = SIDE, max_iters: int = MAX_ITERS
                  ) -> np.ndarray:
    cr, ci = grid(side)
    return np.asarray(mandelbrot_kernel(cr, ci, max_iters=max_iters,
                                        bm=min(128, side),
                                        bn=min(128, side)))


def task_times(n_tasks: int = PAPER_N, *, side: int = SIDE,
               max_iters: int = MAX_ITERS,
               time_per_iter: float = 6e-4) -> np.ndarray:
    """Per-task durations for the simulator (task = pixel, row-major).
    If n_tasks < side*side, tasks are contiguous pixel groups.

    time_per_iter calibrated to the paper's Fig. 3 Mandelbrot scale
    (P=256 parallel time tens of seconds, task times 0..~0.15 s with the
    high variance coming from the real escape-count distribution)."""
    iters = escape_counts(side, max_iters).reshape(-1).astype(np.float64)
    per_pixel = iters * time_per_iter + 1e-7
    if n_tasks == per_pixel.size:
        return per_pixel
    group = per_pixel.size // n_tasks
    return per_pixel[:n_tasks * group].reshape(n_tasks, group).sum(axis=1)


def compute_tile(tile_id: int, *, side: int = SIDE, tile: int = 64,
                 max_iters: int = MAX_ITERS) -> np.ndarray:
    """Compute one (tile x tile) tile — a runtime task. Deterministic."""
    return compute_tiles(tile_id, tile_id + 1, side=side, tile=tile,
                         max_iters=max_iters)[0]


@functools.lru_cache(maxsize=4)
def plane(side: int = SIDE):
    """The grid's c values on the device, flat in row-major pixel order,
    made once per side."""
    return tuple(c.reshape(-1) for c in grid(side))


def _pixel_ids(start, n: int, side: int, tile: int):
    """Flat ids of the first ``n`` pixels of tiles ``start, start+1, ...``
    (row-major tile ids), each tile's square in row-major order; pixels
    past the grid's last tile repeat a pixel of that tile."""
    per_row, per_tile = side // tile, tile * tile
    p = jnp.arange(n, dtype=jnp.int32)
    t = jnp.minimum(start + p // per_tile, per_row * per_row - 1)
    q = p % per_tile
    return ((t // per_row * tile + q // tile) * side
            + t % per_row * tile + q % tile)


def slab_pixels(pixels: int) -> int:
    """Pixels of the slab that a chunk of ``pixels`` runs in: a power of
    two of at least ``MIN_PIXELS``, so that chunk sizes share programs."""
    return max(MIN_PIXELS, 1 << (pixels - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("n", "tile", "max_iters"))
def mandelbrot_chunk(cr, ci, start, *, n: int, tile: int,
                     max_iters: int):
    """Escape counts of ``n`` pixels of tiles from ``start`` on, as one
    lane-dense (n/128, 128) slab: every chunk of at most ``n`` pixels,
    wherever it starts, runs this one program."""
    with jax.named_scope("mandelbrot.lookup"):
        ids = _pixel_ids(start, n, math.isqrt(cr.shape[0]), tile)
        cr, ci = (c[ids].reshape(n // LANES, LANES) for c in (cr, ci))
    return mandelbrot_kernel(cr, ci, max_iters=max_iters, bm=8, bn=LANES)


def compute_tiles(start: int, stop: int, *, side: int = SIDE,
                  tile: int = 64, max_iters: int = MAX_ITERS) -> np.ndarray:
    """Compute tiles ``[start, stop)`` (row-major tile ids) as ONE device
    program: their pixels are gathered from the grid into a slab padded
    to a power of two of at least ``MIN_PIXELS``, and the pad is dropped
    on the host.  Returns (k, tile, tile); each tile equals
    ``compute_tile`` of its id."""
    k = stop - start
    pixels = k * tile * tile

    def dispatch():
        cr, ci = plane(side)
        return mandelbrot_chunk(cr, ci, np.int32(start),
                                n=slab_pixels(pixels), tile=tile,
                                max_iters=max_iters)
    return (chunk_to_host(dispatch).reshape(-1)[:pixels]
            .reshape(k, tile, tile))


def n_tiles(side: int = SIDE, tile: int = 64) -> int:
    return (side // tile) ** 2


def assemble(tiles: dict, *, side: int = SIDE, tile: int = 64) -> np.ndarray:
    img = np.zeros((side, side), np.int32)
    per_row = side // tile
    for tid, data in tiles.items():
        ty, tx = divmod(tid, per_row)
        img[ty * tile:(ty + 1) * tile, tx * tile:(tx + 1) * tile] = data
    return img
