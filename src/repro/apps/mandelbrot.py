"""Mandelbrot application (paper Table 1: N=262,144, HIGH task-time
variance).

The paper schedules the 512x512 = 262,144 pixel iterations as independent
tasks.  Two faces here:

  * ``task_times()`` — per-task nominal durations for the discrete-event
    simulator, derived from the REAL escape counts of the assigned region
    (time proportional to iterations executed) — this reproduces the
    paper's variance structure instead of assuming a distribution;
  * ``compute_tile()/compute_tiles()`` — the actual JAX/Pallas compute,
    used by the runtime examples (rDLB re-executing real tiles after
    injected failures, asserting the final image is loss-less).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import mandelbrot as mandelbrot_kernel

REGION = (-2.0, 0.6, -1.3, 1.3)        # the classic view
PAPER_N = 262_144                      # 512 x 512
SIDE = 512
MAX_ITERS = 256


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


def compute_tiles(start: int, stop: int, *, side: int = SIDE,
                  tile: int = 64, max_iters: int = MAX_ITERS) -> np.ndarray:
    """Compute tiles ``[start, stop)`` (row-major tile ids) as ONE kernel
    launch: the tiles are stacked into a (k*tile, tile) grid whose kernel
    blocks are the tiles themselves.  Returns (k, tile, tile); each tile
    equals ``compute_tile`` of its id."""
    per_row = side // tile
    cr, ci = (g.reshape(per_row, tile, per_row, tile).transpose(0, 2, 1, 3)
              .reshape(per_row * per_row, tile, tile)[start:stop]
              .reshape(-1, tile) for g in grid(side))
    out = mandelbrot_kernel(cr, ci, max_iters=max_iters, bm=tile, bn=tile)
    return np.asarray(out).reshape(-1, tile, tile)


def n_tiles(side: int = SIDE, tile: int = 64) -> int:
    return (side // tile) ** 2


def assemble(tiles: dict, *, side: int = SIDE, tile: int = 64) -> np.ndarray:
    img = np.zeros((side, side), np.int32)
    per_row = side // tile
    for tid, data in tiles.items():
        ty, tx = divmod(tid, per_row)
        img[ty * tile:(ty + 1) * tile, tx * tile:(tx + 1) * tile] = data
    return img
