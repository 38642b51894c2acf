"""Operations and bytes of one Mandelbrot call (tiles [start, stop)), as
the algorithm needs them: each pixel iterates until it escapes, so the
work is its escape count (from the reference image) times the body's
operations, not ``max_iters`` for every pixel as the kernel runs it.

Per iteration of one pixel, in float32:
  zr^2, zi^2                       2
  |z|^2 > 4                        2  (add, compare)
  zr' = zr^2 - zi^2 + cr           2
  zi' = 2 zr zi + ci               3
  count += 1                       1
                                  --
                                  10
Bytes: cr and ci read, the counts written, 4 bytes each per pixel.
"""

import numpy as np

OPS_PER_ITER = 10


def call(cfg: dict, start: int, stop: int, reference) -> tuple[float, float]:
    iters = float(np.asarray(reference[start:stop], np.int64).sum())
    pixels = (stop - start) * cfg["tile"] ** 2
    return OPS_PER_ITER * iters, float(12 * pixels)
