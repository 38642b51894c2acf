"""The system under test for Mandelbrot: the program's own chunk entry,
``repro.apps.mandelbrot.compute_tiles``, at the configuration's sizes.

The program fixes its region of the plane; ``bind`` refuses a
configuration that states another.
"""

from __future__ import annotations


def bind(cfg: dict):
    """-> (chunk_fn(start, stop) -> tiles, prepare()) for the engine's
    ``ChunkBackend``."""
    from repro.apps import mandelbrot
    if tuple(cfg["region"]) != tuple(mandelbrot.REGION):
        raise ValueError(f"the program fixes the region "
                         f"{mandelbrot.REGION}, the configuration states "
                         f"{cfg['region']}")
    side, tile = cfg["side"], cfg["tile"]
    if cfg["n_tasks"] != mandelbrot.n_tiles(side, tile):
        raise ValueError("n_tasks must be the number of tiles")

    def chunk_fn(start: int, stop: int):
        return mandelbrot.compute_tiles(start, stop, side=side, tile=tile,
                                        max_iters=cfg["max_iters"])

    def prepare() -> None:
        """The program builds its grid inside each chunk call."""

    return chunk_fn, prepare
