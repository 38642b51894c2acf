"""The system under test for PSIA: the program's own chunk entry,
``repro.apps.psia.compute_tasks``, at the configuration's sizes.

The program fixes its data (cloud and oriented points from fixed seeds)
and its bin range; ``bind`` refuses a configuration that states others.
"""

from __future__ import annotations

import jax
import numpy as np


def bind(cfg: dict):
    """-> (chunk_fn(start, stop) -> rows, prepare()) for the engine's
    ``ChunkBackend``."""
    from repro.apps import psia
    fixed = {"cloud_seed": 0, "points_seed": 1, "alpha_max": 3.0,
             "beta_max": 3.0}
    for key, value in fixed.items():
        if cfg[key] != value:
            raise ValueError(f"the program fixes {key} = {value}, the "
                             f"configuration states {cfg[key]}")
    n, cloud_n = cfg["n_tasks"], cfg["cloud_n"]

    def chunk_fn(start: int, stop: int):
        return psia.compute_tasks(np.arange(start, stop), n=n,
                                  cloud_n=cloud_n, n_alpha=cfg["n_alpha"],
                                  n_beta=cfg["n_beta"])

    def prepare() -> None:
        """The inputs, made on the device from their seeds."""
        jax.block_until_ready((psia.cloud(cloud_n),
                               psia.oriented_points(n)))

    return chunk_fn, prepare
