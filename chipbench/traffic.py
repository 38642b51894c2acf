"""The one traffic generator: a mix file of parameters -> the loops' workers.

A mix (``traffic/<name>.json``) holds:

- ``technique``: the DLS technique of the loop (``"SS"``, ``"FAC"``, ...),
  over the configuration's ``P`` worker threads, rDLB on;
- ``perturb``: groups ``{"count": k, "worker": {WorkerSpec fields}}``.
  ``k`` is a number, or ``{"all_but": j}`` for ``P - j`` workers.
  A field's value is a number, or ``{"times_n_over_p": x}`` for
  ``max(1, round(x * N / P))`` (``N`` the configuration's tasks).

For every loop the seed draws which workers carry each group's
perturbation: the same set of perturbations every loop and every seed,
in another placement.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

WORKER_FIELDS = ("speed", "msg_latency", "fail_time", "fail_after_tasks",
                 "sleep_per_task", "alive", "hang_time")


def load(bench: Path, name: str) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def _resolve(value, N: int, P: int):
    if isinstance(value, dict):
        (key, x), = value.items()
        if key == "times_n_over_p":
            return max(1, round(x * N / P))
        if key == "all_but":
            return P - x
        raise ValueError(f"unknown traffic expression {key!r}")
    return value


class Plan:
    """The loops' technique and workers for one mix, configuration and
    seed."""

    def __init__(self, mix: dict, cfg: dict, seed: int) -> None:
        self.technique = mix["technique"]
        self.P = int(cfg["P"])
        N = int(cfg["n_tasks"])
        self.groups = []
        for g in mix.get("perturb", []):
            bad = set(g["worker"]) - set(WORKER_FIELDS)
            if bad:
                raise ValueError(f"not WorkerSpec fields: {sorted(bad)}")
            self.groups.append((int(_resolve(g["count"], N, self.P)), {
                k: _resolve(v, N, self.P) for k, v in g["worker"].items()}))
        if sum(c for c, _ in self.groups) > self.P:
            raise ValueError("more perturbed workers than workers")
        self.rng = np.random.default_rng(seed % 2**64)

    def workers(self) -> list[dict]:
        """One loop's WorkerSpec fields, worker by worker."""
        out: list[dict] = [{} for _ in range(self.P)]
        order = iter(self.rng.permutation(self.P))
        for count, fields in self.groups:
            for _ in range(count):
                out[int(next(order))] = dict(fields)
        return out
