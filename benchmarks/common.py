"""Shared benchmark machinery: the paper's experiment grid (Table 1)."""

from __future__ import annotations

import csv
import time
from pathlib import Path

import numpy as np

from repro import api
from repro.apps import mandelbrot, psia
from repro.core import dls

ARTIFACTS = Path(__file__).resolve().parent.parent / "artifacts" / "bench"

P = 256                        # miniHPC: 16 nodes x 16 ranks
NODE_SIZE = 16

# paper technique set (Table 1)
TECHNIQUES = list(dls.ALL_TECHNIQUES)


def apps(quick: bool = True):
    """(name, task_times) for the paper's two applications.

    quick mode groups Mandelbrot pixels 16-per-task (N=16,384) to keep the
    SS event count tractable; durations (and their variance structure) are
    preserved because grouping sums the real per-pixel times.
    """
    n_mandel = 16_384 if quick else mandelbrot.PAPER_N
    return [
        ("psia", psia.task_times(psia.PAPER_N)),
        ("mandelbrot", mandelbrot.task_times(n_mandel)),
    ]


def scenarios(t_estimate: float, seed: int = 0):
    """The seven Table-1 execution scenarios at P=256."""
    return api.scenarios.paper_scenarios(P, t_exec_estimate=t_estimate,
                                         seed=seed)


def spec_for(technique: str, cluster: api.ClusterSpec, *,
             rdlb: bool = True,
             seed: int = 0, h: float = 1e-4) -> api.RunSpec:
    """One Table-1 grid cell as a declarative RunSpec — the benchmarks'
    scenario vocabulary (serializable; the ``python -m repro`` CLI runs
    the same cells from JSON)."""
    return api.RunSpec(
        scheduling=api.SchedulingSpec(technique=technique, seed=seed),
        robustness=api.RobustnessSpec(rdlb_enabled=rdlb),
        cluster=cluster,
        execution=api.ExecutionSpec(h=h),
        name=f"{cluster.name}/{technique}")


def run_one(task_times, technique: str, cluster: api.ClusterSpec, *,
            rdlb: bool, seed: int = 0):
    t0 = time.time()
    r = api.simulate(spec_for(technique, cluster, rdlb=rdlb, seed=seed),
                     task_times)
    return r, time.time() - t0


def write_csv(name: str, header, rows):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = ARTIFACTS / f"{name}.csv"
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path
