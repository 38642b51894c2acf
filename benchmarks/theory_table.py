"""§3.1 theory reproduction: closed form vs Monte-Carlo vs discrete-event
simulator, the quadratic cost decrease, and the checkpoint crossover.

Writes theory.csv:  q, n, closed_form, monte_carlo, simulator, overhead,
checkpoint_crossover_C
"""

from __future__ import annotations

import numpy as np

from benchmarks import common
from repro import api
from repro.core import theory


def run(t: float = 0.01, lam: float = 0.01):
    rows = []
    N = 4096                              # total tasks, fixed
    for q in (8, 16, 32, 64):
        n = N // q
        T = n * t
        closed = theory.expected_time_one_failure(n, t, q, lam)
        mc = theory.monte_carlo_one_failure(n, t, q, lam, reps=30000)
        # simulator: mean over seeds of exactly-one-failure runs
        spec = api.RunSpec(scheduling=api.SchedulingSpec(technique="SS"),
                           execution=api.ExecutionSpec(h=1e-7))
        sims = []
        for seed in range(10):
            sc = api.scenarios.failures(q, 1, t_exec_estimate=T, seed=seed)
            r = api.simulate(spec.replace(cluster=sc), np.full(N, t))
            sims.append(r.t_par)
        rows.append((q, n, closed, mc, float(np.mean(sims)),
                     theory.rdlb_overhead(n, t, q, lam),
                     theory.checkpoint_crossover(n, t, q, lam)))
    common.write_csv("theory", ["q", "n", "closed_form", "monte_carlo",
                                "simulator", "overhead_H_T",
                                "ckpt_crossover_C"], rows)
    return rows


def main(quick: bool = True):
    rows = run()
    lines = []
    for q, n, closed, mc, sim, H, C in rows:
        lines.append(f"theory,q={q},closed={closed:.4f},mc={mc:.4f},"
                     f"sim={sim:.4f},H_T={H:.2e},C*={C:.2e}")
    # quadratic scalability: H(q) ratio across doublings
    H = [r[5] for r in rows]
    lines.append(f"theory,quadratic_ratios,"
                 f"{H[0]/H[1]:.2f},{H[1]/H[2]:.2f},{H[2]/H[3]:.2f}")
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
