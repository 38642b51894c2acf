"""The paper's execution scenarios (Table 1) as ClusterSpecs.

Scenarios on miniHPC (16 nodes x 16 ranks = 256 PEs):

  Failures:       1, P/2, P-1 fail-stop failures, at arbitrary times during
                  execution; failed cores do not recover.  The master (PE 0)
                  never fails (paper limitation: master is a SPOF).
  Perturbations:  PE availability   — all PEs of one node slowed (CPU burner),
                  Network latency   — +10 s per message to/from one node,
                  Combined          — both at once.

Each constructor returns a :class:`ClusterSpec` named after its scenario,
with one :class:`WorkerSpec` per worker.
"""

from __future__ import annotations

import numpy as np

from repro.api.spec import ClusterSpec, WorkerSpec

__all__ = ["baseline", "failures", "pe_perturbation", "latency_perturbation",
           "combined_perturbation", "paper_scenarios"]


def _cluster(name: str, workers: list) -> ClusterSpec:
    return ClusterSpec(n_workers=len(workers), workers=tuple(workers),
                       name=name)


def _node(name: str, P: int, node_size: int, node: int,
          **perturbation) -> ClusterSpec:
    """Every PE of node ``node`` gets ``perturbation``; the rest nominal."""
    workers = [WorkerSpec()] * P
    for pe in range(node * node_size, min(P, (node + 1) * node_size)):
        workers[pe] = WorkerSpec(**perturbation)
    return _cluster(name, workers)


def baseline(P: int) -> ClusterSpec:
    return _cluster("baseline", [WorkerSpec()] * P)


def failures(P: int, n_failures: int, *, t_exec_estimate: float,
             seed: int = 0) -> ClusterSpec:
    """``n_failures`` distinct non-master PEs die at arbitrary times.

    Fail times are drawn uniformly over (0, t_exec_estimate) — "occur
    arbitrary during execution".  PE 0 (master) never fails.
    """
    if not 0 <= n_failures <= P - 1:
        raise ValueError(f"need 0 <= n_failures <= P-1, got {n_failures}")
    rng = np.random.default_rng(seed)
    victims = rng.choice(np.arange(1, P), size=n_failures, replace=False)
    times = rng.uniform(0.05 * t_exec_estimate, 0.95 * t_exec_estimate,
                        size=n_failures)
    workers = [WorkerSpec()] * P
    for v, t in zip(victims, times):
        workers[int(v)] = WorkerSpec(fail_time=float(t))
    return _cluster(f"fail_{n_failures}", workers)


def pe_perturbation(P: int, *, node_size: int = 16, node: int = 1,
                    slowdown: float = 0.25) -> ClusterSpec:
    """All PEs on one node compute at ``slowdown`` x nominal (CPU burner)."""
    return _node("pe_perturb", P, node_size, node, speed=slowdown)


def latency_perturbation(P: int, *, node_size: int = 16, node: int = 1,
                         delay: float = 10.0) -> ClusterSpec:
    """+``delay`` seconds per message to/from every PE of one node."""
    return _node("latency_perturb", P, node_size, node, msg_latency=delay)


def combined_perturbation(P: int, *, node_size: int = 16, node: int = 1,
                          slowdown: float = 0.25,
                          delay: float = 10.0) -> ClusterSpec:
    return _node("combined_perturb", P, node_size, node, speed=slowdown,
                 msg_latency=delay)


def paper_scenarios(P: int, *, t_exec_estimate: float,
                    seed: int = 0) -> dict[str, ClusterSpec]:
    """The seven execution scenarios of Table 1."""
    return {
        "baseline": baseline(P),
        "fail_1": failures(P, 1, t_exec_estimate=t_exec_estimate, seed=seed),
        "fail_half": failures(P, P // 2, t_exec_estimate=t_exec_estimate,
                              seed=seed + 1),
        "fail_pm1": failures(P, P - 1, t_exec_estimate=t_exec_estimate,
                             seed=seed + 2),
        "pe_perturb": pe_perturbation(P),
        "latency_perturb": latency_perturbation(P),
        "combined_perturb": combined_perturbation(P),
    }
