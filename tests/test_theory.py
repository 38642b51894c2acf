"""§3.1 closed forms vs Monte-Carlo and vs the discrete-event simulator."""

import math

import numpy as np
import pytest

from repro import api
from repro.api import scenarios
from repro.core import theory


def test_no_failure_T():
    assert theory.t_no_failure(10, 0.5) == 5.0


@pytest.mark.parametrize("n,t,q,lam", [
    (64, 0.01, 8, 0.05), (128, 0.01, 16, 0.01), (32, 0.1, 4, 0.02),
])
def test_closed_form_matches_monte_carlo(n, t, q, lam):
    ct = theory.expected_time_one_failure(n, t, q, lam)
    mc = theory.monte_carlo_one_failure(n, t, q, lam, reps=40000)
    assert ct == pytest.approx(mc, rel=0.02)


def test_first_order_approx_close_for_small_lambda():
    exact = theory.expected_time_one_failure(100, 0.01, 8, 1e-3)
    approx = theory.expected_time_first_order(100, 0.01, 8, 1e-3)
    assert approx == pytest.approx(exact, rel=1e-3)


def test_overhead_decreases_quadratically_with_system_size():
    """Paper abstract: cost decreases ~quadratically in P (fixed N=n*q)."""
    N, t, lam = 4096, 0.01, 0.01
    h = [theory.rdlb_overhead(N // q, t, q, lam) for q in (8, 16, 32)]
    assert h[0] > h[1] > h[2]
    # doubling q should cut overhead by ~4x (up to the +1/-1 terms)
    assert h[0] / h[1] == pytest.approx(4.0, rel=0.2)
    assert h[1] / h[2] == pytest.approx(4.0, rel=0.2)


def test_checkpoint_crossover():
    n, t, q, lam = 128, 0.01, 16, 0.01
    C_star = theory.checkpoint_crossover(n, t, q, lam)
    assert theory.rdlb_beats_checkpointing(n, t, q, lam, C_star * 1.01)
    assert not theory.rdlb_beats_checkpointing(n, t, q, lam, C_star * 0.5)
    # at the crossover the first-order overheads match
    h_rdlb = theory.rdlb_overhead(n, t, q, lam)
    h_ckpt = theory.checkpoint_overhead(lam, C_star)
    assert h_rdlb == pytest.approx(h_ckpt, rel=1e-6)


def test_simulator_single_failure_within_theory_envelope():
    """Simulated mean extra time under 1 failure is bounded by the
    theoretical worst case (failure at the very end, work spread over
    q-1 survivors)."""
    q, n, t = 8, 64, 0.01
    T = n * t
    spec = api.RunSpec(scheduling=api.SchedulingSpec(technique="SS"),
                       execution=api.ExecutionSpec(h=1e-7))
    extras = []
    for seed in range(30):
        sc = scenarios.failures(q, 1, t_exec_estimate=T, seed=seed)
        r = api.simulate(spec.replace(cluster=sc), np.full(q * n, t))
        assert not r.hang
        extras.append(r.t_par - T)
    worst = (n + 1) * t / 2 * (q / (q - 1)) + n * t * 0.2
    assert 0 <= np.mean(extras) <= worst
