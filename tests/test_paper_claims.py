"""The paper's headline claims, validated end-to-end on the simulator.

  1. rDLB tolerates up to P-1 PE failures (Fig. 3a/3b).
  2. One failure costs almost nothing (Fig. 3/4 discussion).
  3. Under severe perturbations, rDLB improves execution time up to ~7x
     (Fig. 3c/3d: adaptive techniques + latency/combined perturbations).
  4. rDLB boosts FePIA flexibility of adaptive techniques ~30x (Fig. 5).

Claims 1-2 run at P=32 (fast); claims 3-4 at the paper's P=256 with the
paper's PSIA scale (N=20,000, ~0.28 s tasks, 10 s delays) — the barrier
mechanism of AWF-B/D (batch-weight collection) is what makes the paper's
numbers reproducible; see core/rdlb.py::at_batch_barrier.
"""

import math

import numpy as np
import pytest

from repro import api
from repro.api import scenarios
from repro.apps import mandelbrot, psia
from repro.core import robustness

P = 32
BASE = api.RunSpec(scheduling=api.SchedulingSpec(technique="FAC"),
                   cluster=scenarios.baseline(P))
NO_RDLB = "robustness.rdlb_enabled"


def on(spec, technique, cluster=None):
    """``spec`` run with ``technique`` (and ``cluster``, if given)."""
    spec = spec.override("scheduling.technique", technique)
    return spec if cluster is None else spec.replace(cluster=cluster)


@pytest.fixture(scope="module")
def mandel_times():
    return mandelbrot.task_times(4096, side=128, max_iters=128)


@pytest.fixture(scope="module")
def psia_times():
    return psia.task_times(4096)


@pytest.fixture(scope="module")
def psia_paper():
    return psia.task_times(20000)          # the paper's N


def test_task_variance_structure(mandel_times, psia_times):
    """Mandelbrot high variance, PSIA low variance (Table 1)."""
    cv_m = mandel_times.std() / mandel_times.mean()
    cv_p = psia_times.std() / psia_times.mean()
    assert cv_m > 5 * cv_p


@pytest.mark.parametrize("nf", [1, P // 2, P - 1])
def test_claim1_tolerates_failures(mandel_times, nf):
    base = api.simulate(BASE, mandel_times)
    sc = scenarios.failures(P, nf, t_exec_estimate=base.t_par, seed=nf)
    r = api.simulate(BASE.replace(cluster=sc), mandel_times)
    assert not r.hang and r.n_finished == len(mandel_times)


def test_claim1_without_rdlb_hangs(mandel_times):
    base = api.simulate(BASE, mandel_times)
    sc = scenarios.failures(P, 1, t_exec_estimate=base.t_par, seed=0)
    r = api.simulate(BASE.replace(cluster=sc).override(NO_RDLB, False),
                     mandel_times)
    assert r.hang


def test_claim2_single_failure_near_free(psia_times):
    """Near-free with small chunks (SS); bounded by one chunk with FAC."""
    base = api.simulate(on(BASE, "SS"), psia_times)
    sc = scenarios.failures(P, 1, t_exec_estimate=base.t_par, seed=0)
    r = api.simulate(on(BASE, "SS", sc), psia_times)
    assert r.t_par <= base.t_par * 1.1
    base_f = api.simulate(BASE, psia_times)
    r_f = api.simulate(BASE.replace(cluster=sc), psia_times)
    assert r_f.t_par <= base_f.t_par * 2.0


def test_claim3_execution_time_speedup_7x(psia_paper):
    """AWF-B + combined perturbation at P=256: rDLB ~7x faster (paper's
    'decreased application execution time up to 7 times')."""
    sc = scenarios.combined_perturbation(256, node_size=16, node=1,
                                         slowdown=0.25, delay=10.0)
    awf = on(BASE, "AWF-B", sc)
    wo = api.simulate(awf.override(NO_RDLB, False), psia_paper)
    wi = api.simulate(awf, psia_paper)
    assert not wo.hang and not wi.hang
    assert wo.t_par / wi.t_par >= 5.0


def test_claim4_flexibility_boost_30x(psia_paper):
    """FePIA flexibility of AWF-B improves ~30x with rDLB under combined
    perturbations (paper: 'boosted the robustness ... up to 30 times')."""
    sc = scenarios.combined_perturbation(256, node_size=16, node=1,
                                         slowdown=0.25, delay=10.0)
    awf = on(BASE, "AWF-B", sc)
    base = api.simulate(awf.replace(cluster=scenarios.baseline(256)),
                        psia_paper).t_par
    wo = api.simulate(awf.override(NO_RDLB, False), psia_paper).t_par
    wi = api.simulate(awf, psia_paper).t_par
    radius_wo = wo - base
    radius_wi = max(wi - base, 1e-9)
    assert radius_wo / radius_wi >= 20.0


def test_nonadaptive_speedup_under_combined(psia_paper):
    """Nonadaptive techniques also gain (paper Fig. 3), ~2x here."""
    sc = scenarios.combined_perturbation(256, node_size=16, node=1,
                                         slowdown=0.25, delay=10.0)
    fac = BASE.replace(cluster=sc)
    wo = api.simulate(fac.override(NO_RDLB, False), psia_paper)
    wi = api.simulate(fac, psia_paper)
    assert wo.t_par / wi.t_par >= 1.8


def test_fepia_most_robust_is_one(psia_times):
    sc = scenarios.pe_perturbation(P, node_size=8, node=1, slowdown=0.25)
    tb, tp = {}, {}
    for tech in ("SS", "FAC", "GSS"):
        tb[tech] = api.simulate(on(BASE, tech), psia_times).t_par
        tp[tech] = api.simulate(on(BASE, tech, sc), psia_times).t_par
    rho = robustness.flexibility(tp, tb)
    assert min(rho.values()) == pytest.approx(1.0)
