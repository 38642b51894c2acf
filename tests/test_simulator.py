"""Discrete-event simulator tests, incl. the paper's Fig. 1/2 scenarios."""

import numpy as np
import pytest

from repro import api
from repro.api import scenarios
from repro.core import dls

SS = api.RunSpec(scheduling=api.SchedulingSpec(technique="SS"))
NO_RDLB = "robustness.rdlb_enabled"


def uniform_tasks(n, t=1.0):
    return np.full(n, t)


def cluster(*workers):
    return api.ClusterSpec(n_workers=len(workers), workers=workers)


def on(spec, technique):
    return spec.override("scheduling.technique", technique)


# ------------------------------------------------- Fig. 1: 9 tasks, 3 PEs
def test_fig1a_no_failure_ss():
    """SS, 9 equal tasks, 3 PEs: ~3 rounds each, everything finishes."""
    spec = SS.replace(cluster=scenarios.baseline(3))
    r = api.simulate(spec.override("execution.h", 1e-9), uniform_tasks(9))
    assert not r.hang and r.n_finished == 9
    assert r.t_par == pytest.approx(3.0, rel=0.01)


def test_fig1b_failure_without_rdlb_hangs():
    """P3 fails holding T4: execution waits indefinitely (t_par = inf)."""
    spec = SS.replace(cluster=cluster(
        api.WorkerSpec(),
        api.WorkerSpec(),
        api.WorkerSpec(fail_time=1.5),        # dies during its 2nd task
    )).override("execution.h", 1e-9).override(NO_RDLB, False)
    r = api.simulate(spec, uniform_tasks(9))
    assert r.hang and r.n_finished < 9


def test_fig1c_failure_with_rdlb_completes():
    spec = SS.replace(cluster=cluster(
        api.WorkerSpec(),
        api.WorkerSpec(),
        api.WorkerSpec(fail_time=1.5),
    )).override("execution.h", 1e-9)
    r = api.simulate(spec, uniform_tasks(9))
    assert not r.hang and r.n_finished == 9
    # one extra round for the re-executed tasks, not a serialization
    assert r.t_par < 9.0


# -------------------------------------- Fig. 2: perturbation (slow PE)
def test_fig2_perturbation_rdlb_faster():
    spec = SS.replace(cluster=cluster(
        api.WorkerSpec(),
        api.WorkerSpec(speed=0.2),            # severely perturbed
        api.WorkerSpec(),
    )).override("execution.h", 1e-9)
    slow = api.simulate(spec.override(NO_RDLB, False), uniform_tasks(9))
    fast = api.simulate(spec, uniform_tasks(9))
    assert not slow.hang and not fast.hang
    assert fast.t_par <= slow.t_par           # duplicates absorb the tail
    assert fast.n_duplicates >= 1


# ------------------------------------------------------ failure sweeps
@pytest.mark.parametrize("technique", ["SS", "FAC", "GSS", "AWF-B", "AF"])
def test_p_minus_1_failures_tolerated(technique):
    P = 8
    tt = uniform_tasks(256, 0.01)
    spec = on(SS, technique).replace(cluster=scenarios.baseline(P))
    base = api.simulate(spec, tt)
    sc = scenarios.failures(P, P - 1, t_exec_estimate=base.t_par, seed=1)
    r = api.simulate(spec.replace(cluster=sc), tt)
    assert not r.hang and r.n_finished == 256


def test_one_failure_cost_small():
    """Paper §4.2: one failure has almost no effect on execution time —
    sharpest with small chunks (SS); FAC's large early chunks bound the
    cost at one chunk re-execution."""
    P = 16
    tt = uniform_tasks(1024, 0.01)
    base = SS.replace(cluster=scenarios.baseline(P))
    base_ss = api.simulate(base, tt)
    sc = scenarios.failures(P, 1, t_exec_estimate=base_ss.t_par, seed=0)
    r_ss = api.simulate(base.replace(cluster=sc), tt)
    assert r_ss.t_par < base_ss.t_par * 1.1
    base_fac = api.simulate(on(base, "FAC"), tt)
    r_fac = api.simulate(on(base, "FAC").replace(cluster=sc), tt)
    assert r_fac.t_par < base_fac.t_par * 2.0


def test_small_chunks_lose_less_on_failure():
    """Paper §4.2: SS (small chunks) more robust than GSS (large chunks)
    under many failures."""
    P = 8
    tt = uniform_tasks(512, 0.01)
    base_ss = api.simulate(SS.replace(cluster=scenarios.baseline(P)), tt)
    spec = SS.replace(cluster=scenarios.failures(
        P, P // 2, t_exec_estimate=base_ss.t_par, seed=2))
    r_ss = api.simulate(spec, tt)
    r_gss = api.simulate(on(spec, "GSS"), tt)
    assert r_ss.t_par <= r_gss.t_par * 1.05


def test_latency_perturbation_rdlb_speedup():
    """Paper Fig. 3: large latency on one node, rDLB faster.  Task times
    must exceed the message delay or the perturbed node never receives
    work at all (and the perturbation is absorbed trivially)."""
    P = 16
    tt = uniform_tasks(512, 0.2)              # run ~7 s >> 2 s delay
    spec = SS.replace(cluster=scenarios.latency_perturbation(
        P, node_size=4, node=1, delay=2.0))
    # strict win with small chunks (SS): the duplicate always beats the
    # delayed original; with FAC the duplicate of a large chunk may only
    # tie — rDLB must never be SLOWER either way
    without = api.simulate(spec.override(NO_RDLB, False), tt)
    with_r = api.simulate(spec, tt)
    assert with_r.t_par < without.t_par
    assert with_r.n_duplicates >= 1
    wo_fac = api.simulate(on(spec, "FAC").override(NO_RDLB, False), tt)
    wi_fac = api.simulate(on(spec, "FAC"), tt)
    assert wi_fac.t_par <= wo_fac.t_par * (1 + 1e-9)


def test_adaptive_feedback_runs():
    tt = np.abs(np.random.default_rng(0).normal(0.01, 0.005, 500)) + 1e-4
    spec = SS.replace(cluster=scenarios.baseline(8))
    for name in dls.ADAPTIVE_TECHNIQUES:
        r = api.simulate(on(spec, name), tt)
        assert not r.hang and r.n_finished == 500


def test_busy_idle_accounting():
    spec = SS.replace(cluster=scenarios.baseline(4))
    r = api.simulate(spec.override("execution.h", 1e-6),
                     uniform_tasks(64, 0.01))
    assert (r.pe_busy > 0).all()
    assert (r.pe_idle >= -1e-9).all()
    assert r.pe_busy.sum() == pytest.approx(64 * 0.01, rel=0.05)
