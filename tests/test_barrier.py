"""AWF batch-weight barrier behavior (DESIGN §8.2-8.3): sustained-stall
re-issue, duplicate-cap escalation (livelock regression), and the hang
without rDLB."""

import numpy as np

from repro import api
from repro.api import scenarios
from repro.core import dls, rdlb

AWF_B = api.RunSpec(scheduling=api.SchedulingSpec(technique="AWF-B"),
                    cluster=scenarios.baseline(8))


def make_q(N=8, P=4, **kw):
    return rdlb.RobustQueue(N, dls.make_technique("AWF-B", N, P), **kw)


def test_barrier_blocks_until_reports():
    q = make_q()
    # batch 1 = ceil(8/2) = 4 tasks -> chunk 1 each to 4 PEs
    chunks = [q.request(pe) for pe in range(4)]
    assert all(c is not None for c in chunks)
    assert q.at_batch_barrier
    # next batch cannot be composed yet; first miss returns None
    assert q.request(0) is None and q.wait_hint == "barrier"
    # reporting everything clears the barrier
    for c in chunks:
        q.report(c)
    assert not q.at_batch_barrier
    assert q.request(0) is not None


def test_barrier_sustained_stall_reissues():
    q = make_q()
    chunks = [q.request(pe) for pe in range(4)]
    for c in chunks[1:]:
        q.report(c)                      # PE 0's chunk outstanding
    assert q.request(1) is None          # miss 1: damped
    dup = q.request(1)                   # miss 2: duplicate granted
    assert dup is not None and dup.duplicate
    assert dup.start == chunks[0].start


def test_barrier_cap_escalates_no_livelock():
    """A capped duplicate on a dead PE must not block re-issue forever."""
    q = make_q()
    chunks = [q.request(pe) for pe in range(4)]
    for c in chunks[1:]:
        q.report(c)
    assert q.request(1) is None
    d1 = q.request(1)                    # live duplicate -> dead PE 1
    assert d1 is not None
    # PE 2 polls: cap=1 says no... until the 3rd miss lifts it
    got = None
    for _ in range(5):
        got = q.request(2)
        if got is not None:
            break
    assert got is not None and got.duplicate


def test_simulator_awf_pm1_failures_terminates():
    """Regression: P-1 failures + AWF-B barrier used to livelock."""
    tt = np.full(128, 0.01)
    base = api.simulate(AWF_B, tt)
    sc = scenarios.failures(8, 7, t_exec_estimate=base.t_par, seed=1)
    r = api.simulate(AWF_B.replace(cluster=sc), tt)
    assert not r.hang and r.n_finished == 128


def test_simulator_awf_nonrobust_barrier_not_hang_when_healthy():
    """Without failures, AWF-B without rDLB still completes (the barrier
    clears by itself)."""
    tt = np.full(128, 0.01)
    r = api.simulate(AWF_B.override("robustness.rdlb_enabled", False), tt)
    assert not r.hang and r.n_finished == 128
