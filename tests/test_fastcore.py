"""Array-native scheduling core: parity against the pure-Python oracle.

The array core (`repro.core.rdlb.RobustQueue` + the engine's vectorized
fast-forward, `repro.core.fastpath`) must be *indistinguishable* from the
preserved reference implementation (`repro.core.refqueue.ReferenceQueue`)
at the level the paper cares about: identical assignment logs (who got
which chunk, in what order, duplicates included) and identical completion
sets, for every DLS technique across the paper's perturbation scenarios —
fail-stop, count-based fail-stop, straggler, and message latency — with
rDLB on and off, with and without duplicate caps, through hangs and
barrier damping.

Also covered here: the techniques' batched interface (``bulk_sizes`` ≡
sequential ``next_chunk``), the numpy flag views, the lazy ChunkLog, and
the small-scale sanity check of the paper's scalability slope that
``benchmarks/fig_scale.py`` measures at full scale.
"""

import sys
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from repro.core import dls, engine, rdlb, refqueue, simulator

SCENARIO_KINDS = ("fail_stop", "count_fail_stop", "straggler",
                  "msg_latency")


def make_workers(kind: str, P: int):
    """Engine workers for one paper-perturbation kind (PE 0 survives)."""
    ws = [engine.EngineWorker(w) for w in range(P)]
    if kind == "fail_stop":
        for w in range(1, P, 2):
            ws[w].fail_time = 0.2 * w
    elif kind == "count_fail_stop":
        for w in range(1, P, 2):
            ws[w].fail_after_tasks = 4 * w
    elif kind == "straggler":
        for w in range(1, P, 2):
            ws[w].speed = 0.25
    elif kind == "msg_latency":
        for w in range(1, P, 2):
            ws[w].msg_latency = 0.05
    else:
        raise ValueError(kind)
    return ws


def run_one(queue_cls, technique, kind, tt, *, P, seed=0, rdlb_on=True,
            max_duplicates=None, barrier_max_duplicates=1, h=1e-4):
    tech = dls.make_technique(technique, len(tt), P, seed=seed)
    q = queue_cls(len(tt), tech, rdlb_enabled=rdlb_on,
                  max_duplicates=max_duplicates,
                  barrier_max_duplicates=barrier_max_duplicates)
    eng = engine.Engine(q, make_workers(kind, P),
                        simulator.SimBackend(np.asarray(tt, dtype=float)),
                        h=h)
    return eng.run(), q


def log_key(stats):
    return [(c.start, c.size, c.pe, c.seq, c.duplicate, c.origin_seq)
            for c in stats.assignment_log]


def completion_set(queue):
    return set(np.flatnonzero(
        np.asarray(queue.flags) == rdlb.Flag.FINISHED).tolist())


def assert_parity(technique, kind, tt, *, P, **kw):
    st_f, q_f = run_one(rdlb.RobustQueue, technique, kind, tt, P=P, **kw)
    st_r, q_r = run_one(refqueue.ReferenceQueue, technique, kind, tt,
                        P=P, **kw)
    assert log_key(st_f) == log_key(st_r)
    assert completion_set(q_f) == completion_set(q_r)
    assert st_f.hung == st_r.hung
    assert st_f.n_finished == st_r.n_finished
    assert st_f.n_assignments == st_r.n_assignments
    assert st_f.n_duplicates == st_r.n_duplicates
    assert st_f.wasted_tasks == st_r.wasted_tasks
    if not st_f.hung:
        assert st_f.t_virtual == pytest.approx(st_r.t_virtual, rel=1e-9)
    return st_f, st_r


# ------------------------------------------------------------- parity grid
@pytest.mark.parametrize("technique", dls.ALL_TECHNIQUES)
@pytest.mark.parametrize("kind", SCENARIO_KINDS)
def test_parity_all_techniques_paper_scenarios(technique, kind):
    """Acceptance: identical assignment logs + completion sets for all 14
    techniques across the paper scenarios."""
    rng = np.random.default_rng(7)
    tt = np.abs(rng.normal(0.02, 0.008, 160)) + 1e-4
    assert_parity(technique, kind, tt, P=5)


@pytest.mark.parametrize("technique", ("SS", "FAC", "AWF-C"))
def test_parity_uniform_tasks(technique):
    """Uniform costs route eligible runs through the fast-forward; the
    log must still match the oracle event-for-event."""
    tt = np.full(300, 0.01)
    for kind in SCENARIO_KINDS:
        assert_parity(technique, kind, tt, P=4)


@pytest.mark.parametrize("technique", ("SS", "GSS", "FAC"))
def test_parity_nonrobust_hang(technique):
    """rdlb_enabled=False + a fail-stop: both cores hang identically
    (paper Fig. 1b), with identical partial logs and completion sets."""
    rng = np.random.default_rng(3)
    tt = np.abs(rng.normal(0.02, 0.01, 120)) + 1e-4
    st_f, st_r = assert_parity(technique, "fail_stop", tt, P=4,
                               rdlb_on=False)
    assert st_f.hung and st_r.hung


@pytest.mark.parametrize("bdup", (1, None))
def test_parity_barrier_damping(bdup):
    """AWF-B's batch-weight barrier (with and without the damping cap)
    exercises the barrier-miss escalation and the capped re-issue scan."""
    rng = np.random.default_rng(11)
    tt = np.abs(rng.normal(0.02, 0.012, 200)) + 1e-4
    for kind in ("msg_latency", "straggler", "fail_stop"):
        assert_parity("AWF-B", kind, tt, P=5,
                      barrier_max_duplicates=bdup)


def test_parity_max_duplicates_cap():
    rng = np.random.default_rng(5)
    tt = np.abs(rng.normal(0.02, 0.01, 150)) + 1e-4
    for technique in ("SS", "FAC", "AF"):
        assert_parity(technique, "fail_stop", tt, P=5, max_duplicates=1)


# -------------------------------------------------- randomized parity suite
@given(technique=st.sampled_from(dls.ALL_TECHNIQUES),
       kind=st.sampled_from(SCENARIO_KINDS),
       seed=st.integers(0, 10**6),
       rdlb_on=st.booleans(),
       max_dup=st.sampled_from((None, 1, 2)))
@settings(max_examples=40, deadline=None)
def test_randomized_parity(technique, kind, seed, rdlb_on, max_dup):
    """Property: ANY (technique, scenario, seed, knobs) draw produces
    identical logs and completion sets on both cores — including
    non-robust hangs and barrier damping."""
    rng = np.random.default_rng(seed)
    N = int(rng.integers(40, 160))
    P = int(rng.integers(2, 7))
    uniform = bool(rng.integers(0, 2))
    tt = (np.full(N, 0.02) if uniform
          else np.abs(rng.normal(0.02, 0.01, N)) + 1e-4)
    assert_parity(technique, kind, tt, P=P, seed=seed % 1000,
                  rdlb_on=rdlb_on, max_duplicates=max_dup)


# ------------------------------------------------------------ fast-forward
def test_fast_forward_engages_on_uniform_baseline():
    tt = np.full(900, 0.01)
    tech = dls.make_technique("SS", len(tt), 6)
    q = rdlb.RobustQueue(len(tt), tech)
    eng = engine.Engine(q, [engine.EngineWorker(w) for w in range(6)],
                        simulator.SimBackend(tt), h=1e-4)
    st = eng.run()
    assert st.fast_forwarded > 0
    assert not st.hung and st.n_finished == 900
    # oracle comparison (scalar loop, event by event)
    tech_r = dls.make_technique("SS", len(tt), 6)
    q_r = refqueue.ReferenceQueue(len(tt), tech_r)
    eng_r = engine.Engine(q_r, [engine.EngineWorker(w) for w in range(6)],
                          simulator.SimBackend(tt), h=1e-4)
    st_r = eng_r.run()
    assert st_r.fast_forwarded == 0            # oracle never fast-forwards
    assert log_key(st) == log_key(st_r)
    assert st.t_virtual == pytest.approx(st_r.t_virtual, rel=1e-9)
    assert st.n_duplicates == st_r.n_duplicates


def test_fast_forward_declines_outside_regime():
    """Perturbed workers, adaptive techniques, varying costs, h=0 — all
    must decline fast-forward (and still match the oracle, which the
    parity grid asserts)."""
    tt_u = np.full(800, 0.01)
    P = 4

    def ff_count(tt, technique="SS", h=1e-4, workers=None):
        tech = dls.make_technique(technique, len(tt), P)
        q = rdlb.RobustQueue(len(tt), tech)
        ws = workers or [engine.EngineWorker(w) for w in range(P)]
        eng = engine.Engine(q, ws, simulator.SimBackend(np.asarray(tt)),
                            h=h)
        return eng.run().fast_forwarded

    assert ff_count(tt_u) > 0                       # sanity: regime works
    rng = np.random.default_rng(0)
    assert ff_count(np.abs(rng.normal(0.01, 0.005, 800)) + 1e-4) == 0
    assert ff_count(tt_u, technique="AWF-C") == 0   # feedback-dependent
    assert ff_count(tt_u, h=0.0) == 0               # tie-unsafe
    slow = [engine.EngineWorker(w) for w in range(P)]
    slow[2].speed = 0.5
    assert ff_count(tt_u, workers=slow) == 0        # heterogeneous
    failing = [engine.EngineWorker(w) for w in range(P)]
    failing[1].fail_time = 1.0
    assert ff_count(tt_u, workers=failing) == 0     # perturbation pending


def test_fast_forward_uniform_latency_parity():
    """Uniform nonzero latency stays in the fast-forward regime."""
    tt = np.full(600, 0.01)
    P = 5

    def run_with(queue_cls):
        tech = dls.make_technique("mFSC", len(tt), P)
        q = queue_cls(len(tt), tech)
        ws = [engine.EngineWorker(w, msg_latency=0.01) for w in range(P)]
        eng = engine.Engine(q, ws, simulator.SimBackend(tt), h=1e-4)
        return eng.run()

    st_f = run_with(rdlb.RobustQueue)
    st_r = run_with(refqueue.ReferenceQueue)
    assert log_key(st_f) == log_key(st_r)
    assert st_f.t_virtual == pytest.approx(st_r.t_virtual, rel=1e-9)


# ----------------------------------------------------- batched technique API
@pytest.mark.parametrize("technique", dls.NONADAPTIVE_TECHNIQUES
                         + ("STATIC",))
def test_bulk_sizes_match_sequential(technique):
    """bulk_sizes ≡ the same number of sequential next_chunk calls,
    state advance included (consumed in uneven pieces)."""
    N, P = 700, 5
    seq_tech = dls.make_technique(technique, N, P, seed=9)
    bulk_tech = dls.make_technique(technique, N, P, seed=9)
    seq_sizes, R = [], N
    while R > 0:
        s = seq_tech.next_chunk(0, R)
        seq_sizes.append(s)
        R -= s
    bulk_sizes, R = [], N
    piece = 1
    while R > 0:
        got = bulk_tech.bulk_sizes(R, piece)
        assert got is not None
        assert len(got) > 0
        bulk_sizes.extend(int(x) for x in got)
        R -= int(got.sum())
        piece = piece % 7 + 3                     # uneven consumption
    assert bulk_sizes == seq_sizes
    assert sum(bulk_sizes) == N


def test_bulk_sizes_none_for_feedback_dependent():
    for technique in dls.ADAPTIVE_TECHNIQUES:
        tech = dls.make_technique(technique, 100, 4)
        assert tech.bulk_sizes(100, 10) is None
    wf = dls.make_technique("WF", 100, 4, weights=[1, 2, 3, 4])
    assert wf.bulk_sizes(100, 10) is None         # PE-dependent sizes


def test_fixed_chunk_advertised():
    for technique, expect in (("SS", 1), ("STATIC", 25)):
        tech = dls.make_technique(technique, 100, 4)
        assert tech.fixed_chunk() == expect
    for technique in ("GSS", "TSS", "FAC", "RAND", "AF", "AWF-B"):
        assert dls.make_technique(technique, 100, 4).fixed_chunk() is None


# ------------------------------------------------------- flag views / log
def test_unfinished_ids_numpy_view():
    q = rdlb.RobustQueue(10, dls.make_technique("SS", 10, 2))
    c0 = q.request(0)
    c1 = q.request(1)
    q.report(c0)
    ids = q.unfinished_ids()
    assert isinstance(ids, np.ndarray)
    assert ids.tolist() == q.unfinished_tasks()   # thin wrapper agrees
    assert c0.start not in ids and c1.start in ids
    assert q.flags_view() is q.flags


def test_chunk_log_sequence_semantics():
    q = rdlb.RobustQueue(20, dls.make_technique("SS", 20, 3))
    chunks = [q.request(i % 3) for i in range(5)]
    log = q.chunk_log()
    assert len(log) == 5
    assert list(log) == chunks                    # lazy view == objects
    assert log[0] == chunks[0] and log[-1] == chunks[-1]
    assert log[1:3] == chunks[1:3]
    assert log == chunks                          # Sequence equality
    with pytest.raises(IndexError):
        log[5]


# ------------------------------------------- paper-scalability slope sanity
def test_scale_slope_small():
    """fig_scale's trend, asserted at small scale: with one fail-stop and
    fixed total work, the rDLB overhead ratio decreases as P grows
    (theory: H_T ∝ (n+1)/(q−1) with n = N/q — quadratic decrease)."""
    from benchmarks import fig_scale
    rows = fig_scale.overhead_points(Ps=(4, 8, 16), N=2048, t=0.01,
                                     seed=1)
    overheads = [r["overhead"] for r in rows]
    assert all(h >= -0.02 for h in overheads)     # failures cost, not gain
    assert overheads[0] > overheads[-1]           # decreasing in P
    theory = [r["theory_overhead"] for r in rows]
    assert theory[0] > theory[1] > theory[2]


def test_fast_core_speed_smoke():
    """Perf canary at CI-friendly scale: a P=256/N=65536 uniform SS run
    must stay well under a second (it fast-forwards); catches accidental
    re-introduction of per-task Python loops."""
    import time
    tt = np.full(65536, 0.01)
    tech = dls.make_technique("SS", len(tt), 256)
    q = rdlb.RobustQueue(len(tt), tech)
    eng = engine.Engine(q, [engine.EngineWorker(w) for w in range(256)],
                        simulator.SimBackend(tt), h=1e-4)
    t0 = time.perf_counter()
    st = eng.run()
    dt = time.perf_counter() - t0
    assert not st.hung and st.fast_forwarded > 0
    assert dt < 5.0, f"fast core took {dt:.2f}s at P=256/N=65536"
