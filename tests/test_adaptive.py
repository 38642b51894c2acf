"""Adaptive subsystem tests: snapshot capture, forecaster resume parity
(simulator-resumed-from-snapshot == fresh simulation of the remainder),
mid-run hot-swap exactly-once invariants in BOTH engine modes, controller
behaviour, executor wiring, and the acceptance criterion: under the
Table-1 perturbation scenarios the adaptive policy is never worse than
the worst static portfolio technique and within 15% of the per-scenario
oracle-best."""

import math

import numpy as np
import pytest

from repro import api
from repro.adaptive import (AdaptiveConfig, AdaptiveController, Candidate,
                            capture, coarsen_times, forecast_candidate,
                            run_adaptive, run_static, sweep)
from repro.api import scenarios
from repro.core import dls, engine, rdlb, simulator

P_SMALL, N_SMALL = 4, 96
PORTFOLIO = tuple(Candidate(t) for t in ("FAC", "GSS", "mFSC", "AWF-C",
                                         "AF"))


def task_times(n, seed=0, mean=0.01, sd=0.004):
    rng = np.random.default_rng(seed)
    return np.abs(rng.normal(mean, sd, n)) + 1e-4


def cluster(*workers, name=""):
    return api.ClusterSpec(n_workers=len(workers), workers=workers,
                           name=name)


def perturb_scenario():
    return cluster(
        api.WorkerSpec(),
        api.WorkerSpec(speed=0.25),
        api.WorkerSpec(msg_latency=0.05),
        api.WorkerSpec(),
        name="mix")


class CaptureAt:
    """Adaptive stub: snapshot the run after the k-th report."""

    def __init__(self, after_reports):
        self.after = after_reports
        self.snap = None

    def bind(self, engine):
        self._n = 0

    def on_report(self, engine, t):
        self._n += 1
        if self.snap is None and self._n >= self.after:
            self.snap = capture(engine, t)


class SwapAt:
    """Adaptive stub: hot-swap technique/knobs after the k-th report."""

    def __init__(self, after_reports, technique="GSS", max_duplicates=2):
        self.after = after_reports
        self.technique = technique
        self.max_duplicates = max_duplicates
        self.swapped_at = None

    def bind(self, engine):
        self._n = 0

    def on_report(self, engine, t):
        self._n += 1
        if self.swapped_at is None and self._n >= self.after:
            q = engine.queue
            remaining = q.N - q.n_finished
            tech = dls.make_technique(self.technique, max(1, remaining),
                                      len(engine.workers))
            tech.adopt_stats(q.technique.stats)
            q.swap_technique(tech, max_duplicates=self.max_duplicates)
            self.swapped_at = engine.queue.n_assignments


class CountingBackend(engine.WorkerBackend):
    """Counts commits per task id — the exactly-once witness."""

    def __init__(self, task_times=None):
        self._ctime = (None if task_times is None else
                       np.cumsum(np.concatenate([[0.0], task_times])))
        self.commits = {}

    def cost(self, chunk, wid):
        if self._ctime is None:
            return float(chunk.size)
        return float(self._ctime[chunk.stop] - self._ctime[chunk.start])

    def commit(self, chunk, wid, payload, newly):
        for t in newly:
            self.commits[t] = self.commits.get(t, 0) + 1


def run_engine(policy, *, threaded=False, scenario=None, n=N_SMALL,
               technique="FAC", tt=None):
    sc = scenario or perturb_scenario()
    tt = task_times(n) if tt is None else tt
    tech = dls.make_technique(technique, n, sc.n_workers, seed=1)
    queue = rdlb.RobustQueue(n, tech)
    backend = CountingBackend(tt)
    eng = engine.Engine(queue, sc.engine_workers(), backend, h=1e-4,
                        adaptive=policy)
    st = eng.run_threaded() if threaded else eng.run()
    return st, queue, backend


# --------------------------------------------------------------- snapshot
def test_snapshot_capture_midrun():
    policy = CaptureAt(after_reports=6)
    st, queue, _ = run_engine(policy)
    snap = policy.snap
    assert snap is not None and not st.hung
    assert snap.n_tasks == N_SMALL
    assert 0 < snap.n_finished < N_SMALL
    assert snap.n_finished + snap.n_remaining == N_SMALL
    assert set(snap.unscheduled).isdisjoint(snap.scheduled_unfinished)
    assert np.array_equal(
        snap.remaining,
        np.sort(np.concatenate([snap.unscheduled,
                                snap.scheduled_unfinished])))
    assert snap.technique == "FAC"
    assert snap.n_alive == P_SMALL          # no fail-stops in this mix
    assert any(w.observed_rate > 0 for w in snap.workers)
    # stats are copies: mutating the live technique can't change the snap
    before = snap.workers[0].stats.iters_done
    queue.technique.stats[0].record_chunk(5, 1.0, 0.0)
    assert snap.workers[0].stats.iters_done == before


def test_snapshot_excludes_future_failures():
    sc = cluster(api.WorkerSpec(), api.WorkerSpec(fail_time=1e9),
                 name="late_fail")
    policy = CaptureAt(after_reports=2)
    run_engine(policy, scenario=sc, n=16)
    snap = policy.snap
    # the doomed worker is alive AT capture time, so the forecast
    # scenario includes it (failures are unknowable in advance)
    assert snap.n_alive == 2


# ----------------------------------------------- resume == fresh simulate
@pytest.mark.parametrize("cand", [Candidate("FAC"), Candidate("GSS"),
                                  Candidate("AWF-C")])
def test_forecast_resume_matches_fresh_simulation(cand):
    """THE resume property: a forecast from a mid-run snapshot equals a
    fresh simulation of the same remainder under the same conditions."""
    policy = CaptureAt(after_reports=5)
    tt = task_times(N_SMALL)
    run_engine(policy, tt=tt)
    snap = policy.snap
    h = 1e-4
    predicted = forecast_candidate(snap, tt, cand, h=h, seed=0,
                                   max_sim_tasks=None, prewarm=False)

    # fresh simulation of the remainder, built by hand from the snapshot
    rem_times = tt[np.array(snap.remaining)]
    fresh_sc = cluster(*(api.WorkerSpec(speed=w.speed,
                                        msg_latency=w.msg_latency)
                         for w in snap.workers if w.alive), name="fresh")
    tech = dls.make_technique(cand.technique, len(rem_times),
                              fresh_sc.n_workers, seed=0, h=h)
    fresh = api.simulate(api.RunSpec(cluster=fresh_sc,
                                     execution=api.ExecutionSpec(h=h)),
                         rem_times, technique=tech)
    assert predicted == fresh.t_par


def test_coarsen_preserves_total_work():
    tt = task_times(1000)
    c = coarsen_times(tt, 128)
    assert len(c) == 128
    assert c.sum() == pytest.approx(tt.sum())
    assert coarsen_times(tt, None) is tt or np.array_equal(
        coarsen_times(tt, None), tt)
    assert np.array_equal(coarsen_times(tt, 2000), tt)


def test_forecast_empty_remainder_is_zero():
    policy = CaptureAt(after_reports=1)
    tt = task_times(8)
    run_engine(policy, n=8, tt=tt)
    snap = policy.snap
    snap.remaining = []
    assert forecast_candidate(snap, tt, Candidate("FAC")) == 0.0


# ------------------------------------------------ hot-swap exactly-once
def test_hot_swap_exactly_once_virtual():
    """Every task commits exactly once across a swap boundary (run())."""
    policy = SwapAt(after_reports=4, technique="GSS", max_duplicates=2)
    st, queue, backend = run_engine(policy)
    assert policy.swapped_at is not None
    assert not st.hung and queue.done
    assert backend.commits == {t: 1 for t in range(N_SMALL)}
    assert queue.max_duplicates == 2
    assert queue.technique.name == "GSS"
    # chunks were assigned both before and after the swap
    assert 0 < policy.swapped_at < len(st.assignment_log)


def test_hot_swap_exactly_once_threaded():
    """Same invariant under real OS-thread concurrency, with a straggler
    and a count-based fail-stop racing the swap."""
    n = 48
    sc = scenarios.baseline(3)
    policy = SwapAt(after_reports=3, technique="GSS")
    tt = task_times(n)
    tech = dls.make_technique("SS", n, 3, seed=1)
    queue = rdlb.RobustQueue(n, tech)
    backend = CountingBackend(tt)
    workers = sc.engine_workers()
    workers[0].sleep_per_task = 0.002          # straggler
    workers[2].fail_after_tasks = 5            # dies holding a chunk
    eng = engine.Engine(queue, workers, backend, h=0.0, adaptive=policy)
    st = eng.run_threaded()
    assert not st.hung and queue.done
    assert policy.swapped_at is not None
    assert backend.commits == {t: 1 for t in range(n)}


def test_swap_preserves_learned_stats():
    """A pre-warmed swap carries the incumbent's per-PE measurements."""
    policy = SwapAt(after_reports=6, technique="AWF-C")
    st, queue, _ = run_engine(policy)
    assert not st.hung
    # the swapped-in AWF-C started from learned (nonzero) measurements
    assert sum(s.iters_done for s in queue.technique.stats) > 0


def test_adopt_stats_scaled_copy():
    src = dls.PEStats()
    for _ in range(4):
        src.record_chunk(10, 0.5, 0.01)
    tech = dls.make_technique("AF", 100, 2)
    tech.adopt_stats([src, src], time_scale=4.0)
    got = tech.stats[0]
    assert got is not src and got is not tech.stats[1]
    assert got.mean_iter_time == pytest.approx(src.mean_iter_time * 4)
    assert got.var_iter_time == pytest.approx(src.var_iter_time * 16)
    assert got.rate(False) == pytest.approx(src.rate(False) / 4)
    assert got.iters_done == src.iters_done


def test_swap_technique_defaults_keep_knobs():
    q = rdlb.RobustQueue(16, dls.make_technique("AWF-B", 16, 2))
    q._barrier_waiters[0] = 2
    q.swap_technique(dls.make_technique("FAC", 16, 2))
    assert q._barrier_waiters == {}
    assert q.max_duplicates is None            # knobs untouched by default
    assert q.barrier_max_duplicates == 1


# ------------------------------------------------------------- controller
def test_controller_records_decisions_and_completes():
    tt = task_times(256)
    sc = scenarios.pe_perturbation(8, node_size=4)  # workers 4..7 slowed
    cfg = AdaptiveConfig(portfolio=PORTFOLIO, decision_every_chunks=16,
                         min_remaining=16, max_sim_tasks=None)
    res, ctrl = run_adaptive(tt, sc, initial="FAC", config=cfg)
    assert not res.hang and res.n_finished == 256
    assert ctrl.decisions                       # at least the t=0 plan
    for d in ctrl.decisions:
        assert set(d.predictions) >= {c.label for c in PORTFOLIO}
        assert d.chosen in d.predictions


def test_controller_swaps_away_from_bad_initial():
    """Start from SS with a large master overhead: every forecast sees
    SS's serialization cost and the t=0 plan must swap off it."""
    tt = np.full(512, 0.001)
    sc = scenarios.baseline(8)
    cfg = AdaptiveConfig(portfolio=(Candidate("FAC"),),
                         decision_every_chunks=None, max_sim_tasks=None,
                         hysteresis=0.05)
    ctrl = AdaptiveController(task_times=tt, config=cfg)
    tech = dls.make_technique("SS", 512, 8, h=5e-3)
    spec = api.RunSpec(cluster=sc, execution=api.ExecutionSpec(h=5e-3))
    res = api.simulate(spec, tt, technique=tech, adaptive=ctrl)
    assert ctrl.decisions[0].swapped
    assert ctrl.decisions[0].chosen == "FAC"
    ss = run_static(tt, sc, Candidate("SS"), h=5e-3).t_par
    assert res.t_par < ss


def test_controller_reusable_across_runs():
    tt = task_times(128)
    spec = api.RunSpec(cluster=scenarios.baseline(4))
    cfg = AdaptiveConfig(portfolio=PORTFOLIO[:2], max_sim_tasks=None)
    ctrl = AdaptiveController(task_times=tt, config=cfg)
    for _ in range(2):
        tech = dls.make_technique("FAC", 128, 4)
        r = api.simulate(spec, tt, technique=tech, adaptive=ctrl)
        assert not r.hang
    assert len(ctrl.decisions) >= 1             # re-bound, not accumulated


def test_stats_surface_decisions():
    tt = task_times(128)
    cfg = AdaptiveConfig(portfolio=PORTFOLIO[:3], max_sim_tasks=None)
    ctrl = AdaptiveController(task_times=tt, config=cfg)
    tech = dls.make_technique("FAC", 128, 4)
    queue = rdlb.RobustQueue(128, tech)
    eng = engine.Engine(queue, scenarios.baseline(4).engine_workers(),
                        simulator.SimBackend(tt), adaptive=ctrl)
    st = eng.run()
    assert st.adaptive_decisions == ctrl.decisions


# ------------------------------------------------- acceptance criterion
@pytest.mark.parametrize("scenario_fn", [
    lambda P: scenarios.pe_perturbation(P, node_size=8),
    lambda P: scenarios.latency_perturbation(P, node_size=8, delay=0.5),
    lambda P: scenarios.combined_perturbation(P, node_size=8,
                                              slowdown=0.25, delay=0.5),
])
def test_adaptive_within_15pct_of_oracle(scenario_fn):
    """ISSUE acceptance: under the Table-1 perturbation scenarios, the
    adaptive policy is never worse than the worst static portfolio
    technique and within 15% of the per-scenario oracle-best."""
    P, N = 32, 1024
    tt = task_times(N)
    sc = scenario_fn(P)
    h = 1e-4
    statics = [run_static(tt, sc, c, h=h).t_par for c in PORTFOLIO]
    assert all(math.isfinite(t) for t in statics)
    best, worst = min(statics), max(statics)
    cfg = AdaptiveConfig(portfolio=PORTFOLIO, decision_every_chunks=64,
                         min_remaining=32, max_sim_tasks=None)
    res, ctrl = run_adaptive(tt, sc, initial="FAC", config=cfg, h=h)
    assert not res.hang
    assert res.t_par <= worst * 1.001
    assert res.t_par <= best * 1.15


def test_forecast_sweep_is_bounded_by_coarsening():
    """The in-loop cost knob: a coarsened sweep simulates at most
    max_sim_tasks meta-tasks per candidate regardless of N."""
    tt = task_times(4096)
    tech = dls.make_technique("FAC", 4096, 16)
    queue = rdlb.RobustQueue(4096, tech)
    eng = engine.Engine(queue, scenarios.baseline(16).engine_workers(),
                        simulator.SimBackend(tt))
    snap = capture(eng, 0.0)
    preds = sweep(snap, tt, PORTFOLIO[:3], max_sim_tasks=256)
    assert len(preds) == 3
    assert all(math.isfinite(t) for _, t in preds)
    # coarse forecast approximates the exact one
    exact = dict((c.label, t) for c, t in
                 sweep(snap, tt, PORTFOLIO[:1], max_sim_tasks=None))
    coarse = dict((c.label, t) for c, t in preds)
    label = PORTFOLIO[0].label
    assert coarse[label] == pytest.approx(exact[label], rel=0.35)


# -------------------------------------------------------- executor wiring
def test_executors_accept_adaptive_policy():
    import jax

    from repro.data import batch_for_step
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.runtime import RDLBServeExecutor, RDLBTrainExecutor, Request

    cfg_m = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                        n_kv_heads=2, d_ff=64, vocab_size=64)
    model = build_model(cfg_m)
    params = model.init(jax.random.PRNGKey(0))

    acfg = AdaptiveConfig(portfolio=(Candidate("FAC"), Candidate("GSS")),
                          min_remaining=1, max_sim_tasks=None)
    ctrl = AdaptiveController(config=acfg)       # unit-cost tasks
    ex = RDLBTrainExecutor(model, spec=api.train_spec(n_workers=2,
                                                      n_tasks=4),
                           exact_accumulation=True, adaptive=ctrl)
    batch = batch_for_step(cfg_m, 0, 8, 16)
    opt_state = ex.opt.init(params)
    res = ex.train_step(params, opt_state, batch)
    assert not res.hung and np.isfinite(res.loss)
    assert len(ctrl.decisions) >= 1              # t=0 plan ran

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 64, size=4).astype(np.int32),
                    max_new_tokens=2) for i in range(6)]
    ctrl2 = AdaptiveController(config=acfg)
    sx = RDLBServeExecutor(model, params, spec=api.serve_spec(n_workers=2),
                           adaptive=ctrl2)
    stats = sx.serve(reqs)
    assert not stats.hung
    assert all(r.output is not None for r in reqs)
    assert len(ctrl2.decisions) >= 1
