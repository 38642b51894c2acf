"""Parity suite for the device-resident batched simulator.

The contract under test (src/repro/core/devicesim.py): inside the
lowered regime, every element of a batched jit/vmap call matches
``Engine.run`` exactly — ``t_par`` to float64 round-off (1e-9 absolute,
the engine itself is float64), and the integer counters
(assignments/duplicates/finished/wasted, per-worker tasks) bit-for-bit.
Outside the regime, ``lower_run`` must DECLINE with a reason, and a
batched element that exhausts its budget must come back ``valid=False``
— the device path degrades to the scalar oracle, never silently
mis-simulates.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro import api
from repro.adaptive import capture, sweep
from repro.api import DEVICE_PORTFOLIO, scenarios
from repro.core import devicesim

ATOL = 1e-9


def fail_stop_cluster(fail_times, name):
    """One worker per fail time (inf = survives)."""
    return api.ClusterSpec(
        n_workers=len(fail_times), name=name,
        workers=tuple(api.WorkerSpec(
            fail_time=None if np.isinf(f) else float(f))
            for f in fail_times))


def _spec(tech, P, *, rdlb=True, h=1e-4, seed=0):
    return api.RunSpec(
        scheduling=api.SchedulingSpec(technique=tech, seed=seed),
        robustness=api.RobustnessSpec(rdlb_enabled=rdlb),
        cluster=scenarios.baseline(P),
        execution=api.ExecutionSpec(h=h))


def _check(spec, times, fail_times=None):
    """One device element vs one scalar engine run; returns t_par."""
    res = devicesim.simulate_spec(spec, times, fail_times=fail_times)
    assert res is not None, "expected spec to lower"
    assert res.valid.all(), "budget must suffice at test scale"
    if fail_times is not None:
        spec = dataclasses.replace(
            spec, cluster=fail_stop_cluster(fail_times[0], "draw"))
    ref = api.simulate(spec, times)
    assert res.t_par[0] == pytest.approx(ref.t_par, abs=ATOL)
    assert res.n_assignments[0] == ref.n_assignments
    assert res.n_duplicates[0] == ref.n_duplicates
    assert res.n_finished[0] == ref.n_finished
    assert res.wasted_tasks[0] == ref.wasted_tasks
    np.testing.assert_allclose(res.pe_busy[0], ref.pe_busy, atol=ATOL)
    return float(res.t_par[0])


# ------------------------------------------------------------- parity grid
@pytest.mark.parametrize("tech", ["SS", "STATIC", "mFSC", "FSC"])
@pytest.mark.parametrize("P", [4, 16, 64])
def test_parity_clean_grid(tech, P):
    """Failure-free grid over techniques x P x (divisible / partial-chunk
    / tiny) workloads, rdlb on and off — exercises both clean tails."""
    for N in (4 * P, 4 * P + 3, 100):
        times = np.full(N, 0.01)
        for rdlb in (True, False):
            _check(_spec(tech, P, rdlb=rdlb), times)


@pytest.mark.parametrize("tech", ["SS", "mFSC"])
@pytest.mark.parametrize("k", [1, 2, None])      # None -> P-1
def test_parity_failure_draws(tech, k):
    """Fail-stop draws: rdlb survives (finite t_par parity), the
    non-robust run hangs in BOTH engines (Fig. 1b)."""
    P, N = 8, 200
    k = P - 1 if k is None else k
    times = np.full(N, 0.01)
    rng = np.random.default_rng(k)
    fail = np.full((1, P), np.inf)
    victims = rng.choice(np.arange(1, P), size=k, replace=False)
    fail[0, victims] = rng.uniform(0.02, 0.15, size=k)
    t_rob = _check(_spec(tech, P, rdlb=True), times, fail_times=fail)
    assert np.isfinite(t_rob)
    res = devicesim.simulate_spec(_spec(tech, P, rdlb=False), times,
                                  fail_times=fail)
    assert res.valid.all() and res.hung.all() and np.isinf(res.t_par[0])


def test_parity_latency_and_small_N():
    """Message latency and N < P (transaction tail from the start)."""
    for tech, P, N in (("SS", 8, 5), ("STATIC", 8, 5), ("SS", 16, 300)):
        spec = _spec(tech, P)
        spec = dataclasses.replace(
            spec, cluster=api.ClusterSpec(
                n_workers=P,
                workers=tuple(api.WorkerSpec(msg_latency=5e-4)
                              for _ in range(P))))
        _check(spec, np.full(N, 0.01))


def test_parity_monte_carlo_batch():
    """A batched MC cell (paired draws over 3 techniques) matches a
    per-draw scalar loop element-for-element."""
    P, N, D = 16, 160, 16
    times = np.full(N, 0.01)
    specs = [_spec(t, P) for t in ("SS", "mFSC", "FSC")]
    lows = [devicesim.lower_run(s, times)[0] for s in specs]
    assert all(lo is not None for lo in lows)
    rng = np.random.default_rng(7)
    fail = np.full((D, P), np.inf)
    for d in range(D):
        v = rng.choice(np.arange(1, P), size=3, replace=False)
        fail[d, v] = rng.uniform(0.01, 0.12, size=3)
    res = devicesim.simulate_many(
        lows, tech_of=np.repeat(np.arange(3, dtype=np.int32), D),
        fail_times=np.tile(fail, (3, 1)))
    assert res.valid.all()
    for b in range(3 * D):
        t_ix, d = divmod(b, D)
        sp = dataclasses.replace(
            specs[t_ix], cluster=fail_stop_cluster(fail[d], "x"))
        ref = api.simulate(sp, times)
        assert res.t_par[b] == pytest.approx(ref.t_par, abs=ATOL), (b,)
        assert res.n_duplicates[b] == ref.n_duplicates


# --------------------------------------------------------- regime boundary
def test_declines_never_missimulates():
    """Everything outside the homogeneous fixed-chunk regime must DECLINE
    at lowering — falling back to the scalar engine, not mis-simulating."""
    times = np.full(64, 0.01)
    declined = {}
    cases = {
        "adaptive_chunking": _spec("GSS", 4),
        "heterogeneous": dataclasses.replace(
            _spec("SS", 4), cluster=api.ClusterSpec(
                n_workers=4,
                workers=tuple(api.WorkerSpec(speed=s)
                              for s in (1.0, 1.0, 0.5, 0.5)))),
        "dup_cap": dataclasses.replace(
            _spec("SS", 4),
            robustness=api.RobustnessSpec(max_duplicates=2)),
        "h_zero": _spec("SS", 4, h=0.0),
        "adaptive_policy": dataclasses.replace(
            _spec("SS", 4), adaptive=api.AdaptiveSpec(enabled=True)),
    }
    for name, spec in cases.items():
        lo, why = devicesim.lower_run(spec, times)
        assert lo is None, name
        declined[name] = why
    # non-uniform task costs break the round-robin serve-order proof
    lo, why = devicesim.lower_run(
        _spec("SS", 4), np.linspace(0.01, 0.02, 64))
    assert lo is None and "spread" in why
    # ... and every reason is a actionable string, not empty
    assert all(declined.values())


def test_budget_exhaustion_flags_invalid():
    """An element that outruns its scan budget returns valid=False (the
    caller's cue to re-run on the scalar engine) — force it by calling
    the compiled kernel with an artificially tiny round budget."""
    times = np.full(400, 0.01)
    spec = _spec("SS", 4)
    lo, _ = devicesim.lower_run(spec, times)
    import jax
    import jax.numpy as jnp
    fn = devicesim._compiled(4, lo.n_chunks, 16, 0, "sorted")
    with jax.enable_x64(True):
        res = fn(jnp.zeros(1, jnp.int32), jnp.ones(1, bool),
                 jnp.full((1, 4), jnp.inf), jnp.full(1, lo.h),
                 jnp.full(1, lo.lat), jnp.full(1, lo.speed),
                 jnp.asarray(lo.chunk_costs[None]),
                 jnp.asarray(lo.chunk_sizes[None]),
                 jnp.asarray([lo.n_chunks], jnp.int32),
                 jnp.asarray([lo.N], jnp.int64))
    assert not bool(res[2][0])        # valid flag


# ------------------------------------------------------ forecaster parity
def test_device_sweep_matches_scalar_sweep():
    """The batched portfolio forecast ranks and scores candidates exactly
    as the scalar per-candidate loop (t=0 snapshot, live engine)."""
    from repro.core import dls, engine, rdlb, simulator
    P, N = 8, 400
    tt = np.full(N, 0.01)
    tech = dls.make_technique("SS", N, P)
    queue = rdlb.RobustQueue(N, tech)
    eng = engine.Engine(
        queue, scenarios.baseline(P).engine_workers(),
        simulator.SimBackend(tt))
    snap = capture(eng, 0.0)
    scalar = sweep(snap, tt, DEVICE_PORTFOLIO, device=False)
    device = sweep(snap, tt, DEVICE_PORTFOLIO, device=True)
    assert [c.label for c, _ in device] == [c.label for c, _ in scalar]
    for (_, a), (_, b) in zip(device, scalar):
        assert a == pytest.approx(b, abs=ATOL)


def test_adaptive_run_device_flag_is_transparent():
    """An end-to-end adaptive run makes identical decisions with
    device_sweep on and off (the flag changes cost, not behaviour)."""
    tt = np.full(600, 0.01)
    def go(dev):
        spec = dataclasses.replace(
            _spec("mFSC", 8),
            adaptive=api.AdaptiveSpec(
                enabled=True, device_sweep=dev, decision_every_chunks=30,
                portfolio=(api.Candidate("SS"), api.Candidate("STATIC"),
                           api.Candidate("mFSC"))))
        return api.simulate(spec, tt)
    a, b = go(True), go(False)
    assert a.t_par == pytest.approx(b.t_par, abs=ATOL)
    da = [(d.chosen, d.predictions) for d in a.adaptive_decisions]
    db = [(d.chosen, d.predictions) for d in b.adaptive_decisions]
    assert len(da) == len(db) and da
    for (ca, pa), (cb, pb) in zip(da, db):
        assert ca == cb
        assert pa.keys() == pb.keys()
        for k in pa:
            assert pa[k] == pytest.approx(pb[k], abs=1e-7)


# ----------------------------------------------------------- spec plumbing
def test_adaptivespec_device_flag_round_trips():
    spec = _spec("SS", 4)
    spec = dataclasses.replace(
        spec, adaptive=api.AdaptiveSpec(enabled=True, device_sweep=True))
    again = api.RunSpec.from_dict(spec.to_dict())
    assert again.adaptive.device_sweep is True
    assert again.adaptive.to_config().device_sweep is True


def test_monte_carlo_smoke():
    """A tiny --monte-carlo cell produces finite rho with paired draws
    and the most robust technique pinned at 1.0."""
    from benchmarks import fig4_resilience
    rows, lines = fig4_resilience.monte_carlo(P=8, n_tasks=64, draws=32,
                                              cells=(1,))
    assert len(rows) == 3
    by_tech = {r[1]: r for r in rows}
    means = {t: r[3] for t, r in by_tech.items()}
    assert min(means.values()) == pytest.approx(1.0)
    for t, r in by_tech.items():
        assert np.isfinite(r[3]) and r[4] >= 0.0 and r[5] == 0.0
