"""Scalability benchmark: the array-native core at paper-theory scale.

The paper's §4 claims rDLB is linearly scalable and its robustness cost
decreases ~quadratically with system size — claims that can only be
checked empirically if the simulator reaches thousands of workers and a
million tasks.  This module measures, on the array core:

  1. **scale points** — T_par, wall-clock, and event throughput for SS at
     P ∈ {64 … 4096}, N up to 2²⁰ (uniform tasks, the theory's model);
  2. **speedup** — the array core vs the preserved pure-Python reference
     core (`repro.core.refqueue`) on the same run (acceptance: ≥50× at
     P=1024 / N=262144);
  3. **overhead trend** — measured rDLB overhead under one mid-run
     fail-stop vs `repro.core.theory.rdlb_overhead`: decreasing in P
     (sanity-asserted at small scale in tests/test_fastcore.py);
  4. **sweep cost** — one full adaptive portfolio sweep at P=1024,
     N=131072 (acceptance: < 2 s in the in-loop configuration);
  5. **device sweep** — ONE jit/vmap `repro.core.devicesim` call
     simulating >=1000 (candidate × draw) runs at P=1024 vs the
     equivalent Python loop of fast-forward simulations (acceptance:
     >=10× warm, with device-vs-scalar t_par parity asserted).

Writes fig_scale.csv + machine-readable BENCH_scale.json to
artifacts/bench/ (a committed reference copy lives in
benchmarks/baselines/BENCH_scale.json — CI refreshes it from the dry
run so the bench trajectory is seeded for successor PRs).

    PYTHONPATH=src python benchmarks/fig_scale.py            # full
    PYTHONPATH=src python benchmarks/fig_scale.py --dry-run  # CI smoke
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):             # `python benchmarks/fig_scale.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks import common
from repro import api
from repro.core import refqueue, theory


def _spec(technique: str, P: int, scenario=None, h: float = 1e-4):
    return api.RunSpec(
        scheduling=api.SchedulingSpec(technique=technique),
        cluster=scenario or api.scenarios.baseline(P),
        execution=api.ExecutionSpec(h=h))


def _run(technique: str, P: int, N: int, t: float = 0.01, *,
         scenario=None, queue_cls=None, h: float = 1e-4):
    tt = np.full(N, t)
    kw = {} if queue_cls is None else dict(queue_cls=queue_cls)
    t0 = time.perf_counter()
    r = api.simulate(_spec(technique, P, scenario, h=h), tt, **kw)
    return r, time.perf_counter() - t0


# ------------------------------------------------------------ scale points
def scale_points(Ps=(64, 256, 1024, 4096), N=1 << 20, t=0.01):
    """T_par + scheduling cost for SS across system sizes (uniform
    tasks — the theory's workload)."""
    rows = []
    for P in Ps:
        r, wall = _run("SS", P, N, t)
        rows.append(dict(
            P=P, N=N, t_par=r.t_par, wall_s=round(wall, 4),
            assignments=r.n_assignments,
            events_per_s=round(r.n_assignments / max(wall, 1e-9)),
            t_ideal=N * t / P,
            efficiency=round(N * t / P / r.t_par, 4)))
    return rows


# ---------------------------------------------------------------- speedup
def speedup_point(P=1024, N=262144, t=0.01):
    """Array core vs the pure-Python reference core on the SAME SS run
    (identical schedules — the parity suite's guarantee).  The cheap
    side is best-of-3 (first call pays numpy warmup and container
    jitter); the expensive reference runs once."""
    fast, fast_s = _run("SS", P, N, t)
    for _ in range(2):
        _, again = _run("SS", P, N, t)
        fast_s = min(fast_s, again)
    ref, ref_s = _run("SS", P, N, t, queue_cls=refqueue.ReferenceQueue)
    assert fast.n_assignments == ref.n_assignments
    assert abs(fast.t_par - ref.t_par) < 1e-6 * ref.t_par
    return dict(P=P, N=N, fast_s=round(fast_s, 4), ref_s=round(ref_s, 4),
                speedup=round(ref_s / fast_s, 1), t_par=fast.t_par)


# --------------------------------------------------------- overhead trend
def overhead_points(Ps=(64, 256, 1024), N=1 << 18, t=0.01, seed=0):
    """Measured rDLB overhead under ONE mid-run fail-stop vs the paper's
    closed form: H_T ∝ (n+1)/(q−1), n = N/q — decreasing in P."""
    rows = []
    for P in Ps:
        base, _ = _run("SS", P, N, t)
        T = base.t_par
        sc = api.scenarios.failures(P, 1, t_exec_estimate=T, seed=seed)
        fail, _ = _run("SS", P, N, t, scenario=sc)
        lam = 1.0 / T                     # one expected failure per run
        rows.append(dict(
            P=P, N=N, t_base=T, t_fail=fail.t_par,
            overhead=fail.t_par / T - 1.0,
            theory_overhead=theory.rdlb_overhead(N // P, t, P, lam),
            duplicates=fail.n_duplicates))
    return rows


# -------------------------------------------------------------- sweep cost
def sweep_cost(P=1024, N=131072, seed=0):
    """One full adaptive portfolio sweep from a t=0 snapshot, timed in
    the in-loop configuration (default coarsening) and uncoarsened."""
    from repro.adaptive import DEFAULT_PORTFOLIO, capture, sweep
    from repro.core import dls, engine, rdlb, simulator
    tt = np.abs(np.random.default_rng(seed).normal(0.01, 0.003, N)) + 1e-4
    tech = dls.make_technique("FAC", N, P)
    queue = rdlb.RobustQueue(N, tech)
    eng = engine.Engine(
        queue, api.scenarios.pe_perturbation(P).engine_workers(),
        simulator.SimBackend(tt))
    snap = capture(eng, 0.0)
    t0 = time.perf_counter()
    sweep(snap, tt, DEFAULT_PORTFOLIO, max_sim_tasks=2048)
    in_loop = time.perf_counter() - t0
    t0 = time.perf_counter()
    sweep(snap, tt, DEFAULT_PORTFOLIO, max_sim_tasks=None)
    full_n = time.perf_counter() - t0
    return dict(P=P, N=N, candidates=len(DEFAULT_PORTFOLIO),
                in_loop_s=round(in_loop, 3), full_n_s=round(full_n, 3))


# ------------------------------------------------------- device batch sweep
def device_sweep_point(P=1024, N=1 << 17, B=1024, t=0.01, h=1e-6,
                       loop_sample=3):
    """One jit/vmap ``core.devicesim`` call simulating B (candidate x
    draw) homogeneous-regime runs vs the equivalent Python loop of
    fast-forward simulations.

    The batch cycles the four fixed-chunk techniques (the device
    portfolio) over B elements; the loop baseline times ``loop_sample``
    ``api.simulate`` calls per technique and extrapolates to B (running
    the full loop would take minutes — that is the point).  Parity of
    every technique's t_par against the scalar engine is asserted here,
    on top of the dedicated suite in tests/test_devicesim.py."""
    from repro.core import devicesim
    techniques = ("SS", "STATIC", "mFSC", "FSC")
    tt = np.full(N, t)
    lows, scalar_tp = [], []
    loop_per_sim = 0.0
    for tech in techniques:
        spec = _spec(tech, P, h=h)
        lo, why = devicesim.lower_run(spec, tt)
        assert lo is not None, f"{tech}: {why}"
        lows.append(lo)
        best = np.inf
        for _ in range(loop_sample):
            r, wall = _run(tech, P, N, t, h=h)
            best = min(best, wall)
        loop_per_sim += best / len(techniques)
        scalar_tp.append(r.t_par)
    tech_of = np.arange(B, dtype=np.int32) % len(techniques)
    t0 = time.perf_counter()
    res = devicesim.simulate_many(lows, tech_of=tech_of)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = devicesim.simulate_many(lows, tech_of=tech_of)
    warm_s = time.perf_counter() - t0
    assert res.valid.all(), "device path declined in its home regime"
    for u, tp in enumerate(scalar_tp):
        dev = res.t_par[tech_of == u]
        assert np.allclose(dev, tp, rtol=1e-12, atol=1e-9), \
            (techniques[u], dev[0], tp)
    loop_est_s = loop_per_sim * B
    return dict(P=P, N=N, batch=B, techniques=len(techniques),
                cold_s=round(cold_s, 3), warm_s=round(warm_s, 3),
                loop_per_sim_s=round(loop_per_sim, 4),
                loop_est_s=round(loop_est_s, 1),
                speedup_warm=round(loop_est_s / warm_s, 1),
                speedup_cold=round(loop_est_s / cold_s, 1))


# ------------------------------------------------------------------ driver
def run(quick: bool = True):
    if quick:
        points = scale_points(Ps=(64, 256, 1024), N=1 << 18)
        speed = speedup_point(P=256, N=32768)
        sweep = sweep_cost(P=256, N=32768)
        device = device_sweep_point(P=256, N=1 << 15, B=512)
    else:
        points = scale_points()
        speed = speedup_point()
        sweep = sweep_cost()
        device = device_sweep_point()
        assert speed["speedup"] >= 50.0, speed
        assert sweep["in_loop_s"] < 2.0, sweep
        # the tentpole acceptance: >=1000 batched runs at P=1024, >=10x
        # the equivalent Python loop
        assert device["speedup_warm"] >= 10.0, device
    overhead = overhead_points() if not quick else overhead_points(
        Ps=(16, 64), N=1 << 14)
    out = dict(scale_points=points, speedup=speed, overhead=overhead,
               sweep=sweep, device_sweep=device)
    common.write_csv("fig_scale",
                     ["P", "N", "t_par", "wall_s", "assignments",
                      "events_per_s", "efficiency"],
                     [(p["P"], p["N"], p["t_par"], p["wall_s"],
                       p["assignments"], p["events_per_s"],
                       p["efficiency"]) for p in points])
    common.ARTIFACTS.mkdir(parents=True, exist_ok=True)
    with open(common.ARTIFACTS / "BENCH_scale.json", "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    return out


def main(quick: bool = True):
    out = run(quick)
    lines = []
    for p in out["scale_points"]:
        lines.append(f"fig_scale,P={p['P']},N={p['N']},"
                     f"wall_s={p['wall_s']},t_par={p['t_par']:.3f},"
                     f"events_per_s={p['events_per_s']}")
    s = out["speedup"]
    lines.append(f"fig_scale,speedup,P={s['P']},N={s['N']},"
                 f"ref_s={s['ref_s']},fast_s={s['fast_s']},"
                 f"x={s['speedup']}")
    for o in out["overhead"]:
        lines.append(f"fig_scale,overhead,P={o['P']},"
                     f"measured={o['overhead']:.4f},"
                     f"theory={o['theory_overhead']:.4f}")
    w = out["sweep"]
    lines.append(f"fig_scale,sweep,P={w['P']},N={w['N']},"
                 f"in_loop_s={w['in_loop_s']},full_n_s={w['full_n_s']},"
                 f"under_2s={w['in_loop_s'] < 2.0}")
    d = out["device_sweep"]
    lines.append(f"fig_scale,device,P={d['P']},N={d['N']},B={d['batch']},"
                 f"warm_s={d['warm_s']},loop_est_s={d['loop_est_s']},"
                 f"x={d['speedup_warm']}")
    return lines


def dry_run():
    """CI smoke: tiny scale, still emits BENCH_scale.json."""
    points = scale_points(Ps=(16, 64), N=1 << 14)
    speed = speedup_point(P=32, N=8192)
    overhead = overhead_points(Ps=(8, 16), N=1 << 12)
    sweep = sweep_cost(P=64, N=8192)
    device = device_sweep_point(P=64, N=1 << 13, B=256, loop_sample=1)
    out = dict(scale_points=points, speedup=speed, overhead=overhead,
               sweep=sweep, device_sweep=device, dry_run=True)
    common.ARTIFACTS.mkdir(parents=True, exist_ok=True)
    with open(common.ARTIFACTS / "BENCH_scale.json", "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    assert speed["speedup"] > 1.0, speed
    assert overhead[0]["overhead"] > overhead[-1]["overhead"] - 0.05
    print(f"fig_scale,dry,speedup_x,{speed['speedup']}")
    print(f"fig_scale,dry,sweep_s,{sweep['in_loop_s']}")
    print(f"fig_scale,dry,device_x,{device['speedup_warm']}")
    print("fig_scale,dry,OK,1")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="fast smoke run (CI)")
    ap.add_argument("--paper", action="store_true",
                    help="full-scale points (P to 4096, N to 2^20)")
    args = ap.parse_args()
    if args.dry_run:
        dry_run()
    else:
        for line in main(quick=not args.paper):
            print(line)
