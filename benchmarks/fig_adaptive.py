"""Adaptive scheduling benchmark: simulation-in-the-loop selection vs
every static portfolio candidate vs the per-scenario oracle, under the
Table-1 perturbation scenarios at P=256 (PSIA + Mandelbrot).

Writes fig_adaptive_<app>.csv:
    scenario, variant, t_par, n_duplicates, wasted_tasks, decisions, swaps
and reports (a) adaptive-vs-oracle / adaptive-vs-worst ratios and (b) the
wall-clock cost of ONE full portfolio sweep at a decision point for
P=256, N=8192 — the forecast must stay cheap enough to run in-loop
(acceptance: < 1 s on this container).

    PYTHONPATH=src python benchmarks/fig_adaptive.py            # full
    PYTHONPATH=src python benchmarks/fig_adaptive.py --dry-run  # smoke
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

if __package__ in (None, ""):           # `python benchmarks/fig_adaptive.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks import common
from repro import api
from repro.adaptive import (AdaptiveConfig, AdaptiveController, Candidate,
                            DEFAULT_PORTFOLIO, capture, run_adaptive,
                            run_static, sweep)
from repro.core import dls, engine, rdlb, simulator

PERTURB = ("pe_perturb", "latency_perturb", "combined_perturb")


def sweep_cost(P: int = 256, N: int = 8192, *,
               max_sim_tasks: int = 2048,
               portfolio=DEFAULT_PORTFOLIO, seed: int = 0):
    """Time one full portfolio sweep at a t=0 decision point (the
    acceptance bound: < 1 s at P=256, N=8192)."""
    tt = np.abs(np.random.default_rng(seed).normal(0.01, 0.003, N)) + 1e-4
    tech = dls.make_technique("FAC", N, P)
    queue = rdlb.RobustQueue(N, tech)
    eng = engine.Engine(
        queue, api.scenarios.pe_perturbation(P).engine_workers(),
        simulator.SimBackend(tt))
    snap = capture(eng, 0.0)
    t0 = time.time()
    preds = sweep(snap, tt, portfolio, max_sim_tasks=max_sim_tasks)
    return time.time() - t0, preds


def bench_app(app_name: str, tt, scenarios: dict, *,
              portfolio=DEFAULT_PORTFOLIO, h: float = 1e-4,
              max_sim_tasks: int = 2048):
    rows, summary = [], {}
    for scen_name in PERTURB:
        sc = scenarios[scen_name]
        statics = {}
        for cand in portfolio:
            st = run_static(tt, sc, cand, h=h)
            statics[cand.label] = st.t_par
            rows.append((scen_name, cand.label, st.t_par,
                         st.n_duplicates, st.wasted_tasks, 0, 0))
        cfg = AdaptiveConfig(portfolio=portfolio,
                             max_sim_tasks=max_sim_tasks)
        res, ctrl = run_adaptive(tt, sc, initial="FAC", config=cfg, h=h)
        swaps = sum(d.swapped for d in ctrl.decisions)
        rows.append((scen_name, "adaptive", res.t_par, res.n_duplicates,
                     res.wasted_tasks, len(ctrl.decisions), swaps))
        finite = [t for t in statics.values() if np.isfinite(t)]
        summary[scen_name] = dict(
            adaptive=res.t_par, oracle=min(finite), worst=max(finite),
            swaps=swaps,
            chosen=[d.chosen for d in ctrl.decisions])
    common.write_csv(f"fig_adaptive_{app_name}",
                     ["scenario", "variant", "t_par", "n_duplicates",
                      "wasted_tasks", "decisions", "swaps"], rows)
    return rows, summary


def run(quick: bool = True, *, portfolio=DEFAULT_PORTFOLIO):
    out = {}
    for app_name, tt in common.apps(quick):
        scenarios = common.scenarios(1.0)
        out[app_name] = bench_app(app_name, tt, scenarios,
                                  portfolio=portfolio)
    return out


def main(quick: bool = True):
    lines = []
    for app, (_, summary) in run(quick).items():
        for scen, s in summary.items():
            lines.append(
                f"fig_adaptive,{app},{scen},"
                f"adaptive_over_oracle={s['adaptive'] / s['oracle']:.3f},"
                f"adaptive_over_worst={s['adaptive'] / s['worst']:.3f},"
                f"swaps={s['swaps']}")
    dt, _ = sweep_cost()
    lines.append(f"fig_adaptive,sweep,P256_N8192_s,{dt:.3f},"
                 f"under_1s={dt < 1.0}")
    return lines


def dry_run():
    """Fast CI smoke: tiny scale, one scenario, plus a sweep timing."""
    P, N = 16, 512
    tt = np.abs(np.random.default_rng(0).normal(0.01, 0.004, N)) + 1e-4
    sc = api.scenarios.pe_perturbation(P, node_size=4)
    portfolio = (Candidate("FAC"), Candidate("GSS"), Candidate("mFSC"))
    statics = {c.label: run_static(tt, sc, c).t_par
               for c in portfolio}
    cfg = AdaptiveConfig(portfolio=portfolio, decision_every_chunks=32,
                         min_remaining=16, max_sim_tasks=None)
    res, ctrl = run_adaptive(tt, sc, initial="FAC", config=cfg)
    assert not res.hang, "adaptive dry-run hung"
    worst = max(statics.values())
    assert res.t_par <= worst * 1.001, (res.t_par, statics)
    print(f"fig_adaptive,dry,adaptive_t_par,{res.t_par:.4f}")
    print(f"fig_adaptive,dry,oracle_t_par,{min(statics.values()):.4f}")
    print(f"fig_adaptive,dry,decisions,{len(ctrl.decisions)}")
    dt, _ = sweep_cost(P=32, N=1024, max_sim_tasks=512,
                       portfolio=portfolio)
    print(f"fig_adaptive,dry,sweep_s,{dt:.3f}")
    print("fig_adaptive,dry,OK,1")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="fast smoke run (CI)")
    ap.add_argument("--paper", action="store_true",
                    help="full-scale Mandelbrot task count")
    args = ap.parse_args()
    if args.dry_run:
        dry_run()
    else:
        for line in main(quick=not args.paper):
            print(line)
