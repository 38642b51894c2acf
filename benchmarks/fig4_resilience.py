"""Fig. 4 reproduction: FePIA resilience of DLS techniques (with rDLB)
under 1, P/2 and P-1 failures, relative to the most robust technique.

Reads fig3 CSVs (runs fig3 if missing); writes fig4_<app>.csv:
    scenario, technique, rho_res   (1.0 = most robust, lower is better)

The whole grid is also expressible as DATA: ``--emit-spec`` writes the
(technique × {baseline, failure-scenario}) grid as a JSON RunSpec sweep,
and ``python -m repro run --spec artifacts/bench/fig4_<scen>_<app>.spec.json``
reproduces the ρ_res data points (seed-0 scenario instance) without any
benchmark code.
"""

from __future__ import annotations

import csv
import dataclasses
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):        # `python benchmarks/fig4_resilience.py`
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks import common
from repro import api
from repro.core import robustness


def load_fig3(app: str):
    path = common.ARTIFACTS / f"fig3_{app}.csv"
    if not path.exists():
        from benchmarks import fig3_performance
        fig3_performance.run()
    rows = list(csv.DictReader(open(path)))
    return {(r["technique"], r["scenario"], int(r["rdlb"])):
            float(r["t_par"]) for r in rows}


def run():
    out = {}
    for app in ("psia", "mandelbrot"):
        by = load_fig3(app)
        rows = []
        for scen in ("fail_1", "fail_half", "fail_pm1"):
            tb, tf = {}, {}
            for tech in common.TECHNIQUES:
                if tech == "STATIC":
                    continue
                tb[tech] = by[(tech, "baseline", 1)]
                tf[tech] = by[(tech, scen, 1)]
            rho = robustness.resilience(tf, tb)
            rows += [(scen, t, rho[t]) for t in rho]
        common.write_csv(f"fig4_{app}", ["scenario", "technique",
                                         "rho_res"], rows)
        out[app] = rows
    return out


def emit_spec(out=None, *, app: str = "psia", scenario: str = "fail_1",
              quick: bool = True, P: int = None, techniques=None,
              seed: int = 0, task_times=None, workload: dict = None,
              h: float = 1e-4) -> Path:
    """Write one fig4 data-point grid as a JSON RunSpec sweep.

    The file pairs every technique's baseline run with its run under
    ``scenario``; ``python -m repro run --spec <file>`` then computes the
    same FePIA ρ_res this module derives from fig3 CSVs (for the seed-0
    scenario instance).  ``task_times``/``workload``/``P`` allow
    small-scale grids (used by the tier-1 CLI test).
    """
    P = P or common.P
    if task_times is None:
        by_app = dict(common.apps(quick))
        task_times = by_app[app]
        workload = {"kind": app,
                    "n": None if app == "psia" else len(task_times)}
    assert workload is not None, "explicit task_times need a workload dict"
    techniques = list(techniques or
                      (t for t in common.TECHNIQUES if t != "STATIC"))
    base_sc = api.scenarios.baseline(P)
    t_est = api.simulate(common.spec_for("FAC", base_sc, h=h),
                         task_times).t_par
    scenarios = api.scenarios.paper_scenarios(P, t_exec_estimate=t_est,
                                              seed=seed)
    sweep = []
    for scen in ("baseline", scenario):
        cluster = dataclasses.asdict(scenarios[scen])
        for tech in techniques:
            sweep.append({
                "name": f"{scen}/{tech}",
                "overrides": {"scheduling.technique": tech,
                              "cluster": cluster}})
    doc = {
        "workload": workload,
        "spec": common.spec_for("FAC", base_sc, seed=seed, h=h).to_dict(),
        "sweep": sweep,
        "metric": "resilience",
        "baseline_scenario": "baseline",
    }
    if out is None:
        common.ARTIFACTS.mkdir(parents=True, exist_ok=True)
        out = common.ARTIFACTS / f"fig4_{scenario}_{app}.spec.json"
    out = Path(out)
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return out


# --------------------------------------------------------- Monte-Carlo mode
def _draw_failures(rng, P, k, t_est, draws):
    """``draws`` i.i.d. instances of ``api.scenarios.failures(P, k, ...)``
    as a [draws, P] fail-time matrix (inf = survives): k distinct victims from
    1..P-1 (the master never fails), times uniform over the paper's
    "arbitrary during execution" window."""
    keys = rng.random((draws, P - 1))
    victims = np.argpartition(keys, min(k, P - 2), axis=1)[:, :k] + 1
    times = rng.uniform(0.05 * t_est, 0.95 * t_est, size=(draws, k))
    fail = np.full((draws, P), np.inf)
    np.put_along_axis(fail, victims, times, axis=1)
    return fail


def _rho_per_draw(t_fail, t_base):
    """Vectorized ``robustness.resilience`` over paired draws.

    t_fail: [T, D] per-technique failure-run times; t_base: [T].
    Returns rho [T, D] (inf where a run hung)."""
    radii = np.where(np.isinf(t_fail), np.inf,
                     np.maximum(0.0, t_fail - t_base[:, None]))
    r_min = radii.min(axis=0)                       # paired: per draw
    floor = np.maximum(r_min, 1e-9)
    with np.errstate(invalid="ignore"):
        rho = np.where(r_min <= 1e-9,
                       np.where(radii <= 1e-9, 1.0, radii / floor),
                       radii / np.where(np.isinf(r_min), 1.0, r_min))
    return np.where(np.isinf(radii), np.inf, rho)


def monte_carlo(*, P: int = 32, n_tasks: int = 256, t_task: float = 0.01,
                draws: int = 10_000, cells=None, h: float = 1e-4,
                seed: int = 0, techniques=("SS", "mFSC", "FSC")):
    """ρ_res as a DISTRIBUTION: ``draws`` failure instances per cell.

    Figure 4 proper scores ONE seed-0 instance of each failure scenario.
    This mode re-draws the scenario (victims AND fail times) ``draws``
    times per cell k ∈ {1, P/2, P-1} and reports the mean ρ_res with a
    95% normal CI — feasible only because every draw is one element of a
    batched ``core.devicesim`` call (a 10^4-draw cell is one jit/vmap
    call, not 10^4 event-loop runs).  Draws are PAIRED across techniques
    (same victims/times), matching the paper's shared-scenario design and
    shrinking the CI.  Elements the device path declines (``valid=False``)
    are re-run on the scalar engine, so every draw is exact.

    Returns (rows, lines): CSV rows [(k, technique, draws, rho_mean,
    rho_ci95, frac_hung, t_base, device_frac)] and printable summaries.
    """
    times = np.full(n_tasks, float(t_task))
    if cells is None:
        cells = (1, P // 2, P - 1)
    from repro.core import devicesim
    base_sc = api.scenarios.baseline(P)
    specs = {t: common.spec_for(t, base_sc, rdlb=1, seed=seed, h=h)
             for t in techniques}
    lows = []
    for t in techniques:
        lo, why = devicesim.lower_run(specs[t], times)
        assert lo is not None, f"{t}: {why}"
        lows.append(lo)
    nt = len(techniques)
    base = devicesim.simulate_many(lows)
    assert base.valid.all()
    t_base = base.t_par                              # [nt]
    t_est = float(t_base.max())
    rows, lines = [], []
    t0 = time.perf_counter()
    for ci, k in enumerate(cells):
        rng = np.random.default_rng([seed, k])
        fail = _draw_failures(rng, P, k, t_est, draws)
        res = devicesim.simulate_many(
            lows, tech_of=np.repeat(np.arange(nt, dtype=np.int32), draws),
            fail_times=np.tile(fail, (nt, 1)))
        t_fail = np.where(res.hung, np.inf, res.t_par)
        # exactness: budget-exhausted elements re-run on the scalar engine
        bad = np.flatnonzero(~res.valid)
        for b in bad:
            t_ix, d = divmod(int(b), draws)
            workers = tuple(
                api.WorkerSpec(fail_time=None if np.isinf(f) else float(f))
                for f in fail[d])
            sp = dataclasses.replace(
                specs[techniques[t_ix]],
                cluster=api.ClusterSpec(n_workers=P, workers=workers,
                                        name=f"mc_{k}_{d}"))
            t_fail[b] = api.simulate(sp, times).t_par
        rho = _rho_per_draw(t_fail.reshape(nt, draws), t_base)
        for t_ix, tech in enumerate(techniques):
            r = rho[t_ix]
            fin = r[np.isfinite(r)]
            mean = float(fin.mean()) if len(fin) == len(r) else np.inf
            ci95 = (1.96 * float(fin.std(ddof=1)) / np.sqrt(len(fin))
                    if len(fin) > 1 else 0.0)
            hungf = 1.0 - len(fin) / len(r)
            devf = 1.0 - len(bad) / (nt * draws)
            rows.append((k, tech, draws, mean, ci95, hungf,
                         float(t_base[t_ix]), devf))
            lines.append(f"fig4mc,P={P},k={k},{tech},"
                         f"rho={mean:.3f}+-{ci95:.3f},hung={hungf:.3f}")
    lines.append(f"fig4mc,elapsed={time.perf_counter() - t0:.1f}s,"
                 f"draws_per_cell={draws}")
    common.write_csv("fig4_mc", ["k", "technique", "draws", "rho_mean",
                                 "rho_ci95", "frac_hung", "t_base",
                                 "device_frac"], rows)
    return rows, lines


def main(quick: bool = True):
    out_rows = run()
    lines = []
    for app, rows in out_rows.items():
        for scen in ("fail_1", "fail_half", "fail_pm1"):
            sub = {t: r for s, t, r in rows if s == scen}
            best = min(sub, key=sub.get)
            worst = max(sub, key=sub.get)
            lines.append(f"fig4,{app},{scen},best={best},"
                         f"worst={worst}:{sub[worst]:.1f}x")
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-spec", action="store_true",
                    help="write the fig4 grid as a JSON RunSpec sweep "
                         "instead of running the benchmark")
    ap.add_argument("--monte-carlo", action="store_true",
                    help="device-batched rho_res distribution: --draws "
                         "failure instances per cell k in {1, P/2, P-1}")
    ap.add_argument("--draws", type=int, default=10_000)
    ap.add_argument("--P", type=int, default=32)
    ap.add_argument("--app", default="psia",
                    choices=("psia", "mandelbrot"))
    ap.add_argument("--scenario", default="fail_1")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.emit_spec:
        path = emit_spec(args.out, app=args.app, scenario=args.scenario)
        print(f"fig4,spec,{path}")
    elif args.monte_carlo:
        _, mc_lines = monte_carlo(P=args.P, draws=args.draws)
        for line in mc_lines:
            print(line)
    else:
        for line in main():
            print(line)
