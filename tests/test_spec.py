"""Spec-layer tests: lossless JSON round-trips over every paper
scenario, the Table-1 scenarios as ClusterSpecs, the one-perturbation-
vocabulary ClusterSpec constructors, Candidate-as-spec-delta, the
spec-only executor constructors, core's independence of the api layer,
and the ``python -m repro`` CLI (a fig4 resilience data point from a
JSON file)."""

import ast
import dataclasses
import io
import json
import math
import warnings
from contextlib import redirect_stdout
from pathlib import Path

import numpy as np
import pytest

import repro.core
from repro import api
from repro.adaptive import capture, forecast_candidate
from repro.api import scenarios
from repro.core import dls, engine, rdlb, simulator


# ---------------------------------------------------------- round-trips
def spec_for_scenario(sc):
    return api.RunSpec(
        scheduling=api.SchedulingSpec(technique="AWF-B", seed=7,
                                      params=(("h", 1e-3),)),
        robustness=api.RobustnessSpec(max_duplicates=3,
                                      barrier_max_duplicates=None),
        cluster=sc,
        execution=api.ExecutionSpec(mode="threaded", h=1e-3,
                                    horizon=1e6, poll=2e-3),
        adaptive=api.AdaptiveSpec(
            enabled=True, hysteresis=0.1, max_sim_tasks=None,
            portfolio=(api.Candidate("GSS"),
                       api.Candidate("FAC", max_duplicates=2,
                                     overrides=(("execution.h", 5e-3),)))),
        n_tasks=96, name=f"paper/{sc.name}")


def test_roundtrip_identity_every_paper_scenario():
    """RunSpec -> to_dict -> JSON -> from_dict -> RunSpec is identity
    for every Table-1 scenario (the satellite acceptance)."""
    for name, sc in scenarios.paper_scenarios(
            16, t_exec_estimate=2.0, seed=5).items():
        spec = spec_for_scenario(sc)
        blob = json.dumps(spec.to_dict())
        back = api.RunSpec.from_dict(json.loads(blob))
        assert back == spec, name
        assert hash(back) == hash(spec), name
        assert back.to_dict() == spec.to_dict(), name
        # and through the convenience JSON path
        assert api.RunSpec.from_json(spec.to_json()) == spec, name


def test_roundtrip_preserves_inf_and_none():
    spec = api.RunSpec(
        robustness=api.RobustnessSpec(max_duplicates=None,
                                      barrier_max_duplicates=None),
        cluster=api.ClusterSpec(
            n_workers=2,
            workers=(api.WorkerSpec(fail_time=math.inf),
                     api.WorkerSpec(fail_after_tasks=0, alive=False))))
    assert api.RunSpec.from_json(spec.to_json()) == spec


def test_save_load(tmp_path):
    spec = spec_for_scenario(scenarios.baseline(4))
    path = tmp_path / "spec.json"
    spec.save(path)
    assert api.RunSpec.load(path) == spec


def test_override_paths_and_validation():
    spec = api.RunSpec()
    s2 = spec.override("scheduling.technique", "GSS") \
             .override("execution.h", 5e-3) \
             .override("robustness.max_duplicates", 4)
    assert s2.scheduling.technique == "GSS"
    assert s2.execution.h == 5e-3
    assert s2.robustness.max_duplicates == 4
    assert spec == api.RunSpec()               # frozen: original untouched
    with pytest.raises(AttributeError):
        spec.override("scheduling.nope", 1)
    with pytest.raises(ValueError):
        api.SchedulingSpec(technique="NOPE")
    with pytest.raises(ValueError):
        api.ExecutionSpec(mode="warp")
    with pytest.raises(ValueError):
        api.ClusterSpec(n_workers=2, workers=(api.WorkerSpec(),))


# ------------------------------------------- one perturbation vocabulary
TABLE1_P, TABLE1_T = 48, 2.0
# scenario -> (fail-stops, node 1's speed, node 1's msg_latency)
TABLE1 = {
    "baseline": (0, 1.0, 0.0),
    "fail_1": (1, 1.0, 0.0),
    "fail_half": (TABLE1_P // 2, 1.0, 0.0),
    "fail_pm1": (TABLE1_P - 1, 1.0, 0.0),
    "pe_perturb": (0, 0.25, 0.0),
    "latency_perturb": (0, 1.0, 10.0),
    "combined_perturb": (0, 0.25, 10.0),
}


@pytest.mark.parametrize("name", sorted(TABLE1))
def test_table1_scenario_is_cluster_spec(name):
    """Each Table-1 scenario is a ClusterSpec (named after it; the
    failure scenarios ``fail_<n>``) with one WorkerSpec per worker:
    victims die inside the run's window and never the master; node 1
    (PEs 16..31) alone carries the perturbation; the spec round-trips
    through JSON and drives the engine workers."""
    n_fail, speed, latency = TABLE1[name]
    sc = scenarios.paper_scenarios(TABLE1_P, t_exec_estimate=TABLE1_T,
                                   seed=3)[name]
    assert isinstance(sc, api.ClusterSpec)
    assert sc.name == (f"fail_{n_fail}" if n_fail else name)
    assert sc.n_workers == len(sc.workers) == TABLE1_P
    fail = [w.fail_time for w in sc.workers if w.fail_time is not None]
    assert len(fail) == n_fail
    assert all(0.05 * TABLE1_T <= t <= 0.95 * TABLE1_T for t in fail)
    assert sc.workers[0].fail_time is None               # master survives
    node1 = range(16, 32)
    for wid, w in enumerate(sc.workers):
        assert w.speed == (speed if wid in node1 else 1.0), wid
        assert w.msg_latency == (latency if wid in node1 else 0.0), wid
    blob = json.dumps(dataclasses.asdict(sc))
    assert api.ClusterSpec.from_dict(json.loads(blob)) == sc
    ws = sc.engine_workers()
    assert [w.wid for w in ws] == list(range(TABLE1_P))
    assert all(w.alive for w in ws)
    assert [w.fail_time for w in ws] == [w.fail_time for w in sc.workers]
    assert ws[16].speed == speed and ws[16].msg_latency == latency


def test_core_imports_no_api():
    """The layering holds: no module under repro/core imports repro.api
    (the API layer builds on core, never the reverse)."""
    offenders = []
    for path in sorted(Path(repro.core.__file__).parent.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module] + [f"{node.module}.{a.name}"
                                         for a in node.names]
            else:
                continue
            if any(n == "repro.api" or n.startswith("repro.api.")
                   for n in names):
                offenders.append(f"{path.name}:{node.lineno}")
    assert not offenders, offenders


def test_cluster_from_serve_maps_both_modes():
    """The serve vocabulary: dead -> alive=False; slow (extra s/request)
    -> speed divisor in virtual time AND sleep in threaded mode."""
    ws = api.ClusterSpec.from_serve(
        3, dead={2}, slow={1: 1.0}, fail_at={0: 5}).engine_workers()
    assert not ws[2].alive
    assert ws[1].speed == pytest.approx(0.5)
    assert ws[1].sleep_per_task == 1.0
    assert ws[0].fail_after_tasks == 5


def test_serve_slow_composes_with_declared_speed():
    """The slow overlay COMPOSES with a spec-declared straggler speed
    (1/(1/speed + extra)); it must never make a slow worker faster."""
    cluster = api.ClusterSpec(
        n_workers=1, workers=(api.WorkerSpec(speed=0.1,
                                             sleep_per_task=0.5),))
    w = cluster.with_serve_state(slow={0: 1.0}).workers[0]
    assert w.speed == pytest.approx(1.0 / 11.0)
    assert w.speed < 0.1
    assert w.sleep_per_task == pytest.approx(1.5)


def test_swap_technique_can_toggle_rdlb():
    """A candidate override of robustness.rdlb_enabled reaches the live
    queue via the controller's swap (not just the forecasts)."""
    from repro.adaptive import AdaptiveConfig, AdaptiveController
    q = rdlb.RobustQueue(16, dls.make_technique("SS", 16, 2),
                         rdlb_enabled=False)
    q.swap_technique(dls.make_technique("FAC", 16, 2))
    assert q.rdlb_enabled is False            # untouched by default
    eng = engine.Engine(q, [engine.EngineWorker(0),
                            engine.EngineWorker(1)],
                        engine.WorkerBackend())
    ctrl = AdaptiveController(config=AdaptiveConfig())
    cand = api.Candidate("GSS",
                         overrides=(("robustness.rdlb_enabled", True),))
    ctrl._swap(eng, cand, n_remaining=16)
    assert q.rdlb_enabled is True
    assert q.technique.name == "GSS"


def test_spec_declared_cluster_drives_executors():
    """Perturbations declared ON THE SPEC's cluster reach the engine
    workers."""
    spec = api.RunSpec(
        cluster=api.ClusterSpec(
            n_workers=3,
            workers=(api.WorkerSpec(), api.WorkerSpec(speed=0.5),
                     api.WorkerSpec(fail_after_tasks=1))),
        n_tasks=6)
    eng = api.build(spec, engine.WorkerBackend())
    assert eng.workers[1].speed == 0.5
    assert eng.workers[2].fail_after_tasks == 1
    st = eng.run()
    assert not st.hung and eng.queue.done


def test_from_worker_states_keeps_spec_profile():
    """Live WorkerState overlays its originating WorkerSpec, so
    spec-declared fail_time / msg_latency / sleep_per_task survive the
    per-step cluster rebuild in the training executor."""
    from repro.runtime import WorkerState
    prof = api.WorkerSpec(msg_latency=0.1, fail_time=2.0,
                          sleep_per_task=0.01)
    ws = WorkerState(0, speed=0.5, profile=prof)
    w = api.ClusterSpec.from_worker_states([ws]).workers[0]
    assert w.msg_latency == 0.1 and w.fail_time == 2.0
    assert w.sleep_per_task == 0.01
    assert w.speed == 0.5 and w.alive            # live fields win


def test_train_executor_honors_spec_declared_faults():
    """Fail-stops and stragglers declared on the spec's cluster inject
    real failures into training — and the update is still
    exactly-once-identical to a clean run."""
    pytest.importorskip("jax")
    import jax
    from repro.data import batch_for_step
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.runtime import RDLBTrainExecutor
    cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = batch_for_step(cfg, 0, 8, 16)

    def step(spec):
        ex = RDLBTrainExecutor(model, spec=spec,
                               exact_accumulation=True)
        return ex.train_step(params, ex.opt.init(params), batch)

    base = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="SS"),
        cluster=api.ClusterSpec(n_workers=3, name="train"),
        execution=api.ExecutionSpec(h=0.0, horizon=100000.0), n_tasks=8)
    faulty_cluster = api.ClusterSpec(
        n_workers=3, name="train",
        workers=(api.WorkerSpec(), api.WorkerSpec(fail_after_tasks=1),
                 api.WorkerSpec(speed=0.25)))
    clean = step(base)
    faulty = step(base.replace(cluster=faulty_cluster))
    assert not clean.hung and not faulty.hung
    assert faulty.n_duplicates >= 1
    assert faulty.survivors == [0, 2]
    leaves = zip(jax.tree_util.tree_leaves(clean.params),
                 jax.tree_util.tree_leaves(faulty.params))
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in leaves)


# -------------------------------------------------- candidate = spec delta
def test_candidate_apply_sets_technique_and_knobs():
    base = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        robustness=api.RobustnessSpec(max_duplicates=7,
                                      barrier_max_duplicates=None))
    out = api.Candidate("GSS", max_duplicates=2).apply(base)
    assert out.scheduling.technique == "GSS"
    assert out.robustness.max_duplicates == 2
    assert out.robustness.barrier_max_duplicates is None   # KEEP
    # technique=None keeps the incumbent technique
    keep = api.Candidate(max_duplicates=4).apply(base)
    assert keep.scheduling.technique == "FAC"
    assert keep.robustness.max_duplicates == 4
    # unset fields are DELTAS: they keep the incumbent's knobs
    stay = api.Candidate("GSS").apply(base)
    assert stay.robustness.max_duplicates == 7
    assert stay.robustness.barrier_max_duplicates is None
    # ... including for pure-override candidates
    pure = api.Candidate(overrides=(("execution.h", 5e-3),)).apply(base)
    assert pure.scheduling.technique == "FAC"
    assert pure.robustness.max_duplicates == 7
    assert pure.execution.h == 5e-3


def test_candidate_overrides_explore_any_field():
    base = api.RunSpec()
    c = api.Candidate("SS", overrides=(("execution.h", 5e-3),
                                       ("robustness.rdlb_enabled", False)))
    out = c.apply(base)
    assert out.execution.h == 5e-3
    assert not out.robustness.rdlb_enabled
    assert "execution.h=0.005" in c.label
    # hashable (the controller dict()s over candidates) + JSON round-trip
    assert hash(c) == hash(api.Candidate.from_dict(
        json.loads(json.dumps(dataclasses_asdict(c)))))


def dataclasses_asdict(c):
    import dataclasses
    return dataclasses.asdict(c)


def test_forecast_sweep_explores_non_dup_fields():
    """A portfolio candidate overriding a NON-(technique × dup) field
    changes the forecast — the sweep explores the whole spec space."""
    tt = np.full(128, 0.01)
    tech = dls.make_technique("SS", 128, 4)
    queue = rdlb.RobustQueue(128, tech)
    eng = engine.Engine(queue, scenarios.baseline(4).engine_workers(),
                        simulator.SimBackend(tt), h=1e-4)
    snap = capture(eng, 0.0)
    lo = forecast_candidate(snap, tt, api.Candidate("SS"), h=1e-4)
    hi = forecast_candidate(
        snap, tt, api.Candidate("SS", overrides=(("execution.h", 5e-3),)),
        h=1e-4)
    assert math.isfinite(lo) and math.isfinite(hi)
    assert hi > lo * 2       # SS pays P*N master overhead: h dominates


# --------------------------------------------------- deprecation shims
def test_simulate_legacy_accepts_custom_technique_objects():
    """api.simulate drives a prebuilt Technique subclass with an
    unregistered name (``technique=``; custom wiring is a supported
    seam)."""
    class MyTech(dls.SS):
        name = "MY_CUSTOM"
    r = api.simulate(api.RunSpec(cluster=scenarios.baseline(2)),
                     np.ones(8), technique=MyTech(8, 2))
    assert not r.hang and r.n_finished == 8
    assert r.technique == "MY_CUSTOM"


def test_snapshot_carries_rdlb_switch():
    """Forecasts of a non-robust run must simulate the non-robust queue
    (rdlb_enabled travels through the snapshot into the base spec)."""
    from repro.adaptive.forecaster import base_spec_from_snapshot
    tt = np.ones(16)
    tech = dls.make_technique("SS", 16, 2)
    queue = rdlb.RobustQueue(16, tech, rdlb_enabled=False)
    eng = engine.Engine(queue, scenarios.baseline(2).engine_workers(),
                        simulator.SimBackend(tt))
    snap = capture(eng, 0.0)
    assert snap.rdlb_enabled is False
    assert not base_spec_from_snapshot(snap).robustness.rdlb_enabled


def test_executor_ctor_legacy_warns():
    """Both executors are configured by a RunSpec alone: the old
    keywords, and a missing ``spec=``, raise TypeError."""
    pytest.importorskip("jax")
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.runtime import RDLBServeExecutor, RDLBTrainExecutor
    cfg = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=64)
    model = build_model(cfg)
    params = model.init(__import__("jax").random.PRNGKey(0))
    train = api.train_spec(technique="GSS", n_workers=2, n_tasks=4)
    serve = api.serve_spec(technique="GSS", n_workers=3)
    old = (dict(n_workers=2), dict(n_tasks=4), dict(technique="SS"),
           dict(rdlb_enabled=False), dict(max_duplicates=2),
           dict(concurrent=True))
    for kw in old:
        with pytest.raises(TypeError):
            RDLBTrainExecutor(model, spec=train, **kw)
        with pytest.raises(TypeError):
            RDLBServeExecutor(model, params, spec=serve, **kw)
    with pytest.raises(TypeError):
        RDLBTrainExecutor(model)
    with pytest.raises(TypeError):
        RDLBServeExecutor(model, params)
    # the spec path builds cleanly, with no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        assert RDLBTrainExecutor(model, spec=train).spec is train
        assert RDLBServeExecutor(model, params, spec=serve).spec is serve


# ------------------------------------------------------ adaptive via spec
def test_spec_enables_adaptive_controller():
    tt = np.full(256, 0.01)
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        cluster=scenarios.pe_perturbation(8, node_size=4),
        adaptive=api.AdaptiveSpec(
            enabled=True, decision_every_chunks=16, min_remaining=16,
            max_sim_tasks=None,
            portfolio=(api.Candidate("FAC"), api.Candidate("GSS"),
                       api.Candidate("mFSC"))))
    r = api.simulate(spec, tt)
    assert not r.hang and r.n_finished == 256
    assert r.adaptive_decisions            # at least the t=0 plan
    assert all(d.chosen in d.predictions for d in r.adaptive_decisions)


# ----------------------------------------------------------------- CLI
def test_cli_reproduces_fig4_resilience_point(tmp_path, capsys):
    """`python -m repro run --spec <json>` reproduces a fig4 resilience
    data point: CLI rho_res == robustness.resilience over direct
    api.simulate runs of the same grid."""
    from benchmarks import fig4_resilience
    from repro.api import cli
    from repro.core import robustness

    tt = np.full(128, 0.01)
    techniques = ["SS", "FAC", "GSS"]
    path = tmp_path / "fig4_small.json"
    fig4_resilience.emit_spec(
        path, P=6, scenario="fail_1", techniques=techniques,
        task_times=tt, workload={"kind": "uniform", "n": 128, "t": 0.01})

    # direct computation over the same declarative grid
    _, entries, metric, baseline = cli.load_run_file(str(path))
    assert metric == "resilience" and baseline == "baseline"
    t_par = {name: api.simulate(spec, tt).t_par for name, spec in entries}
    rho = robustness.resilience(
        {t: t_par[f"fail_1/{t}"] for t in techniques},
        {t: t_par[f"baseline/{t}"] for t in techniques})

    assert cli.main(["run", "--spec", str(path)]) == 0
    out = capsys.readouterr().out
    got = {}
    for line in out.splitlines():
        if line.startswith("resilience,fail_1,"):
            _, _, tech, val = line.split(",")
            got[tech] = float(val)
    assert set(got) == set(techniques)
    for t in techniques:
        assert got[t] == pytest.approx(rho[t], abs=1e-4)
    # the most robust technique maps to 1.0 (FePIA normalization)
    assert min(got.values()) == pytest.approx(1.0)


def test_cli_dry_run_and_show(tmp_path, capsys):
    from repro.api import cli
    spec = api.RunSpec(cluster=api.ClusterSpec(n_workers=2, name="t"))
    doc = {"workload": {"kind": "uniform", "n": 16, "t": 1.0},
           "spec": spec.to_dict()}
    path = tmp_path / "one.json"
    path.write_text(json.dumps(doc))
    assert cli.main(["run", "--spec", str(path), "--dry-run"]) == 0
    out = capsys.readouterr().out
    assert "dryrun" in out and "ok" in out
    assert cli.main(["show", "--spec", str(path)]) == 0
    assert "workload: 16 tasks" in capsys.readouterr().out
