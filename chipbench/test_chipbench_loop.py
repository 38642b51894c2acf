"""The loop driver end to end at a tiny size on the CPU, through the
harness's functions (not the command), and the traffic generator."""

import json

import numpy as np
import pytest

from chipbench import harness, traffic

E2E = {"loop_s", "setup_s"}
LAYER_CPU = {"compiles_in_window", "dup_task_share", "commit_s_per_loop"}
CELLS = ["psia-fac-1survivor", "mandelbrot-ss", "psia-fac",
         "mandelbrot-fac-slow1"]


def _run(root, cell, *, trace=False, seed=2**31 + 11, seconds=0.2,
         **kw):
    c = harness.load_cell(root, cell)
    return harness.run_cell(c, seed=seed, seconds=seconds, trace=trace,
                            t_start=0.0, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_tiny_size(tiny_root, cell):
    out = _run(tiny_root, cell)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert E2E <= set(out["metrics"])
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"loops_failed", "loops_differing",
                                  "tasks_off"}
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1
    json.dumps(out)


def test_p95_only_where_the_manifest_lists_it(tiny_root):
    ss = _run(tiny_root, "mandelbrot-ss")
    fac = _run(tiny_root, "mandelbrot-fac-slow1")
    assert "loop_s_p95" in ss["metrics"]
    assert "loop_s_p95" not in fac["metrics"]


def test_traced_run_reports_per_layer_metrics(tiny_root):
    out = _run(tiny_root, "psia-fac-1survivor", trace=True)
    assert out["correct"] is True
    # the CPU has no device plane: device metrics are left out, not 0
    assert set(out["metrics"]) == LAYER_CPU
    assert out["metrics"]["compiles_in_window"]["value"] == 0
    assert out["device"]["busy_s"] == 0.0
    assert out["device"]["window_s"] > 0
    bd = out["breakdown"]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_fail_stop_reissues_work(tiny_root):
    out = _run(tiny_root, "psia-fac-1survivor", trace=True)
    share = out["metrics"]["dup_task_share"]["value"]
    assert 5.0 < share < 100.0


def test_loop_record_counts_executed_chunks(tiny_root):
    cell = harness.load_cell(tiny_root, "psia-fac")
    fn, prepare = cell.module("apps", "psia").bind(cell.cfg)
    prepare()
    plan = traffic.Plan(cell.mix, cell.cfg, 1)
    rec, rows = harness.run_loop(cell.cfg, plan, fn, plan.workers())
    assert not rec.hung and rec.n_finished == cell.cfg["n_tasks"]
    covered = np.zeros(cell.cfg["n_tasks"], bool)
    for start, stop, _ in rec.calls:
        covered[start:stop] = True
    assert covered.all()
    assert rows.shape == (64, 64, 64)


def test_plan_is_fixed_by_the_seed():
    mix = {"technique": "FAC", "perturb": [
        {"count": 3, "worker": {"fail_after_tasks": {"times_n_over_p": 0.5}}}]}
    cfg = {"n_tasks": 20000, "P": 4}
    a = traffic.Plan(mix, cfg, 2**33 + 5)
    b = traffic.Plan(mix, cfg, 2**33 + 5)
    draws_a = [a.workers() for _ in range(20)]
    assert draws_a == [b.workers() for _ in range(20)]
    for ws in draws_a:
        assert len(ws) == 4
        failing = [w for w in ws if w]
        assert failing == [{"fail_after_tasks": 2500}] * 3
    survivors = {next(i for i, w in enumerate(ws) if not w)
                 for ws in draws_a}
    assert len(survivors) > 1       # the seed moves the survivor


def test_all_but_counts_from_p():
    mix = {"technique": "FAC", "perturb": [
        {"count": {"all_but": 1},
         "worker": {"fail_after_tasks": {"times_n_over_p": 0.5}}}]}
    plan = traffic.Plan(mix, {"n_tasks": 20000, "P": 256}, 2**31 + 3)
    ws = plan.workers()
    assert len(ws) == 256
    assert sum(1 for w in ws if not w) == 1
    assert [w for w in ws if w] == [{"fail_after_tasks": 39}] * 255


def test_plan_takes_p_from_the_config():
    plan = traffic.Plan({"technique": "SS"}, {"n_tasks": 64, "P": 16}, 0)
    assert plan.P == 16 and len(plan.workers()) == 16
    assert plan.workers() == [{}] * 16


@pytest.mark.parametrize("mix, err", [
    ({"technique": "SS", "perturb": [{"count": 1, "worker": {"nap": 1}}]},
     "WorkerSpec"),
    ({"technique": "SS", "perturb": [{"count": 5, "worker": {}}]},
     "more perturbed"),
    ({"technique": "SS", "perturb": [
        {"count": 1, "worker": {"speed": {"times_n": 1}}}]}, "expression"),
])
def test_plan_refuses_bad_mixes(mix, err):
    with pytest.raises(ValueError, match=err):
        traffic.Plan(mix, {"n_tasks": 8, "P": 4}, 0)


def test_negative_seed_is_accepted():
    plan = traffic.Plan({"technique": "SS"}, {"n_tasks": 8, "P": 4}, -3)
    assert len(plan.workers()) == 4


def test_trace_covers_the_windows_first_seconds(tiny_root, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.05)
    out = _run(tiny_root, "mandelbrot-ss", trace=True, seconds=0.5)
    assert out["attempted"] >= 3
    window = out["device"]["window_s"]
    assert 0.05 <= window < 0.5
    assert set(out["metrics"]) == LAYER_CPU
