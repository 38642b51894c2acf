"""rDLB runtime executor tests: exactly-once gradients under failures,
hang reproduction, elastic continuation, straggler duplication, serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.data import batch_for_step
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.runtime import RDLBServeExecutor, RDLBTrainExecutor, Request
from repro.runtime.elastic import (rebalance_tasks, shrink_to_survivors)

CFG = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=256)


@pytest.fixture(scope="module")
def setup():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    batch = batch_for_step(CFG, 0, 16, 32)
    return model, params, batch


def run_step(model, params, batch, *, fault=None, rdlb=True,
             technique="FAC", n_workers=4, n_tasks=8):
    """One train step; ``fault`` maps worker id -> its WorkerSpec on the
    spec's cluster (the rest nominal)."""
    fault = fault or {}
    spec = api.train_spec(technique=technique, n_workers=n_workers,
                          n_tasks=n_tasks, rdlb_enabled=rdlb)
    spec = spec.override("cluster.workers", tuple(
        fault.get(w, api.WorkerSpec()) for w in range(n_workers)))
    ex = RDLBTrainExecutor(model, spec=spec, exact_accumulation=True)
    opt_state = ex.opt.init(params)
    res = ex.train_step(params, opt_state, batch)
    return ex, res


def fail_after(tasks):
    return {w: api.WorkerSpec(fail_after_tasks=n) for w, n in tasks.items()}


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def test_clean_step_updates_params(setup):
    model, params, batch = setup
    _, res = run_step(model, params, batch)
    assert not res.hung and np.isfinite(res.loss)
    assert not trees_equal(params, res.params)


@pytest.mark.parametrize("technique", ["SS", "FAC", "GSS", "AWF-B", "AF"])
def test_grads_identical_under_failures(setup, technique):
    """THE paper property, at gradient level: k fail-stop workers change
    NOTHING about the computed update (exactly-once, content-addressed
    re-execution)."""
    model, params, batch = setup
    _, clean = run_step(model, params, batch, technique=technique)
    _, faulty = run_step(model, params, batch, technique=technique,
                         fault=fail_after({1: 1, 3: 0}))
    assert not faulty.hung
    assert faulty.n_duplicates >= 1
    assert trees_equal(clean.params, faulty.params)
    assert clean.loss == pytest.approx(faulty.loss, abs=1e-9)


def test_w_minus_1_failures_tolerated(setup):
    model, params, batch = setup
    _, clean = run_step(model, params, batch)
    _, res = run_step(model, params, batch,
                      fault=fail_after({1: 0, 2: 0, 3: 0}))
    assert not res.hung and len(res.survivors) == 1
    assert trees_equal(clean.params, res.params)


def test_hang_without_rdlb(setup):
    model, params, batch = setup
    _, res = run_step(model, params, batch, rdlb=False,
                      fault=fail_after({1: 1}))
    assert res.hung


def test_no_failure_no_rdlb_is_fine(setup):
    model, params, batch = setup
    _, a = run_step(model, params, batch, rdlb=False)
    _, b = run_step(model, params, batch, rdlb=True)
    assert not a.hung and trees_equal(a.params, b.params)


def test_straggler_gets_duplicated(setup):
    model, params, batch = setup
    _, clean = run_step(model, params, batch)
    _, res = run_step(model, params, batch, technique="SS",
                      fault={0: api.WorkerSpec(speed=0.05)})
    assert not res.hung
    assert trees_equal(clean.params, res.params)


def test_elastic_shrink_and_rebalance(setup):
    model, params, batch = setup
    ex, res = run_step(model, params, batch,
                       fault=fail_after({2: 0}))
    st = shrink_to_survivors(ex)
    assert ex.n_workers == 3 and st.generation == 1
    n = rebalance_tasks(8, ex.n_workers, 16)
    assert 16 % n == 0 and n >= ex.n_workers


def test_rebalance_terminates_when_workers_exceed_batch():
    """Regression: global_batch=8, n_workers=12 used to loop forever
    (no n >= 12 divides 8); now clamps to one row per task."""
    assert rebalance_tasks(8, 12, 8) == 8
    assert rebalance_tasks(16, 12, 8) == 8
    # unchanged behaviour where the old code worked
    assert rebalance_tasks(8, 3, 16) == 8
    assert rebalance_tasks(5, 2, 16) == 8     # next divisor of 16 above 5
    assert rebalance_tasks(1, 1, 7) == 1
    with pytest.raises(ValueError):
        rebalance_tasks(4, 4, 0)


def test_shrink_carries_survivor_state(setup):
    """Regression: shrink used to rebuild fresh WorkerState for
    survivors, discarding observed speed and execution history that
    adaptive policies (and AWF-style weights) prime from."""
    model, params, batch = setup
    ex, res = run_step(model, params, batch,
                       fault={0: api.WorkerSpec(speed=0.5),
                              **fail_after({2: 0})})
    assert not res.hung
    before = {w.wid: (w.speed, w.tasks_done)
              for w in ex.workers if w.alive}
    st = shrink_to_survivors(ex)
    assert ex.n_workers == 3 and st.generation == 1
    renumbering = st.history[-1]["renumbering"]
    assert set(renumbering) == set(before)
    for old_wid, new_wid in renumbering.items():
        w = ex.workers[new_wid]
        assert w.wid == new_wid and w.alive
        assert (w.speed, w.tasks_done) == before[old_wid]
    assert any(w.tasks_done > 0 for w in ex.workers)
    assert any(w.speed == 0.5 for w in ex.workers)   # straggler observed


def test_wasted_work_accounting(setup):
    model, params, batch = setup
    _, res = run_step(model, params, batch, technique="SS", n_tasks=4,
                      fault={0: api.WorkerSpec(speed=0.01)})
    # duplicates may or may not land first; executed >= n_tasks
    executed = sum(res.tasks_by_worker.values())
    assert executed >= res.n_tasks


# ------------------------------------------------------------------ serve
def test_serve_failure_recovery():
    cfg = CFG.replace(vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 64, size=4).astype(np.int32),
                    max_new_tokens=2) for i in range(6)]
    ex = RDLBServeExecutor(model, params, spec=api.serve_spec(n_workers=3))
    stats = ex.serve(reqs, fail_at={1: 1})
    assert not stats.hung
    assert all(r.output is not None for r in reqs)
    # deterministic decode: duplicates produce identical tokens, so
    # results are valid regardless of which worker finished them
    ex2 = RDLBServeExecutor(model, params,
                            spec=api.serve_spec(n_workers=1))
    reqs2 = [Request(i, reqs[i].prompt, max_new_tokens=2) for i in range(6)]
    ex2.serve(reqs2)
    for a, b in zip(reqs, reqs2):
        assert np.array_equal(a.output, b.output)


def test_serve_hang_without_rdlb():
    cfg = CFG.replace(vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = [Request(i, np.arange(4, dtype=np.int32), max_new_tokens=1)
            for i in range(4)]
    ex = RDLBServeExecutor(model, params, spec=api.serve_spec(
        n_workers=2, rdlb_enabled=False))
    stats = ex.serve(reqs, fail_at={1: 0})
    assert stats.hung
