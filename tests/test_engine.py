"""Unified-engine tests: sim/exec parity, schedule-invariant gradients,
batched-decode equivalence, threaded concurrency, and the duplicate-count
bookkeeping regression."""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import api
from repro.api import scenarios
from repro.core import dls, engine, rdlb, simulator
from repro.data import batch_for_step
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.runtime import RDLBServeExecutor, RDLBTrainExecutor, Request
from repro.runtime.backends import ChunkBackend, FnBackend, TrainBackend

CFG = ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=128)


def chunk_key(c):
    return (c.start, c.size, c.pe, c.seq, c.duplicate, c.origin_seq)


# --------------------------------------------------------- sim/exec parity
@pytest.mark.parametrize("technique", ["SS", "FAC", "GSS", "AWF-B", "AF"])
def test_sim_and_exec_backends_identical_schedule(technique):
    """THE SimAS property: the simulator and a really-executing backend
    drive the same engine loop, so the same (technique, scenario, seed)
    produces the same assignment log, event for event — even under a
    straggler + fail-stop scenario."""
    N, P = 64, 4
    tt = np.abs(np.random.default_rng(0).normal(0.05, 0.02, N)) + 1e-3
    cluster = api.ClusterSpec(n_workers=P, workers=(
        api.WorkerSpec(),
        api.WorkerSpec(speed=0.25),            # straggler
        api.WorkerSpec(fail_time=0.5),         # fail-stop
        api.WorkerSpec(msg_latency=0.05),      # latency-perturbed
    ))

    def run_with(backend):
        tech = dls.make_technique(technique, N, P, seed=3)
        queue = rdlb.RobustQueue(N, tech)
        eng = engine.Engine(queue, cluster.engine_workers(), backend,
                            h=1e-4)
        return eng.run()

    executed = FnBackend(task_fn=lambda t: t * t, task_times=tt)
    st_sim = run_with(simulator.SimBackend(tt))
    st_exec = run_with(executed)
    assert not st_sim.hung and not st_exec.hung
    assert ([chunk_key(c) for c in st_sim.assignment_log]
            == [chunk_key(c) for c in st_exec.assignment_log])
    assert st_sim.t_virtual == pytest.approx(st_exec.t_virtual)
    assert st_sim.n_duplicates == st_exec.n_duplicates
    # ... and the executing backend really computed every task, once
    assert executed.results == {t: t * t for t in range(N)}


def test_run_to_completion_is_engine_backed():
    q = rdlb.RobustQueue(12, dls.make_technique("FAC", 12, 3))
    log = rdlb.run_to_completion(q, range(3))
    assert q.done and q.n_finished == 12
    covered = sorted(t for c in log if not c.duplicate for t in c.tasks())
    assert covered == list(range(12))


def test_run_to_completion_raises_on_nonrobust_stall():
    q = rdlb.RobustQueue(4, dls.make_technique("SS", 4, 2),
                         rdlb_enabled=False)
    held = q.request(0)                       # never reported: Fig. 1b
    assert held is not None
    with pytest.raises(RuntimeError):
        rdlb.run_to_completion(q, [1])


# ----------------------------------------------- dup-count leak regression
def test_duplicate_slot_frees_on_report():
    """Regression: ``_reissue`` counts the duplicate under the ORIGINAL
    chunk's seq; ``report`` must decrement the same key (it used to
    decrement under the duplicate's own seq, leaking the slot)."""
    q = rdlb.RobustQueue(2, dls.make_technique("SS", 2, 3),
                         max_duplicates=1)
    c0 = q.request(0)
    c1 = q.request(0)                         # PE 0 holds both tasks
    dup = q.request(1)
    assert dup.duplicate and dup.origin_seq == c0.seq
    assert q._c_dups[c0.seq] == 1
    q.report(dup)                             # duplicate completes
    assert q._c_dups[c0.seq] == 0             # slot freed under origin seq
    q.report(c0)                              # late original: wasted
    assert q._c_dups[c0.seq] == 0             # no double-free / underflow
    q.report(c1)
    assert q.done
    assert (q._c_dups[:q._seq] >= 0).all()


def test_late_duplicate_report_decrements_origin():
    """Original wins; the WASTED duplicate's report must still free its
    slot under the origin seq (no stale live-duplicate accounting)."""
    q = rdlb.RobustQueue(1, dls.make_technique("SS", 1, 2),
                         max_duplicates=2)
    c0 = q.request(0)
    d0 = q.request(1)
    assert q._c_dups[c0.seq] == 1
    q.report(c0)                              # original first
    q.report(d0)                              # duplicate wasted
    assert q.wasted_tasks == 1
    assert q._c_dups[c0.seq] == 0


# ------------------------------------------------- schedule-invariant step
@pytest.fixture(scope="module")
def train_setup():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    batch = batch_for_step(CFG, 0, 8, 16)
    return model, params, batch


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def test_train_step_schedule_invariant(train_setup):
    """exact_accumulation: the update is bit-identical no matter how the
    engine schedules the microbatches (workers, technique, concurrency)."""
    model, params, batch = train_setup
    results = []
    for kw in (dict(n_workers=1, technique="SS"),
               dict(n_workers=4, technique="FAC"),
               dict(n_workers=3, technique="GSS"),
               dict(n_workers=4, technique="FAC", threaded=True)):
        ex = RDLBTrainExecutor(model, spec=api.train_spec(n_tasks=8, **kw),
                               exact_accumulation=True)
        opt_state = ex.opt.init(params)
        res = ex.train_step(params, opt_state, batch)
        assert not res.hung
        results.append(res)
    for other in results[1:]:
        assert trees_equal(results[0].params, other.params)
        assert results[0].loss == pytest.approx(other.loss, abs=1e-9)


# --------------------------------------------------------- serving parity
@pytest.fixture(scope="module")
def serve_setup():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, size=6).astype(np.int32)
               for _ in range(10)]
    return model, params, prompts


def make_requests(prompts):
    return [Request(i, p, max_new_tokens=3) for i, p in enumerate(prompts)]


def test_batched_decode_matches_per_request(serve_setup):
    """One padded jitted batch call per chunk == the per-request token
    loop, token for token (rows are independent through the cache)."""
    model, params, prompts = serve_setup
    a = make_requests(prompts)
    b = make_requests(prompts)
    # GSS -> multi-request chunks -> real batching (and batch-dim padding)
    spec = api.serve_spec(technique="GSS", n_workers=2)
    RDLBServeExecutor(model, params, spec=spec, batch_decode=True).serve(a)
    RDLBServeExecutor(model, params, spec=spec, batch_decode=False).serve(b)
    for x, y in zip(a, b):
        assert x.output is not None and np.array_equal(x.output, y.output)


def test_concurrent_serve_first_completion_wins(serve_setup):
    """Threaded mode: duplicates genuinely race; a straggler + fail-stop
    replica still yields complete, deterministic outputs."""
    model, params, prompts = serve_setup
    ref = make_requests(prompts)
    RDLBServeExecutor(model, params,
                      spec=api.serve_spec(n_workers=1)).serve(ref)
    reqs = make_requests(prompts)
    ex = RDLBServeExecutor(model, params, spec=api.serve_spec(
        n_workers=3, threaded=True))
    ex.slow[0] = 0.02                        # straggler replica
    stats = ex.serve(reqs, fail_at={1: 1})   # fail-stop replica
    assert not stats.hung
    assert 1 in ex.dead
    for x, y in zip(reqs, ref):
        assert x.output is not None and np.array_equal(x.output, y.output)


def test_concurrent_serve_hang_without_rdlb(serve_setup):
    model, params, prompts = serve_setup
    reqs = make_requests(prompts[:4])
    ex = RDLBServeExecutor(model, params, spec=api.serve_spec(
        n_workers=2, rdlb_enabled=False, threaded=True))
    stats = ex.serve(reqs, fail_at={1: 0})
    assert stats.hung


# ------------------------------------- commits outside the engine's lock
def test_threaded_commits_run_concurrently():
    """Two live workers' commits are inside ``commit`` at once: each
    waits for the other at a two-party barrier, which times out (and
    the run raises) if the engine serialises its commits."""

    class MeetingBackend(engine.WorkerBackend):
        def __init__(self):
            self.barrier = threading.Barrier(2)
            self.committed = []

        def commit(self, chunk, wid, payload, newly):
            self.barrier.wait(timeout=10.0)
            self.committed.extend(newly)

    backend = MeetingBackend()
    queue = rdlb.RobustQueue(2, dls.make_technique("SS", 2, 2))
    eng = engine.Engine(queue, [engine.EngineWorker(wid=i)
                                for i in range(2)], backend)
    st = eng.run_threaded(stall_timeout=30.0)
    assert not st.hung and st.n_finished == 2
    assert sorted(backend.committed) == [0, 1]


class _AllocCountingChunkBackend(ChunkBackend):
    """A ``ChunkBackend`` that counts allocations of ``results``.  A read
    that finds no array yet waits 20 ms, so the first commits of a run
    all see ``None`` and race to allocate."""

    def __init__(self, *args):
        self._results, self.allocs = None, 0
        super().__init__(*args)

    @property
    def results(self):
        out = self._results
        if out is None:
            time.sleep(0.02)
        return out

    @results.setter
    def results(self, value):
        if value is not None:
            self.allocs += 1
        self._results = value


@pytest.mark.parametrize("kind", ["chunk", "train"])
def test_threaded_commits_exactly_once_under_races(kind):
    """P = 32 threads with a straggler and a count-based fail-stop, so
    duplicates race their originals and commits overlap: every task is
    applied exactly once, by the report that won it.  A short switch
    interval makes a lost update in a backend's reduction likely."""
    N, P, width = 512, 32, 4096
    if kind == "chunk":
        def chunk_fn(start, stop):
            return np.repeat(np.arange(start, stop, dtype=np.float32)[:, None],
                             width, axis=1)
        backend = _AllocCountingChunkBackend(chunk_fn, N)
    else:
        backend = TrainBackend(
            lambda t: (float(t), {"w": np.full(width, t, np.float32)}))
    workers = [engine.EngineWorker(wid=i) for i in range(P)]
    workers[0].sleep_per_task = 0.02          # straggler
    workers[1].fail_after_tasks = 0           # dies holding its 1st chunk
    queue = rdlb.RobustQueue(N, dls.make_technique("FAC", N, P))
    eng = engine.Engine(queue, workers, backend)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        st = eng.run_threaded(stall_timeout=30.0)
    finally:
        sys.setswitchinterval(interval)
    assert not st.hung and st.n_finished == N
    assert st.n_duplicates > 0 and 1 not in st.survivors
    if kind == "chunk":
        want = np.repeat(np.arange(N, dtype=np.float32)[:, None], width,
                         axis=1)
        assert backend.allocs == 1
        np.testing.assert_array_equal(backend.results, want)
    else:
        assert backend.n_done == N
        assert backend.loss_sum == float(sum(range(N)))
        np.testing.assert_array_equal(
            np.asarray(backend.reduced()["w"]),
            np.full(width, sum(range(N)), np.float32))


# ------------------------------------------- spec vs hand-built parity
@pytest.mark.parametrize("technique", ["SS", "FAC", "AWF-B"])
def test_spec_built_run_matches_hand_built_assignment_log(technique):
    """A spec-built run (api.build) produces an assignment log IDENTICAL
    to an Engine wired by hand from the same cluster's engine workers."""
    N, P = 64, 4
    tt = np.abs(np.random.default_rng(1).normal(0.05, 0.02, N)) + 1e-3
    cluster = api.ClusterSpec(n_workers=P, workers=(
        api.WorkerSpec(),
        api.WorkerSpec(speed=0.25),
        api.WorkerSpec(fail_time=0.5),
        api.WorkerSpec(msg_latency=0.05),
    ))

    # wiring by hand
    tech = dls.make_technique(technique, N, P, seed=3)
    queue = rdlb.RobustQueue(N, tech, max_duplicates=2)
    hand_eng = engine.Engine(queue, cluster.engine_workers(),
                             simulator.SimBackend(tt), h=1e-4)
    st_hand = hand_eng.run()

    # the same run, declared as data
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique=technique, seed=3),
        robustness=api.RobustnessSpec(max_duplicates=2),
        cluster=cluster,
        execution=api.ExecutionSpec(h=1e-4))
    spec = api.RunSpec.from_json(spec.to_json())   # ... through JSON
    spec_eng = api.build(spec, simulator.SimBackend(tt), n_tasks=N)
    st_spec = api.run(spec, spec_eng)

    assert not st_hand.hung and not st_spec.hung
    assert ([chunk_key(c) for c in st_hand.assignment_log]
            == [chunk_key(c) for c in st_spec.assignment_log])
    assert st_hand.t_virtual == pytest.approx(st_spec.t_virtual)
    assert st_hand.n_duplicates == st_spec.n_duplicates


# ------------------------------------- idle accounting (count-based fail)
def test_idle_clamped_at_last_completion_for_count_based_failstop():
    """Regression: a worker with fail_after_tasks (fail_time None) used
    to accrue idle until t_par; idle now ends at its last completion."""
    N = 8
    tt = np.ones(N)
    spec = api.RunSpec(
        cluster=api.ClusterSpec(
            n_workers=2,
            workers=(api.WorkerSpec(),
                     api.WorkerSpec(fail_after_tasks=1))),
        scheduling=api.SchedulingSpec(technique="SS"),
        execution=api.ExecutionSpec(h=1e-9))
    eng = api.build(spec, simulator.SimBackend(tt), n_tasks=N)
    st = api.run(spec, eng)
    assert not st.hung
    # worker 1 executed exactly 1 task (~1s busy) then died at its next
    # assignment; t_par ~ 7s.  Its idle must be ~0 (clamped at the last
    # completion), not ~6s.
    assert st.by_worker.get(1, 0) == 1
    assert st.t_virtual > 5.0
    assert st.worker_idle[1] < 0.5
    # the healthy worker's idle accounting is unchanged
    assert st.worker_idle[0] < 0.5
    # initially-dead workers accrue no idle either
    spec2 = spec.override("cluster.workers", ())
    spec2 = spec2.replace(cluster=api.ClusterSpec(
        n_workers=2, workers=(api.WorkerSpec(),
                              api.WorkerSpec(alive=False))))
    eng2 = api.build(spec2, simulator.SimBackend(tt), n_tasks=N)
    st2 = api.run(spec2, eng2)
    assert not st2.hung
    assert st2.worker_idle[1] == 0.0


# ------------------------------------ threaded-knob plumbing (ExecutionSpec)
def test_threaded_knobs_plumbed_from_spec():
    """Satellite: poll / stall_timeout / max_fruitless_polls flow from
    ExecutionSpec through api.run into the threaded loop — an explicit
    tiny max_fruitless_polls surfaces a stall by poll COUNT, well
    before the wall-clock stall_timeout.

    The stall is an AWF-B batch-weight barrier that can never clear:
    worker 1 dies holding a chunk the barrier is waiting on, and with
    rDLB off nothing re-issues it.  At a barrier workers keep polling
    (they do NOT take the non-robust dead-end exit), so the ONLY
    sub-stall_timeout way out is the fruitless poll counter — if that
    plumbing broke, this run would last the full 30 s and fail the
    wall-clock bound below."""
    import time
    N = 8
    tt = np.full(N, 0.01)
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="AWF-B"),
        robustness=api.RobustnessSpec(rdlb_enabled=False),
        cluster=api.ClusterSpec(
            n_workers=2,
            workers=(api.WorkerSpec(sleep_per_task=0.01),
                     api.WorkerSpec(fail_after_tasks=1))),
        execution=api.ExecutionSpec(mode="threaded", poll=0.005,
                                    stall_timeout=30.0,
                                    max_fruitless_polls=5))
    eng = api.build(spec, simulator.SimBackend(tt), n_tasks=N)
    assert eng.max_fruitless_polls == 5      # reached the engine
    t0 = time.monotonic()
    st = api.run(spec, eng)
    assert st.hung
    assert time.monotonic() - t0 < 10.0


# ------------------------------------------------------------ stats shape
def test_engine_stats_coherent():
    N, P = 32, 4
    tt = np.full(N, 0.01)
    sc = scenarios.failures(P, 1, t_exec_estimate=N * 0.01 / P, seed=0)
    tech = dls.make_technique("FAC", N, P)
    queue = rdlb.RobustQueue(N, tech)
    eng = engine.Engine(queue, sc.engine_workers(),
                        simulator.SimBackend(tt), h=1e-4)
    st = eng.run()
    assert not st.hung and st.n_finished == N
    assert st.n_assignments == len(st.assignment_log)
    assert st.n_duplicates == sum(c.duplicate for c in st.assignment_log)
    assert sum(st.by_worker.values()) >= N
    assert (st.worker_busy >= 0).all() and (st.worker_idle >= -1e-9).all()
