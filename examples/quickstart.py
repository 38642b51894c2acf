"""Quickstart: the rDLB mechanism in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

1. Schedule N tasks over P workers with a DLS technique.
2. Kill P-1 workers mid-run -> the queue re-issues their in-flight work.
3. Compare against the closed-form expectation of paper §3.1.
4. Adaptive scheduling: forecast the portfolio mid-run, hot-swap the
   technique for the remainder.
5. One spec to run them all: the SAME declarative RunSpec (a JSON-able
   scenario) drives the simulator, the training executor, and the
   serving executor.
6. Virtual -> threaded -> process: the SAME RunSpec again, escalating
   from simulated time to OS threads to REAL worker processes — where
   a declared fail_time becomes an actual mid-run SIGKILL.
7. Scale: the array-native core simulates P=1024 workers chewing
   through a MILLION tasks in seconds from one RunSpec — the regime
   where the paper's quadratic cost-decrease claim actually lives.
8. Monte-Carlo resilience: the device-resident simulator batches
   thousands of failure draws into ONE jit/vmap call — rho_res with a
   95% confidence interval from a single RunSpec.
9. Flight recorder: trace the process-mode chaos run event by event
   and export Chrome/Perfetto JSON — the re-issue filling the killed
   worker's gap, visible on a timeline.
10. Close the loop: calibrate the declared spec against the recorded
    run and re-forecast — the calibrated virtual twin predicts the
    physical run the declared twin underestimates by ~45%.
11. Device-resident decode: the serving hot path generates every token
    on device (prefill + fused scan, argmax feedback in-graph) — same
    tokens as the per-token loop, multiples of its throughput.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from repro import api
from repro.adaptive import AdaptiveConfig, Candidate, run_adaptive, run_static
from repro.core import dls, rdlb, theory

P, N = 8, 1024
TASK_T = 0.01

print("=== 1. rDLB queue: exactly-once under failures ===")
queue = rdlb.RobustQueue(N, dls.make_technique("FAC", N, P))
dead = {1, 2, 3, 4, 5, 6, 7}        # P-1 workers will never report
held = []
while not queue.done:
    progressed = False
    for pe in range(P):
        chunk = queue.request(pe)
        if chunk is None:
            continue
        progressed = True
        if pe in dead:
            held.append(chunk)       # fail-stop: assigned, never reported
            continue
        queue.report(chunk)
    if not progressed:
        break
s = queue.stats()
print(f"   finished {s['n_finished']}/{N} tasks with {len(dead)} dead "
      f"workers ({s['n_duplicates']} re-issues, {s['wasted_tasks']} wasted)")
assert queue.done

print("=== 2. Discrete-event simulation: failure vs hang ===")
# A Table-1 scenario is a ClusterSpec; one RunSpec varies around it.
tt = np.full(N, TASK_T)
spec2 = api.RunSpec(scheduling=api.SchedulingSpec(technique="FAC"),
                    cluster=api.scenarios.baseline(P))
base = api.simulate(spec2, tt)
spec2 = spec2.replace(cluster=api.scenarios.failures(
    P, 1, t_exec_estimate=base.t_par, seed=0))
with_rdlb = api.simulate(spec2, tt)
without = api.simulate(spec2.override("robustness.rdlb_enabled", False), tt)
print(f"   baseline           t_par = {base.t_par:.3f}s")
print(f"   1 failure + rDLB   t_par = {with_rdlb.t_par:.3f}s")
print(f"   1 failure, no rDLB t_par = {without.t_par}  <- the paper's hang")

print("=== 3. Theory (§3.1): expected cost of one failure ===")
n = N // P
e_t = theory.expected_time_one_failure(n, TASK_T, P, lam=0.05)
c_star = theory.checkpoint_crossover(n, TASK_T, P, lam=0.05)
print(f"   E[T] = {e_t:.3f}s (T = {n * TASK_T:.2f}s); rDLB beats "
      f"checkpoint/restart when C >= {c_star:.2e}s")

print("=== 4. Adaptive scheduling: simulate-in-the-loop, hot-swap ===")
# Half the workers compute at quarter speed; no static technique wins
# every scenario, so the controller forecasts a portfolio (by resuming
# the simulator from a mid-run snapshot) and swaps the queue's technique
# for the remainder when a candidate predicts a faster finish.
perturbed = api.scenarios.pe_perturbation(P, node_size=P // 2, node=1)
portfolio = tuple(Candidate(t) for t in ("FAC", "GSS", "mFSC", "AWF-C"))
cfg = AdaptiveConfig(portfolio=portfolio, decision_every_chunks=32,
                     min_remaining=16, max_sim_tasks=None)
res, ctrl = run_adaptive(tt, perturbed, initial="FAC", config=cfg)
statics = {c.label: run_static(tt, perturbed, c).t_par
           for c in portfolio}
oracle = min(statics, key=statics.get)
print(f"   static portfolio   " +
      ", ".join(f"{k}={v:.3f}s" for k, v in statics.items()))
print(f"   adaptive           t_par = {res.t_par:.3f}s "
      f"(oracle-best static: {oracle} = {statics[oracle]:.3f}s)")
for d in ctrl.decisions:
    print(f"     t={d.t:7.3f}s remaining={d.n_remaining:4d} "
          f"{'swap -> ' + d.chosen if d.swapped else 'stay on ' + d.chosen}")
print(f"   adaptive/oracle    {res.t_par / statics[oracle]:.3f}x "
      f"(bound asserted in tests/test_adaptive.py)")

print("=== 5. One spec to run them all (simulate / train / serve) ===")
# A scenario is DATA: one frozen RunSpec — FAC scheduling, 4 workers with
# worker 3 dead from the start, rDLB on — serialized to JSON and driven
# through all three drivers.  The JSON round-trip is lossless.
spec = api.train_spec(technique="FAC", n_tasks=8).replace(
    cluster=api.ClusterSpec.from_serve(4, dead={3}, name="demo"))
assert api.RunSpec.from_json(spec.to_json()) == spec
sim5 = api.simulate(spec, np.ones(spec.n_tasks))
print(f"   simulator: t_par={sim5.t_par:.1f} "
      f"({sim5.n_finished}/{sim5.n_tasks} tasks, 1 dead worker)")

import jax                                   # the real-compute drivers
from repro.data import batch_for_step
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.runtime import RDLBServeExecutor, RDLBTrainExecutor, Request

cfg5 = ModelConfig(family="dense", n_layers=1, d_model=32, n_heads=2,
                   n_kv_heads=2, d_ff=64, vocab_size=64)
model5 = build_model(cfg5)
params5 = model5.init(jax.random.PRNGKey(0))

ex5 = RDLBTrainExecutor(model5, spec=spec, exact_accumulation=True)
res5 = ex5.train_step(params5, ex5.opt.init(params5),
                      batch_for_step(cfg5, 0, spec.n_tasks, 16))
print(f"   train:     loss={res5.loss:.4f} survivors={res5.survivors} "
      f"(same spec, gradients exactly-once)")

sx5 = RDLBServeExecutor(model5, params5, spec=spec)
reqs5 = [Request(i, np.arange(4, dtype=np.int32), max_new_tokens=2)
         for i in range(spec.n_tasks)]
st5 = sx5.serve(reqs5)
done5 = sum(r.output is not None for r in reqs5)
print(f"   serve:     {done5}/{len(reqs5)} requests "
      f"(same spec, first-completion-wins)")
assert not res5.hung and not st5.hung and done5 == len(reqs5)

print("=== 6. Virtual -> threaded -> process: one spec, three physics ===")
# The same scenario — 3 workers, worker 1 fail-stops mid-run — escalated
# through the execution modes.  In threaded mode the worker thread dies
# at wall-clock fail_time holding its chunk; in process mode the worker
# is a REAL OS process and the fail-stop is a REAL SIGKILL
# (repro.cluster.chaos).  Either way rDLB re-issues the victim's
# in-flight work and every task still completes exactly once.  Virtual
# mode is the predictive twin: same queue, same completion set,
# simulated time.  (sleep_per_task gives tasks real duration in the
# wall-clock modes, so the fail-stop lands mid-run in all three.)
tt6 = np.full(48, 0.005)
workers6 = tuple(api.WorkerSpec(sleep_per_task=0.004,
                                fail_time=0.04 if wid == 1 else None)
                 for wid in range(3))
spec6 = api.RunSpec(
    scheduling=api.SchedulingSpec(technique="FAC"),
    cluster=api.ClusterSpec(n_workers=3, workers=workers6,
                            name="one_kill"),
    execution=api.ExecutionSpec(mode="virtual", stall_timeout=10.0,
                                wall_timeout=60.0))
for mode in ("virtual", "threaded", "process"):
    r6 = api.simulate(spec6.override("execution.mode", mode), tt6)
    clock = ("virtual" if mode == "virtual" else "wall")
    kills = {"virtual": "simulated fail-stop", "threaded": "thread dies",
             "process": "1 REAL SIGKILL"}[mode]
    print(f"   {mode:9s} {r6.n_finished}/{len(tt6)} tasks, "
          f"{clock} t={r6.t_par:.3f}s, dups={r6.n_duplicates} [{kills}]")
    assert not r6.hang and r6.n_finished == len(tt6)

print("=== 7. Scale: a million tasks over 1024 workers, in seconds ===")
# Self-scheduling (SS) means one queue transaction per task — the worst
# case for a simulator and exactly the paper's §4 scaling regime.  The
# array-native core (numpy flag/re-issue transactions + a vectorized
# fast-forward over the steady-state rounds) runs it as fast as the
# hardware allows; the preserved pure-Python oracle would take minutes.
import time as _time
P7, N7 = 1024, 1_000_000
tt7 = np.full(N7, 0.01)
spec7 = api.RunSpec(
    scheduling=api.SchedulingSpec(technique="SS"),
    cluster=api.scenarios.baseline(P7),
    execution=api.ExecutionSpec(h=1e-4))
t0 = _time.perf_counter()
r7 = api.simulate(spec7, tt7)
wall7 = _time.perf_counter() - t0
print(f"   P={P7}, N={N7:,}: {r7.n_assignments:,} queue transactions "
      f"in {wall7:.2f}s wall")
print(f"   simulated t_par = {r7.t_par:.2f}s (vs N*t/P = "
      f"{N7 * 0.01 / P7:.2f}s ideal — SS at P=1024 is master-bound: "
      f"~h*N of serialized scheduling, the paper's SS overhead story)")
assert not r7.hang and r7.n_finished == N7 and wall7 < 30.0

print("=== 8. Monte-Carlo resilience: 10^4 failure draws, one call ===")
# Figure 4 scores ONE seed-0 instance of each failure scenario.  The
# device-resident simulator (repro.core.devicesim) lowers a RunSpec onto
# jax and batches THOUSANDS of perturbation draws into one jit/vmap
# call, so rho_res becomes a distribution with a confidence interval
# instead of a point.  Here: every "k workers fail at uniform-random
# times" draw for SS, paired across draws with mFSC/FSC baselines —
# each cell is one device call, not 10^4 event-loop runs.  (The full
# 10^4-draw grid is `python benchmarks/fig4_resilience.py
# --monte-carlo`; this demo keeps draws small.)
from benchmarks.fig4_resilience import monte_carlo
rows8, _ = monte_carlo(P=16, n_tasks=192, draws=500, cells=(1, 15))
for k, tech, d8, mean8, ci8, *_ in rows8:
    print(f"   k={k:2d} {tech:5s} rho_res = {mean8:.3f} "
          f"+- {ci8:.3f} (95% CI, {d8} draws)")

print("=== 9. Flight recorder: trace a chaos run, open in Perfetto ===")
# Aggregates say WHAT happened; the trace shows WHEN.  Turn on the
# flight recorder (ExecutionSpec.trace) for the section-6 one-kill
# scenario in process mode — a REAL SIGKILL — and export the run as
# Chrome-trace JSON.  Drag the file onto https://ui.perfetto.dev: one
# lane per worker, the victim's lane ends at the kill instant, the
# rDLB re-issue shows up orange on a survivor's lane filling the gap.
from repro.core import trace as trc
spec9 = spec6.override("execution.mode", "process").override(
    "execution.trace", True)
r9 = api.simulate(spec9, tt6)
assert not r9.hang and r9.n_finished == len(tt6)
c9 = r9.trace.counters()                # stream == queue accounting
assert c9["n_finished"] == r9.n_finished
assert c9["n_duplicates"] == r9.n_duplicates
out9 = Path("artifacts") / "quickstart_trace.json"
out9.parent.mkdir(exist_ok=True)
trc.save_chrome(r9.trace, out9)
lat9 = r9.trace.dispatch_latency()
print(f"   {len(r9.trace)} events recorded; dispatch latency "
      f"p50={lat9['p50'] * 1e6:.0f}us p99={lat9['p99'] * 1e6:.0f}us")
print(f"   wrote {out9} -- open it at https://ui.perfetto.dev")
print(f"   (or: python -m repro trace summarize {out9})")

print("=== 10. Record -> calibrate -> re-forecast (repro.obs) ===")
# The declared spec says tasks take 0.005s, but the process workers
# ALSO sleep 0.004s per task (sleep_per_task), so the declared virtual
# twin underestimates the section-9 run by ~45%.  calibrate_trace fits
# the spec back from the recorded run — measured per-worker speeds,
# dispatch overhead h, message latency — while PRESERVING the declared
# fail_time so the twin replays the same SIGKILL.  The calibrated twin
# then predicts the physical run it was fitted on; every override (or
# deliberate non-override) is a reason-annotated residual.
# (CLI equivalent: python -m repro trace calibrate run.json --spec
# spec.json -o calibrated.json)
from repro.obs import calibrate_trace
calib10 = calibrate_trace(r9.trace, spec9, task_times=tt6)
twin_decl = spec9.override("execution.mode", "virtual").override(
    "execution.trace", False)
twin_cal = calib10.spec.override("execution.mode", "virtual").override(
    "execution.trace", False)
t_decl = api.simulate(twin_decl, tt6).t_par
t_cal = api.simulate(twin_cal, tt6).t_par
meas10 = r9.t_par       # loop time, excluding process spawn/teardown
print(f"   measured (process run)     t = {meas10:.3f}s")
print(f"   declared-spec virtual twin t = {t_decl:.3f}s "
      f"({abs(t_decl - meas10) / meas10 * 100:.0f}% off)")
print(f"   calibrated virtual twin    t = {t_cal:.3f}s "
      f"({abs(t_cal - meas10) / meas10 * 100:.0f}% off)")
for res10 in calib10.residuals[:3]:
    print(f"     {res10}")
assert abs(t_cal - meas10) < abs(t_decl - meas10)
# In-loop: AdaptiveSpec(calibrate=True) runs this fit at every replan,
# with an EWMA drift detector deciding when measured speeds have moved
# enough to re-adopt — evidence lands on DecisionRecord.calibration.

print("=== 11. Device-resident decode: tokens/s on the serving path ===")
# The section-5 serve calls decode one jitted decode_step per token —
# S+max_new host round-trips per request group.  FusedGenerator folds
# the whole generation into ONE jitted call: model.prefill fills the
# cache for all prompt positions in a single pass, then a lax.scan runs
# the decode steps with greedy argmax ON DEVICE and the token fed back
# in-graph.  Same model, same requests, token-identical output — the
# only change is execution shape.  (benchmarks/decode_bench.py sweeps
# B in {1,4,16,64}; scripts/ci.sh gates the speedup at B=16.)
from repro.runtime.serve_executor import FusedGenerator, \
    greedy_decode_group
rng11 = np.random.default_rng(11)
prompts11 = rng11.integers(0, cfg5.vocab_size, size=(8, 16)).astype(
    np.int32)
decode11 = jax.jit(model5.decode_step, donate_argnums=(1,))
gen11 = FusedGenerator(model5)
out_loop = greedy_decode_group(model5, params5, decode11, prompts11, 8)
out_fused = gen11(params5, prompts11, 8)          # also the jit warm-up
assert np.array_equal(out_loop, out_fused)
t0 = _time.perf_counter()
greedy_decode_group(model5, params5, decode11, prompts11, 8)
t_loop11 = _time.perf_counter() - t0
t0 = _time.perf_counter()
gen11(params5, prompts11, 8)
t_fused11 = _time.perf_counter() - t0
print(f"   per-token loop  {8 * 8 / t_loop11:7.0f} tok/s")
print(f"   fused (1 call)  {8 * 8 / t_fused11:7.0f} tok/s "
      f"({t_loop11 / t_fused11:.1f}x, token-identical)")
print("OK")
