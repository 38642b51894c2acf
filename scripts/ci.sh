#!/usr/bin/env bash
# CPU smoke and perf gates for the simulator, benchmarks and examples,
# then the pytest suite.  Tier-1 verification is the pytest command in
# ROADMAP.md; this script is a wider local check around it.
# Usage:  scripts/ci.sh [extra pytest args]
#   scripts/ci.sh -m "not slow"     # skip long-running tests
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
python -m compileall -q src
python benchmarks/fig_adaptive.py --dry-run
# perf-smoke gate: the array-native core must finish a fixed
# P=512/N=65536 SS simulation well inside a generous wall budget —
# catches accidental re-introduction of per-task Python loops in the
# flag/re-issue hot path.  Hard `timeout` so a regression cannot wedge CI.
timeout 60 python - <<'PY'
import time
import numpy as np
from repro import api
tt = np.full(65536, 0.01)
spec = api.RunSpec(
    scheduling=api.SchedulingSpec(technique="SS"),
    cluster=api.scenarios.baseline(512),
    execution=api.ExecutionSpec(h=1e-4))
t0 = time.perf_counter()
r = api.simulate(spec, tt)
dt = time.perf_counter() - t0
assert not r.hang and r.n_finished == 65536, (r.t_par, r.n_finished)
assert dt < 10.0, f"perf-smoke regression: {dt:.2f}s for P=512/N=65536"
print(f"perf-smoke,ok,wall={dt:.3f}s,assignments={r.n_assignments}")
# and the SCALAR event loop (a straggler declines fast-forward): the
# per-chunk constant must stay bounded too
spec2 = spec.replace(cluster=api.scenarios.pe_perturbation(
    512, node_size=16, node=1, slowdown=0.25))
tt2 = np.full(16384, 0.01)
t0 = time.perf_counter()
r2 = api.simulate(spec2, tt2)
dt2 = time.perf_counter() - t0
assert not r2.hang and r2.n_finished == 16384, (r2.t_par, r2.n_finished)
assert dt2 < 10.0, f"scalar-loop regression: {dt2:.2f}s for P=512/N=16384"
print(f"perf-smoke,scalar,wall={dt2:.3f}s,assignments={r2.n_assignments}")
PY
# device-sweep smoke + perf gate: one jit/vmap core.devicesim call over a
# >=256-element (candidate x draw) batch must (a) agree with the scalar
# engine and (b) beat the equivalent Python loop by >=5x at P=256.  Hard
# `timeout` so a compile hang cannot wedge CI (full 10x gate at P=1024
# runs in fig_scale --paper).
timeout 240 python - <<'PY'
from benchmarks.fig_scale import device_sweep_point
d = device_sweep_point(P=256, N=1 << 15, B=512, loop_sample=2)
assert d["batch"] >= 256, d
assert d["speedup_warm"] >= 5.0, f"device-sweep perf gate: {d}"
print(f"device-smoke,ok,B={d['batch']},warm_s={d['warm_s']},"
      f"x={d['speedup_warm']}")
PY
# CPU dry runs: machine-readable BENCH_*.json every CI run (small:
# fig_scale dry-run writes BENCH_scale.json, theory is seconds-cheap),
# copied to benchmarks/baselines/.  These are CPU numbers; the chip's
# record is PERF_LEDGER.jsonl.
timeout 120 python benchmarks/fig_scale.py --dry-run
mkdir -p benchmarks/baselines
cp artifacts/bench/BENCH_scale.json benchmarks/baselines/BENCH_scale.json
timeout 300 python -m benchmarks.run --only theory --emit-json > /dev/null
# decode perf-smoke gate: device-resident fused generation (prefill +
# lax.scan decode with on-device argmax feedback) must beat the
# per-token loop by >=2x at B=16 on CPU, token-identical (asserted
# inside decode_bench quick mode; the full >=5x paper gate runs in
# kernels_bench --paper).  Emits BENCH_decode.json and seeds the
# dry-run baseline so successor PRs inherit the decode trajectory.
timeout 300 python -m benchmarks.run --only decode --emit-json > /dev/null
cp artifacts/bench/BENCH_decode.json benchmarks/baselines/BENCH_decode.json
# spec-layer smokes: the facade, the CLI, and the examples cannot rot
tmp_spec=$(mktemp /tmp/rdlb_spec_XXXXXX.json)
python - "$tmp_spec" <<'PY'
import sys
import numpy as np
from benchmarks import fig4_resilience
fig4_resilience.emit_spec(
    sys.argv[1], P=8, techniques=["SS", "FAC"],
    task_times=np.full(64, 0.01),
    workload={"kind": "uniform", "n": 64, "t": 0.01})
PY
python -m repro run --spec "$tmp_spec" --dry-run
rm -f "$tmp_spec"
python examples/quickstart.py > /dev/null
# process-cluster smoke: real worker processes, one REAL mid-run SIGKILL,
# exactly-once completion — under a hard wall-clock guard so a regression
# can hang CI for at most two minutes
timeout 120 python - <<'PY'
import numpy as np
from repro import api
tt = np.full(60, 0.004)
spec = api.RunSpec(
    scheduling=api.SchedulingSpec(technique="FAC"),
    cluster=api.ClusterSpec(n_workers=3, workers=(
        api.WorkerSpec(), api.WorkerSpec(fail_time=0.04),
        api.WorkerSpec())),
    execution=api.ExecutionSpec(mode="process", stall_timeout=10.0,
                                wall_timeout=60.0))
r = api.simulate(spec, tt)
assert not r.hang and r.n_finished == 60, (r.t_par, r.n_finished)
print(f"cluster-smoke,ok,t_wall={r.t_wall:.3f}s,dups={r.n_duplicates}")
PY
# flight-recorder smokes: (a) the CLI --trace path exports valid
# Chrome-trace JSON whose reconstructed counters match the run; (b) the
# tracing-off hot path stays free — the traced P=512/N=65536 perf-smoke
# must land within 1.10x of the untraced run (best-of-3, additive
# epsilon absorbs scheduler jitter on a loaded CI host)
tmp_trace=$(mktemp /tmp/rdlb_trace_XXXXXX.json)
tmp_spec=$(mktemp /tmp/rdlb_spec_XXXXXX.json)
python - "$tmp_spec" <<'PY'
import json
import sys
from repro import api
doc = {
    "workload": {"kind": "uniform", "n": 256, "t": 0.005},
    "spec": api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        cluster=api.ClusterSpec(n_workers=4, workers=(
            api.WorkerSpec(),) * 3 + (api.WorkerSpec(fail_time=0.1),)),
    ).to_dict(),
}
with open(sys.argv[1], "w") as f:
    json.dump(doc, f)
PY
python -m repro run --spec "$tmp_spec" --trace "$tmp_trace" > /dev/null
python - "$tmp_trace" <<'PY'
import json
import sys
from repro.core import trace as trc
with open(sys.argv[1]) as f:
    doc = json.load(f)
assert doc["traceEvents"], "empty Chrome trace"
assert all("ph" in e and "pid" in e for e in doc["traceEvents"])
c = trc.load_trace(sys.argv[1]).counters()
assert c["n_finished"] == 256, c
print(f"trace-smoke,ok,events={len(doc['traceEvents'])},"
      f"dups={c['n_duplicates']}")
PY
python -m repro trace summarize "$tmp_trace" > /dev/null
rm -f "$tmp_trace" "$tmp_spec"
timeout 120 python - <<'PY'
import time
import numpy as np
from repro import api
tt = np.full(65536, 0.01)
spec = api.RunSpec(
    scheduling=api.SchedulingSpec(technique="SS"),
    cluster=api.scenarios.baseline(512),
    execution=api.ExecutionSpec(h=1e-4))

def best_of(s, n=3):
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        r = api.simulate(s, tt)
        best = min(best, time.perf_counter() - t0)
        assert not r.hang and r.n_finished == 65536
    return best

t_off = best_of(spec)
t_on = best_of(spec.override("execution.trace", True))
assert t_on <= t_off * 1.10 + 0.05, (
    f"trace overhead gate: traced {t_on:.3f}s vs untraced {t_off:.3f}s")
print(f"trace-overhead,ok,off={t_off:.3f}s,on={t_on:.3f}s")
# live telemetry must honor the same budget: streaming every event
# through the MetricsHub estimators (store-less recorder) stays within
# 1.10x of the fully-off run on the same P=512/N=65536 perf-smoke
t_m = best_of(spec.override("execution.metrics", True))
assert t_m <= t_off * 1.10 + 0.05, (
    f"metrics overhead gate: metered {t_m:.3f}s vs off {t_off:.3f}s")
print(f"metrics-overhead,ok,off={t_off:.3f}s,on={t_m:.3f}s")
PY
# calibration smoke: record a short threaded chaos run, fit the spec
# back through the CLI (`trace calibrate`), and the calibrated virtual
# twin must predict the measured makespan better than the declared one.
# Threaded wall time comes from sleep_per_task (0.006s) while the
# declared workload says 0.004s/task, so the declared twin is ~33% off
# by construction and calibration must close most of that — determinism
# makes this a tight gate, and the hard timeout keeps a regression from
# wedging CI.
timeout 120 python - <<'PY'
import json
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
from repro import api

tmp = Path(tempfile.mkdtemp(prefix="rdlb_calib_"))
doc = {
    "workload": {"kind": "uniform", "n": 96, "t": 0.004},
    "spec": api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        cluster=api.ClusterSpec(3, tuple(
            api.WorkerSpec(sleep_per_task=0.006,
                           fail_time=0.08 if w == 1 else None)
            for w in range(3)), name="ci_calib"),
        execution=api.ExecutionSpec(mode="threaded", h=0.0,
                                    stall_timeout=10.0)).to_dict(),
}
(tmp / "run.json").write_text(json.dumps(doc))
for attempt in range(3):
    out = subprocess.run(
        [sys.executable, "-m", "repro", "run", "--spec",
         str(tmp / "run.json"), "--trace", str(tmp / "trace.json")],
        capture_output=True, text=True, check=True)
    t_meas = float(out.stdout.splitlines()[0].split(",")[5])
    subprocess.run(
        [sys.executable, "-m", "repro", "trace", "calibrate",
         str(tmp / "trace.json"), "--spec", str(tmp / "run.json"),
         "-o", str(tmp / "calibrated.json")],
        capture_output=True, text=True, check=True)
    tt = np.full(96, 0.004)
    decl = api.RunSpec.from_dict(doc["spec"]).override(
        "execution.mode", "virtual")
    cal = api.RunSpec.load(tmp / "calibrated.json").override(
        "execution.mode", "virtual")
    err_decl = abs(api.simulate(decl, tt).t_par - t_meas) / t_meas
    err_cal = abs(api.simulate(cal, tt).t_par - t_meas) / t_meas
    if err_cal < err_decl:
        break
assert err_cal < err_decl, (
    f"calibration gate: calibrated twin {err_cal:.1%} off vs "
    f"declared {err_decl:.1%}")
print(f"calibration-smoke,ok,err_decl={err_decl:.3f},"
      f"err_cal={err_cal:.3f}")
PY
python -m pytest -x -q "$@"
