"""The benchmark's harness: one cell of ``BENCHMARK.json``, run once.

A cell names a configuration and a traffic mix.  Everything that belongs
to one of them lives in files of its own, found by name under the
benchmark's directory:

- ``configs/<config>.json``: the sizes, the application (``app``), the
  kernels it runs, and the limits of the correctness comparison;
- ``apps/<app>.py``: binds the program's own chunk entry (the system
  under test) to the sizes: ``bind(cfg) -> (chunk_fn, prepare)``;
- ``reference/<app>.py``: the plain jnp reference, importing nothing of
  the program: ``compute(cfg, dtype)`` and ``chunk_fn(cfg, dtype)``;
- ``work/<kernel>.py``: the algorithm's operations and bytes of one
  kernel call, from its shapes: ``call(cfg, start, stop, reference)``;
- ``traffic/<mix>.json``: technique, workers and perturbation, read by
  the one generator in ``traffic.py``;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)``, which
  returns a number or None when it finds nothing to read.

A run: set-up (inputs on the device, one whole loop of the cell's
traffic to warm every shape), then parallel loops back to back until
their makespans add up to the window, then the reference and the
comparison that decides ``correct``.  Each loop is one
``api.run(spec, api.build(spec, backend))`` with threaded workers and
rDLB on, the way a time-stepping application calls its parallel loop
once per step.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

from chipbench import traffic as traffic_gen

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SPAN_LOOP = "loop"
SPAN_EXECUTE = "backend.execute"
SPAN_COMMIT = "backend.commit"
# a traced run traces the loops that start in the window's first seconds
# (the whole window of a fast cell is millions of trace events)
TRACE_SECONDS = 15.0


# ------------------------------------------------------------- loading
def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_dir(root: Path, manifest: dict) -> Path:
    return root / manifest["paths"][0]


def load_manifest(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    root: Path
    manifest: dict
    workload: dict
    cfg: dict
    mix: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def dir(self) -> Path:
        return bench_dir(self.root, self.manifest)

    def module(self, kind: str, name: str):
        return load_module(self.dir / kind / f"{name}.py",
                           f"chipbench_{kind}_{name}".replace("-", "_")
                           .replace(".", "_"))

    def applies(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells


def load_cell(root: Path, workload: str) -> Cell:
    manifest = load_manifest(root)
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic_gen.load(bench_dir(root, manifest), w["traffic"])
    return Cell(root, manifest, w, cfg, mix)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    or ``JAX_COMPILATION_CACHE_DIR``), made to hold every program, however
    quick to compile, so that only a cell's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


# --------------------------------------------------------- instruments
class Compiles:
    """Programs compiled or loaded from the persistent cache inside the
    ``with`` block (``jax.monitoring`` backend-compile events)."""

    def __enter__(self) -> "Compiles":
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


class GcTime:
    """Seconds the cyclic garbage collector ran (``gc.callbacks``)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self._t0: Optional[float] = None

    def __enter__(self) -> "GcTime":
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None


def span_backend(inner):
    """Wrap a program backend so that each ``execute`` and ``commit``
    is a host span in the profiler's trace, and the executed chunks are
    recorded (start, stop, duplicate)."""
    import jax
    from repro.core.engine import WorkerBackend

    class SpanBackend(WorkerBackend):
        def __init__(self) -> None:
            self.inner = inner
            self.calls: list[tuple[int, int, bool]] = []

        def execute(self, chunk, wid):
            with jax.profiler.TraceAnnotation(SPAN_EXECUTE):
                out = self.inner.execute(chunk, wid)
            self.calls.append((chunk.start, chunk.stop, chunk.duplicate))
            return out

        def cost(self, chunk, wid):
            return self.inner.cost(chunk, wid)

        def commit(self, chunk, wid, payload, newly):
            with jax.profiler.TraceAnnotation(SPAN_COMMIT):
                self.inner.commit(chunk, wid, payload, newly)

    return SpanBackend()


@dataclasses.dataclass
class LoopRecord:
    seconds: float              # makespan: api.build + api.run
    hung: bool
    n_finished: int
    calls: list                 # executed chunks (start, stop, duplicate)
    gc_s: float = 0.0           # of ``seconds``, the garbage collector's


def run_loop(cfg: dict, plan: traffic_gen.Plan, chunk_fn: Callable,
             workers: list[dict]):
    """One parallel loop of the cell: returns (record, result rows)."""
    from repro import api
    from repro.runtime import ChunkBackend
    N = cfg["n_tasks"]
    backend = span_backend(ChunkBackend(chunk_fn, N))
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique=plan.technique),
        robustness=api.RobustnessSpec(rdlb_enabled=True),
        cluster=api.ClusterSpec(
            n_workers=plan.P,
            workers=tuple(api.WorkerSpec.from_dict(w) for w in workers)),
        execution=api.ExecutionSpec(mode="threaded"),
        n_tasks=N)
    t0 = time.perf_counter()
    stats = api.run(spec, api.build(spec, backend))
    dt = time.perf_counter() - t0
    rec = LoopRecord(dt, stats.hung, stats.n_finished, backend.calls)
    return rec, backend.inner.results


# ------------------------------------------------------------- the run
@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    cell: Cell
    setup_s: float
    loops: list                   # LoopRecord of every loop in the window
    compiles_in_window: int
    trace: Optional[dict]         # trace_reduce.reduce(...) or None
    reference: Optional[np.ndarray]
    device_kind: str
    work: dict                    # kernel name -> work module

    @property
    def cfg(self) -> dict:
        return self.cell.cfg

    @property
    def peaks(self) -> dict:
        """The device's published peaks; an unknown device is an error."""
        table = json.loads((self.cell.dir / "peaks.json").read_text())
        if self.device_kind not in table["devices"]:
            raise KeyError(f"no published peaks for device kind "
                           f"{self.device_kind!r} in peaks.json")
        return table["devices"][self.device_kind]

    @property
    def window_s(self) -> float:
        return float(sum(r.seconds for r in self.loops))

    @property
    def traced_loops(self) -> list:
        """The loops the trace holds: the window's first ones."""
        return self.loops[:self.trace["loops"]] if self.trace else []

    def kernel_work(self, kernel: str, loops: list
                    ) -> tuple[float, float, float]:
        """(ops, bytes, least seconds at the peaks) of every call of
        ``kernel`` in ``loops``, from the algorithm's counts."""
        mod = self.work[kernel]
        F, B = self.peaks["flops_per_s"], self.peaks["bytes_per_s"]
        ops = byts = least = 0.0
        for rec in loops:
            for start, stop, _ in rec.calls:
                o, b = mod.call(self.cfg, start, stop, self.reference)
                ops, byts = ops + o, byts + b
                least += max(o / F, b / B)
        return ops, byts, least


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float,
             chunk_fn: Optional[Callable] = None) -> dict:
    """Run the cell once and return its result line (a dict).

    ``chunk_fn`` replaces the program's chunk entry (the control run and
    the tests' planted faults); the benchmark's own runs leave it None.
    """
    import jax
    from chipbench import trace_reduce

    cfg = cell.cfg
    N = cfg["n_tasks"]
    plan = traffic_gen.Plan(cell.mix, cfg, seed)
    if chunk_fn is None:
        chunk_fn, prepare = cell.module("apps", cfg["app"]).bind(cfg)
        prepare()
    # warm-up: one whole loop of this cell's traffic compiles (or loads)
    # every program the window runs, and nothing else
    run_loop(cfg, plan, chunk_fn, plan.workers())
    gc.collect()

    loops: list[LoopRecord] = []
    first: Optional[np.ndarray] = None
    differing = failed = 0
    tracedir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    with Compiles() as comp, GcTime() as gct:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1      # the spans, not the runtime
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        tracing = trace
        elapsed = 0.0
        while elapsed < seconds:
            workers = plan.workers()
            # the last loop's garbage goes outside the makespans, so each
            # loop starts from the same heap
            gc.collect()
            gc0 = gct.seconds
            try:
                with jax.profiler.StepTraceAnnotation(SPAN_LOOP,
                                                      step_num=len(loops)):
                    rec, res = run_loop(cfg, plan, chunk_fn, workers)
            except Exception:
                traceback.print_exc()
                failed += 1
                break
            rec.gc_s = gct.seconds - gc0
            elapsed += rec.seconds
            loops.append(rec)
            if tracing and elapsed >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing = False
            if rec.hung or rec.n_finished != N or res is None:
                failed += 1
                continue
            # outside the makespan: every loop must equal the first
            if first is None:
                first = res
            elif not np.array_equal(res, first):
                differing += 1
            del res
        if tracing:
            jax.profiler.stop_trace()
    compiles = comp.n
    print("makespans_s " + json.dumps([r.seconds for r in loops]),
          file=sys.stderr, flush=True)
    print("gc_s " + json.dumps([r.gc_s for r in loops]), file=sys.stderr,
          flush=True)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    # the reference runs once the window is closed and the program's
    # loops are freed; it takes nothing the program made
    gc.collect()
    ref_mod = cell.module("reference", cfg["app"])
    reference = np.asarray(ref_mod.compute(cfg))
    if first is None:
        tasks_off = N
    else:
        tasks_off = int(np.any((first != reference).reshape(N, -1),
                               axis=1).sum())
    values = {"loops_failed": failed, "loops_differing": differing,
              "tasks_off": tasks_off}
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    correct = bool(loops) and all(v <= limits[k] for k, v in values.items())

    reduced = None
    if trace:
        reduced = trace_reduce.reduce_dir(tracedir, kernels=cfg["kernels"])
        shutil.rmtree(tracedir, ignore_errors=True)
    ctx = Context(cell=cell, setup_s=setup_s, loops=loops,
                  compiles_in_window=compiles, trace=reduced,
                  reference=reference,
                  device_kind=dev.device_kind,
                  work={k: cell.module("work", k) for k in cfg["kernels"]})
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    if loops:
        for m in cell.manifest[kind]:
            if not cell.applies(m):
                continue
            v = cell.module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out: dict[str, Any] = {"correct": correct, "attempted": len(loops),
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if reduced is not None:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = trace_reduce.breakdown(reduced)
    out["checks"] = checks
    return out
