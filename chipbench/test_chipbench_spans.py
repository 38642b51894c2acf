"""The program's spans on the profiler's clock.

On the CPU: a tiny threaded PSIA loop (P = 4, ``ChunkBackend`` under the
harness's span wrapper, the Pallas interpreter) traced by the profiler
with the flight recorder on.  On the chip: ``program_spans``'s reduction
of a trace recorded on one TPU v5e chip (``testdata/spans.xplane.pb.gz``:
one ``psia-fac-1survivor`` loop at N = 256 and P = 256, with the
program's spans, by ``program_spans.py record``; ``spans.loops.json``
holds its makespan and executed chunks).
The older trace (``testdata/loops.xplane.pb.gz``) holds no program span:
there the benchmark's metrics read as they always have."""

import glob
import gzip
import json
import math
import os

import pytest

from chipbench import harness, program_spans, trace_reduce
from chipbench.conftest import REPO, TINY

DATA = REPO / "chipbench" / "testdata"
KERNELS = {"mandelbrot": {"module": "jit_mandelbrot"},
           "spin_image": {"module": "jit__spin_images"}}
CHUNK_SPANS = ("chunk.dispatch", "chunk.to_host")
PER_LAYER = {"dispatch_us_per_chunk", "to_host_us_per_chunk",
             "request_us_per_chunk", "lock_wait_s_per_loop"}


def _profile(name: str):
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / name).read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def _events(profile, names):
    """(plane, line number, name, start_ns, end_ns) of every event named
    in ``names`` (a host line is a thread)."""
    return [(plane.name, k, ev.name, ev.start_ns,
             ev.start_ns + ev.duration_ns)
            for plane in profile.planes
            for k, line in enumerate(plane.lines)
            for ev in line.events if ev.name in names]


# --------------------------------------------------- a tiny loop, CPU
@pytest.fixture(scope="module")
def tiny_loop(tmp_path_factory):
    """(profile, executed chunks, recorder trace) of one traced loop."""
    import jax
    from jax.profiler import ProfileData
    from repro import api
    from repro.runtime import ChunkBackend
    cell = harness.load_cell(REPO, "psia-fac")
    cfg = {**cell.cfg, **TINY["psia-t1"]}
    N = cfg["n_tasks"]
    chunk_fn, prepare = cell.module("apps", cfg["app"]).bind(cfg)
    prepare()
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        robustness=api.RobustnessSpec(rdlb_enabled=True),
        cluster=api.ClusterSpec(n_workers=cfg["P"]),
        execution=api.ExecutionSpec(mode="threaded", trace=True),
        n_tasks=N)

    def loop():
        backend = harness.span_backend(ChunkBackend(chunk_fn, N))
        return backend, api.run(spec, api.build(spec, backend))

    loop()                          # compiles every chunk size
    tracedir = tmp_path_factory.mktemp("spans")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tracedir), profiler_options=opts)
    try:
        with jax.profiler.StepTraceAnnotation("loop", step_num=0):
            backend, stats = loop()
    finally:
        jax.profiler.stop_trace()
    assert stats.n_finished == N and not stats.hung
    path, = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                      recursive=True)
    return ProfileData.from_file(path), backend.calls, stats.trace


def test_every_program_span_is_on_the_host_plane(tiny_loop):
    prof, _, _ = tiny_loop
    evs = _events(prof, program_spans.PROGRAM_SPANS)
    assert {e[2] for e in evs} == set(program_spans.PROGRAM_SPANS)
    assert {e[0] for e in evs} == {trace_reduce.HOST_PLANE}


def test_chunk_spans_nest_inside_backend_execute(tiny_loop):
    prof, calls, _ = tiny_loop
    execs = {}
    for _, line, _, s, e in _events(prof, {"backend.execute"}):
        execs.setdefault(line, []).append((s, e))
    inside = {}
    for _, line, name, s, e in _events(prof, CHUNK_SPANS):
        outer = [x for x in execs.get(line, []) if x[0] <= s and e <= x[1]]
        assert len(outer) == 1, (name, line)
        inside.setdefault(outer[0], []).append(name)
    assert len(inside) == len(calls)
    # the steps of one chunk, in their order, once each
    assert all(names == list(CHUNK_SPANS) for names in inside.values())


def test_span_counts_are_the_executed_chunks(tiny_loop):
    prof, calls, _ = tiny_loop
    red = program_spans.reduce(prof)
    n = red["span_n"]
    for name in CHUNK_SPANS + ("engine.report", "engine.lock_wait"):
        assert n[name] == len(calls), name
    assert n["engine.run"] == 1
    assert n["engine.request"] >= len(calls)


def test_the_span_readers_return_numbers(tiny_loop):
    prof, _, _ = tiny_loop
    red = program_spans.reduce(prof)
    values = program_spans.per_layer(red)
    assert set(values) == PER_LAYER
    for name, v in values.items():
        assert math.isfinite(v) and v >= 0, name
    assert values["dispatch_us_per_chunk"] > 0
    # no device: the whole window is idle, and the spans name it
    assert sum(red["idle_by_program"].values()) == pytest.approx(
        red["window_s"], rel=1e-6)
    assert program_spans.idle_gaps(red)[0][0].startswith("all:")


def test_recorder_events_fall_on_their_dispatch_spans(tiny_loop):
    """``t`` of a threaded run's recorder event, from the ``engine.run``
    span's start, is the event's instant on the profiler's clock: each
    ``EV_EXEC`` interval, so placed, holds its own chunk's
    ``chunk.dispatch`` span, which starts just after it."""
    from repro.core import trace as trc
    prof, calls, rec = tiny_loop
    tol = 300e3                                     # ns
    (run_start, _), = [(s, e) for *_, s, e in
                       _events(prof, {"engine.run"})]
    spans = sorted((s, e) for *_, s, e in _events(prof, {"chunk.dispatch"}))
    is_exec = rec.kind == trc.EV_EXEC
    execs = sorted((run_start + t * 1e9, run_start + (t + dt) * 1e9)
                   for t, dt in zip(rec.t[is_exec], rec.dt[is_exec]))
    assert len(execs) == len(calls) == len(spans)
    # one dispatch span to each execution: executions taken by their
    # ends, each given the earliest free span inside it
    free = list(range(len(spans)))
    offsets = []
    for a, b in sorted(execs, key=lambda x: x[1]):
        fits = [j for j in free
                if a - tol <= spans[j][0] and spans[j][1] <= b + tol]
        assert fits, (a, b)
        free.remove(fits[0])
        offsets.append(spans[fits[0]][0] - a)
    offsets.sort()
    assert abs(offsets[len(offsets) // 2]) < tol


def test_record_keeps_the_trace_and_the_loops(tmp_path):
    line = program_spans.record("psia-fac", seed=2**33 + 7, loops=1,
                                sizes=TINY["psia-t1"], out=str(tmp_path))
    assert set(line["per_layer"]) == PER_LAYER
    assert len(line["makespans_s"]) == line["loops"] == 1
    loops = json.loads((tmp_path / "psia-fac.loops.json").read_text())
    (_, seconds, calls), = loops["loops"]
    assert seconds == line["makespans_s"][0]
    red = program_spans.reduce(program_spans.load(
        str(tmp_path / "psia-fac.xplane.pb.gz")))
    assert red["span_n"]["chunk.dispatch"] == len(calls)


# ------------------------------------------------ the idle split, rules
class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_idle_goes_to_the_first_open_span_in_the_order():
    host = _Plane("/host:CPU", [
        _Line("main", [_Ev("loop", 0, 100), _Ev("engine.run", 0, 100)]),
        _Line("w0", [_Ev("engine.lock_wait", 0, 40),
                     _Ev("engine.report", 40, 10),
                     _Ev("engine.request", 60, 20)]),
        _Line("w1", [_Ev("chunk.dispatch", 5, 25),
                     _Ev("chunk.to_host", 45, 20)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [_Ev("op", 20, 5)])])

    class Prof:
        planes = [host, dev]
    red = program_spans.reduce(Prof())
    # idle: [0,5) lock_wait; [5,20)+[25,30) dispatch (over lock_wait);
    # [30,40) lock_wait; [40,50) report (over to_host); [50,65) to_host;
    # [65,80) request; [80,100) engine
    assert red["idle_by_program"] == pytest.approx(
        {"engine.lock_wait": 15e-9, "chunk.dispatch": 20e-9,
         "engine.report": 10e-9, "chunk.to_host": 15e-9,
         "engine.request": 15e-9, "engine": 20e-9})
    assert red["idle_s"] == pytest.approx(95e-9)
    assert red["span_n"] == {"engine.run": 1, "engine.lock_wait": 1,
                             "engine.report": 1, "engine.request": 1,
                             "chunk.dispatch": 1, "chunk.to_host": 1}
    assert program_spans.per_layer(red) == pytest.approx({
        "dispatch_us_per_chunk": 25e-3, "to_host_us_per_chunk": 20e-3,
        "request_us_per_chunk": 20e-3, "lock_wait_s_per_loop": 40e-9})
    assert {row[0] for row in program_spans.idle_gaps(red)[:2]} == {
        "all:chunk.dispatch", "all:engine"}


# ------------------------------------------- a trace recorded on a chip
@pytest.fixture(scope="module")
def chip_trace():
    loops = json.loads((DATA / "spans.loops.json").read_text())["loops"]
    return _profile("spans.xplane.pb.gz"), loops


def test_chip_idle_by_program_and_busy_add_up_to_the_window(chip_trace):
    prof, _ = chip_trace
    red = program_spans.reduce(prof)
    old = trace_reduce.reduce(prof, kernels=KERNELS)
    assert red["window_s"] == old["window_s"]
    assert old["n_devices"] == 1 and 0 < old["busy_s"] < old["window_s"]
    idle = sum(red["idle_by_program"].values())
    assert idle + old["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert set(red["idle_by_program"]) <= set(program_spans.IDLE_ORDER) | {
        program_spans.ENGINE}


def test_chip_split_takes_the_window_and_gaps_trace_reduce_builds(
        chip_trace):
    """``split`` over the window and gaps built as ``trace_reduce.reduce``
    builds them (device by device, then their union) reads as
    ``reduce`` does over the trace's own planes."""
    prof, _ = chip_trace
    loops, spans, _ = program_spans.collect(prof)
    window = trace_reduce._merge(loops)
    busy = []
    for plane in prof.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for ev in line.events]
            busy += trace_reduce._intersect(trace_reduce._merge(ops),
                                            window)
    gaps = trace_reduce._complement(window, trace_reduce._merge(busy))
    got = program_spans.split(window, gaps, spans)
    want = program_spans.reduce(prof)
    assert want.pop("loops") == len(loops) == 1
    assert got == want


def test_chip_spans_and_kernel_calls_are_the_executed_chunks(chip_trace):
    prof, loops = chip_trace
    (_, _, calls), = loops
    red = program_spans.reduce(prof)
    for name in CHUNK_SPANS + ("engine.report",):
        assert red["span_n"][name] == len(calls), name
    old = trace_reduce.reduce(prof, kernels=KERNELS)
    assert old["kernels"]["spin_image"]["calls"] == len(calls)
    # the kernel goes by its own name, inside its program
    top, _ = trace_reduce.breakdown(old)["device_ops"][0]
    assert top.startswith("jit__spin_images/spin_image")
    assert set(program_spans.per_layer(red)) == PER_LAYER


# ----------------------------------------- the older trace, unchanged
def test_a_trace_without_program_spans_gives_no_split():
    red = program_spans.reduce(_profile("loops.xplane.pb.gz"))
    assert red["loops"] == 2 and red["span_n"] == {}
    assert red["idle_by_program"] == {} and red["gaps"] == []
    assert program_spans.per_layer(red) == {}
    assert program_spans.idle_gaps(red) == []


def test_the_benchmark_reads_the_older_trace_as_before():
    """The benchmark's own metrics and breakdown on the older trace, as
    they read before the program had spans."""
    red = trace_reduce.reduce(_profile("loops.xplane.pb.gz"),
                              kernels=KERNELS)
    loops = json.loads((DATA / "loops.json").read_text())["loops"]
    cell = harness.load_cell(REPO, "psia-fac")
    recs = [harness.LoopRecord(s, False, cell.cfg["n_tasks"],
                               [tuple(x) for x in calls])
            for name, s, calls in loops if name == "psia-fac"]
    ctx = harness.Context(cell=cell, setup_s=0.0, loops=recs,
                          compiles_in_window=0, trace=red, reference=None,
                          device_kind="TPU v5 lite",
                          work={"spin_image": cell.module("work",
                                                          "spin_image")})
    want = {"compiles_in_window": 0, "dup_task_share": 0.015,
            "commit_s_per_loop": 0.45964317450000003,
            "device_idle_share": 53.853722390271784,
            "spin_image_roofline": 0.06176529989999578}
    got = {m: cell.module("metrics", m).read(ctx) for m in want}
    assert got == pytest.approx(want, rel=1e-12)
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["jit__spin_images/branch_0_fun.1",
                                   pytest.approx(0.669804937, rel=1e-12)]
    assert bd["idle_gaps"][:3] == [
        ["all:backend.commit", pytest.approx(0.3596834570000048, rel=1e-9)],
        ["all:engine", pytest.approx(0.32094965100000006, rel=1e-9)],
        ["all:backend.execute", pytest.approx(0.10561937599999967,
                                              rel=1e-9)]]
