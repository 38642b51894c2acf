"""The trace reduction on a small trace recorded on one TPU v5e chip
(``testdata/loops.xplane.pb.gz``: one ``mandelbrot-fac-slow1`` loop and
one ``psia-fac`` loop, with the harness's spans; ``loops.json`` holds
their makespans and executed chunks), and its interval arithmetic."""

import gzip
import json

import pytest

from chipbench import harness, trace_reduce
from chipbench.conftest import REPO

DATA = REPO / "chipbench" / "testdata"
KERNELS = {"mandelbrot": {"module": "jit_mandelbrot"},
           "spin_image": {"module": "jit__spin_images"}}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / "loops.xplane.pb.gz").read_bytes())
    prof = ProfileData.from_serialized_xspace(raw)
    loops = json.loads((DATA / "loops.json").read_text())["loops"]
    return trace_reduce.reduce(prof, kernels=KERNELS), loops


def test_window_is_the_loop_spans(recorded):
    red, loops = recorded
    makespans = sum(s for _, s, _ in loops)
    assert red["loops"] == len(loops)
    assert makespans <= red["window_s"] <= 1.05 * makespans


def test_busy_and_idle_add_up(recorded):
    red, _ = recorded
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(red["idle_by_host"].values())
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert set(red["idle_by_host"]) <= {"backend.execute", "backend.commit",
                                        "engine"}


def test_one_kernel_launch_per_executed_chunk(recorded):
    red, loops = recorded
    want = {"mandelbrot": 0, "spin_image": 0}
    for name, _, calls in loops:
        want["mandelbrot" if name.startswith("mandelbrot") else
             "spin_image"] += len(calls)
    assert {k: v["calls"] for k, v in red["kernels"].items()} == want
    kernel_s = sum(v["seconds"] for v in red["kernels"].values())
    assert 0 < kernel_s <= red["busy_s"]


def test_spans_inside_the_window(recorded):
    red, _ = recorded
    assert set(red["span_s"]) == {"backend.execute", "backend.commit"}
    for v in red["span_s"].values():
        assert 0 < v <= red["window_s"]


def test_breakdown_shape(recorded):
    red, _ = recorded
    bd = trace_reduce.breakdown(red)
    assert set(bd) == {"device_ops", "idle_gaps"}
    for rows in bd.values():
        assert 1 <= len(rows) <= 10
        for name, secs in rows:
            assert isinstance(name, str) and isinstance(secs, float)
    top = bd["device_ops"][0][0]
    assert top.startswith("jit__spin_images/")
    assert bd["idle_gaps"][0][0].startswith("all:")


@pytest.mark.parametrize("cell, kernel", [
    ("mandelbrot-fac-slow1", "mandelbrot"), ("psia-fac", "spin_image")])
def test_rooflines_from_the_recorded_trace_stay_under_100(recorded, cell,
                                                          kernel,
                                                          bench_root):
    red, loops = recorded
    c = harness.load_cell(bench_root, cell)
    recs = [harness.LoopRecord(s, False, c.cfg["n_tasks"],
                               [tuple(x) for x in calls])
            for name, s, calls in loops if name == cell]
    ref = None
    if kernel == "mandelbrot":
        ref = c.module("reference", "mandelbrot").compute(c.cfg)
    ctx = harness.Context(cell=c, setup_s=0.0, loops=recs,
                          compiles_in_window=0, trace=red, reference=ref,
                          device_kind="TPU v5 lite",
                          work={kernel: c.module("work", kernel)})
    share = c.module("metrics", f"{kernel}_roofline").read(ctx)
    assert 0 < share <= 100


def test_unknown_device_kind_is_an_error(recorded):
    red, loops = recorded
    c = harness.load_cell(REPO, "psia-fac-1survivor")
    ctx = harness.Context(cell=c, setup_s=0.0, loops=[], trace=red,
                          compiles_in_window=0, reference=None,
                          device_kind="TPU v99", work={})
    with pytest.raises(KeyError, match="TPU v99"):
        ctx.peaks


def test_interval_arithmetic():
    merged = trace_reduce._merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert trace_reduce._intersect(merged, [(2, 6), (8, 20)]) == [
        (2, 3), (5, 6), (8, 9)]
    assert trace_reduce._complement([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace_reduce._complement([(0, 4), (10, 12)], [(3, 11)]) == [
        (0, 3), (11, 12)]
    assert trace_reduce._clip(1, 8, merged) == 2 + 3


def test_a_trace_without_a_device_reads_no_device_time(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.StepTraceAnnotation("loop", step_num=0):
        with jax.profiler.TraceAnnotation("backend.execute"):
            jnp.arange(8.0).sum().block_until_ready()
    jax.profiler.stop_trace()
    red = trace_reduce.reduce_dir(str(tmp_path), kernels=KERNELS)
    assert red["loops"] == 1 and red["window_s"] > 0
    assert red["n_devices"] == 0 and red["busy_s"] == 0.0
    assert red["kernels"] == {}


def test_idle_time_is_split_by_what_the_host_did():
    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    host = Plane("/host:CPU", [
        Line("main", [Ev("loop", 0, 100)]),
        Line("w0", [Ev("backend.execute", 0, 30), Ev("backend.commit", 40,
                                                      20)]),
        Line("w1", [Ev("backend.execute", 45, 10)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_f(1)", 10, 10)]),
        Line("XLA Ops", [Ev("op", 10, 10)])])

    class Prof:
        planes = [host, dev]
    red = trace_reduce.reduce(Prof(), kernels={})
    assert red["busy_s"] == pytest.approx(10e-9)
    # idle: [0,10) and [20,30) execute; [30,40) engine; [40,60) commit
    # (the execute on w1 lies under it); [60,100) engine
    assert red["idle_by_host"] == pytest.approx(
        {"backend.execute": 20e-9, "backend.commit": 20e-9,
         "engine": 50e-9})
    assert [g[0] for g in red["gaps"]] == ["engine", "backend.execute"]
