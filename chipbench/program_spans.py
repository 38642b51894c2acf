#!/usr/bin/env python3
"""The program's own spans in a profiler trace: the steps of the chunk
path, and the device's idle time split by them.

The program opens spans under fixed names (``repro.core.trace.span``;
the docstring of ``repro/core/trace.py`` lists them: ``engine.run``,
``engine.request``, ``engine.lock_wait``, ``engine.report``,
``chunk.dispatch``, ``chunk.to_host``), on the
``/host:CPU`` plane, on the line of the thread that ran them and on the
device's clock.

As in ``trace_reduce``, only time inside the ``loop`` spans counts.
``split`` takes the window and the device-idle gaps as
``trace_reduce.reduce`` builds them, with the program's spans, and
gives, for each name, the union of its spans in that window
(``span_s``), their thread-seconds (``span_thread_s``) and their count
(``span_n``), and splits every gap across the spans open in it
(``idle_by_program``).  ``reduce`` is ``split`` over a trace's own
planes (``collect``).  Where spans on several threads overlap, the
first open span in ``IDLE_ORDER`` takes the time:

1. ``engine.report``: it holds the commit lock, so while it is open every
   other worker's report waits for it;
2. ``chunk.dispatch``, ``chunk.to_host``: the chunk path
   in the order a chunk takes it; the earliest step open is the one the
   device's next work waits for;
3. ``engine.request``: the queue, between two chunks of a worker;
4. ``engine.lock_wait``: last, since it only waits for another thread's
   report; it takes a gap only where no report is open, the lock's
   hand-off.

What no program span covers is ``engine``: the rest of the engine's
loop, ``api.build``, starting and joining the threads.

    python3 chipbench/program_spans.py reduce <trace.xplane.pb[.gz]>...
    python3 chipbench/program_spans.py record --workload <cell> \\
        --seed <n> --loops <k> [--n-tasks <N>] [--workers <P>] \\
        [--out <dir>]

``reduce`` prints each trace's split and span metrics as one JSON line;
``record`` runs a warm-up loop and then ``k`` traced loops of a cell
(the harness's loop, at the cell's sizes or with ``N`` tasks and ``P``
workers), prints the same line with ``trace_reduce``'s breakdown beside
it, and with ``--out`` keeps the trace (gzipped) and the loops'
makespans and chunks.

``collect``, ``reduce`` and ``record`` repeat the plane walk and the
traced loops of ``trace_reduce`` and ``harness``: they go once
``trace_reduce.reduce`` collects the program's spans and calls ``split``
itself.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import SPAN_LOOP  # noqa: E402
from chipbench.trace_reduce import (DEVICE_PREFIX, HOST_PLANE,  # noqa: E402
                                    _clip, _complement, _intersect,
                                    _length, _merge)

IDLE_ORDER = ("engine.report", "chunk.dispatch", "chunk.to_host",
              "engine.request", "engine.lock_wait")
PROGRAM_SPANS = ("engine.run",) + IDLE_ORDER
ENGINE = "engine"
# (metric, span): the mean of one span, in microseconds
MEAN_US = (("dispatch_us_per_chunk", "chunk.dispatch"),
           ("to_host_us_per_chunk", "chunk.to_host"),
           ("request_us_per_chunk", "engine.request"))


def _starts_inside(t: int, window) -> bool:
    k = bisect.bisect_right(window, (t, float("inf"))) - 1
    return k >= 0 and window[k][0] <= t < window[k][1]


def collect(profile):
    """The ``loop`` spans, the program's spans by name and the device's
    operations of ``profile`` (a ``jax.profiler.ProfileData``), each as
    ``(start_ns, end_ns)`` intervals."""
    loops, spans, ops = [], defaultdict(list), []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name == SPAN_LOOP:
                        loops.append(iv)
                    elif ev.name in PROGRAM_SPANS:
                        spans[ev.name].append(iv)
        elif plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events)
    return loops, spans, ops


def reduce(profile) -> dict:
    """The program's spans in the ``loop`` window of ``profile`` (a
    ``jax.profiler.ProfileData``) and the device's idle time by them."""
    loops, spans, ops = collect(profile)
    window = _merge(loops)
    red = split(window, _complement(window, _merge(ops)), spans)
    red["loops"] = len(loops)
    return red


def split(window, gaps, spans) -> dict:
    """The program's ``spans`` (name -> intervals) inside ``window``, and
    the device-idle ``gaps`` split across them: the window and gaps as
    ``trace_reduce.reduce`` builds them (sorted, disjoint, in ns)."""
    idle_s = _length(gaps) * 1e-9
    # each name's part of the timeline: where it is open and no span
    # before it in IDLE_ORDER is
    own, taken = {}, []
    for name in IDLE_ORDER:
        own[name] = _complement(_merge(spans.get(name, [])), taken)
        taken = _merge(taken + own[name])
    idle_by = {name: _length(_intersect(gaps, iv)) * 1e-9
               for name, iv in own.items()}
    idle_by[ENGINE] = idle_s - sum(idle_by.values())
    labelled = []
    for s, e in gaps:
        part = {name: _clip(s, e, iv) for name, iv in own.items()}
        part[ENGINE] = (e - s) - sum(part.values())
        labelled.append((max(part, key=part.get), (e - s) * 1e-9))
    labelled.sort(key=lambda x: -x[1])

    span_s, span_thread_s, span_n = {}, {}, {}
    for name, iv in spans.items():
        n = sum(_starts_inside(s, window) for s, _ in iv)
        if not n:
            continue
        span_n[name] = n
        span_thread_s[name] = sum(_clip(s, e, window) for s, e in iv) * 1e-9
        span_s[name] = _length(_intersect(_merge(iv), window)) * 1e-9
    return {
        "window_s": _length(window) * 1e-9,
        "idle_s": idle_s,
        "span_s": span_s,
        "span_thread_s": span_thread_s,
        "span_n": span_n,
        "idle_by_program": ({k: v for k, v in idle_by.items() if v > 0.0}
                            if span_n else {}),
        "gaps": labelled[:10] if span_n else [],
    }


def per_layer(red: dict) -> dict:
    """The per-layer numbers the spans give: the mean dispatch, copy to
    the host and queue request (microseconds per span: a request that
    finds no chunk counts too), and the thread-seconds waiting for the
    commit lock per loop."""
    n, secs = red["span_n"], red["span_thread_s"]
    out = {metric: 1e6 * secs[name] / n[name]
           for metric, name in MEAN_US if n.get(name)}
    if red["loops"] and "engine.lock_wait" in n:
        out["lock_wait_s_per_loop"] = secs["engine.lock_wait"] / red["loops"]
    return out


def idle_gaps(red: dict) -> list:
    """The idle time by program span, as ``all:<span>`` totals, then the
    longest single gaps by the span that took most of each; empty for a
    trace without the program's spans."""
    totals = sorted(red["idle_by_program"].items(), key=lambda kv: -kv[1])
    rows = [[f"all:{k}", v] for k, v in totals]
    return rows + [[k, v] for k, v in red["gaps"]][:10 - len(rows)]


def load(path: str):
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    if path.endswith(".gz"):
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def summary(red: dict) -> dict:
    return {"window_s": red["window_s"], "idle_s": red["idle_s"],
            "loops": red["loops"], "per_layer": per_layer(red),
            "idle_gaps": idle_gaps(red), "span_n": red["span_n"],
            "span_thread_s": red["span_thread_s"]}


def record(workload: str, *, seed: int, loops: int, sizes=None,
           out=None) -> dict:
    """Warm-up, then ``loops`` traced loops of the cell, its
    configuration updated with ``sizes``; the trace's summary (and with
    ``out`` the trace and the loops, kept there)."""
    import gc

    import jax

    from chipbench import harness, trace_reduce
    from chipbench import traffic as traffic_gen
    cell = harness.load_cell(ROOT, workload)
    cfg = {**cell.cfg, **(sizes or {})}
    plan = traffic_gen.Plan(cell.mix, cfg, seed)
    chunk_fn, prepare = cell.module("apps", cfg["app"]).bind(cfg)
    harness.enable_compile_cache()
    prepare()
    harness.run_loop(cfg, plan, chunk_fn, plan.workers())
    tracedir = tempfile.mkdtemp(prefix="program-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    recs = []
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    for i in range(loops):
        workers = plan.workers()
        gc.collect()
        with jax.profiler.StepTraceAnnotation(SPAN_LOOP, step_num=i):
            rec, _ = harness.run_loop(cfg, plan, chunk_fn, workers)
        recs.append(rec)
    jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    line = summary(reduce(load(path)))
    old = trace_reduce.reduce_file(path, kernels=cfg["kernels"])
    line.update(cell=workload, n_tasks=cfg["n_tasks"],
                device=jax.devices()[0].device_kind,
                makespans_s=[r.seconds for r in recs],
                busy_s=old["busy_s"], kernels=old["kernels"],
                breakdown=trace_reduce.breakdown(old))
    if out:
        os.makedirs(out, exist_ok=True)
        with open(path, "rb") as src, \
                gzip.open(os.path.join(out, f"{workload}.xplane.pb.gz"),
                          "wb", compresslevel=9) as dst:
            shutil.copyfileobj(src, dst)
        Path(out, f"{workload}.loops.json").write_text(json.dumps(
            {"loops": [[workload, r.seconds, r.calls] for r in recs]}))
    shutil.rmtree(tracedir, ignore_errors=True)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("reduce")
    r.add_argument("traces", nargs="+")
    rec = sub.add_parser("record")
    rec.add_argument("--workload", required=True)
    rec.add_argument("--seed", type=int, required=True)
    rec.add_argument("--loops", type=int, required=True)
    rec.add_argument("--n-tasks", type=int)
    rec.add_argument("--workers", type=int)
    rec.add_argument("--out")
    args = ap.parse_args(argv)
    if args.cmd == "reduce":
        for path in args.traces:
            print(json.dumps({"trace": path, **summary(reduce(load(path)))}),
                  flush=True)
    else:
        sizes = {k: v for k, v in (("n_tasks", args.n_tasks),
                                   ("P", args.workers)) if v}
        print(json.dumps(record(args.workload, seed=args.seed,
                                loops=args.loops, sizes=sizes,
                                out=args.out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
