"""Profiler trace -> device busy time, kernel time and idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
What it holds, as one recorded chip trace (``testdata/``) shows it:

- each chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds
  one event per operation that ran, its line ``XLA Modules`` one event
  per program run (named ``jit_<function>(<fingerprint>)``);
- a Pallas kernel is an ``XLA Ops`` event whose name carries
  ``custom_call_target="tpu_custom_call"``; which kernel it is follows
  from the program around it (the configuration's ``kernels`` map each
  kernel to that program's name);
- the host plane ``/host:CPU`` holds the benchmark's spans: ``loop`` per
  parallel loop, ``backend.execute`` and ``backend.commit`` per chunk,
  on the lines of the threads that ran them, on the same clock.

Only time inside the ``loop`` spans counts: the traced window is their
union, so the checks the benchmark makes between loops are not idle
time of the system.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SPANS = ("loop", "backend.execute", "backend.commit")
ENGINE = "engine"


def _merge(iv):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(iv) -> float:
    return float(sum(e - s for s, e in iv))


def _clip(s, e, window) -> float:
    """Length of [s, e) inside the sorted disjoint ``window``."""
    k = max(0, bisect.bisect_right(window, (s, float("inf"))) - 1)
    total = 0.0
    while k < len(window) and window[k][0] < e:
        total += max(0.0, min(e, window[k][1]) - max(s, window[k][0]))
        k += 1
    return total


def _complement(window, busy):
    """Gaps of ``busy`` inside ``window`` (both sorted and disjoint)."""
    gaps, j = [], 0
    for ws, we in window:
        cur = ws
        while j < len(busy) and busy[j][1] <= ws:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < we:
            if busy[k][0] > cur:
                gaps.append((cur, busy[k][0]))
            cur = max(cur, busy[k][1])
            k += 1
        if cur < we:
            gaps.append((cur, we))
    return gaps


def _op_short(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _module_base(name: str) -> str:
    return name.split("(", 1)[0]


def reduce_dir(tracedir: str, *, kernels: dict) -> dict:
    paths = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tracedir}")
    return reduce_file(max(paths, key=os.path.getmtime), kernels=kernels)


def reduce_file(path: str, *, kernels: dict) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), kernels=kernels)


def reduce(profile, *, kernels: dict) -> dict:
    """Seconds of the traced window, of device work in it, of each
    kernel, of the host spans, and the idle time by host activity (each
    gap split by the spans it overlaps; a gap is named by the largest
    part).

    ``kernels`` maps a kernel's name to ``{"module": <program name>}``.
    """
    spans = defaultdict(list)
    devices = []
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    window = _merge(spans["loop"])
    window_s = _length(window) * 1e-9
    # what the host was doing, one thing at a time: a commit (under the
    # engine's lock) over an execute on another thread, and "engine" for
    # the rest of a loop
    commit = _merge(spans["backend.commit"])
    execute = _complement(_merge(spans["backend.execute"]), commit)

    by_module = {_module_base(v["module"]): k for k, v in kernels.items()}
    busy_total = 0.0
    n_busy = 0
    op_time: dict[str, float] = defaultdict(float)
    kernel_time: dict[str, float] = defaultdict(float)
    kernel_calls: dict[str, int] = defaultdict(int)
    busy_union_all = []
    for lines in devices:
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       _module_base(ev.name))
                      for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in mods]
        ops = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            inside = _clip(s, e, window)
            if inside <= 0.0:
                continue
            ops.append((s, e))
            k = bisect.bisect_right(starts, s) - 1
            # the op starts inside its program's run (the two ends may
            # differ by the nanosecond the trace rounds to)
            module = mods[k][2] if k >= 0 and mods[k][1] > s else "?"
            op_time[f"{module}/{_op_short(ev.name)}"] += inside
            if KERNEL_MARK in ev.name and module in by_module:
                kernel_time[by_module[module]] += inside
                kernel_calls[by_module[module]] += 1
        busy = _intersect(_merge(ops), window)
        if busy:
            n_busy += 1
            busy_total += _length(busy)
            busy_union_all.extend(busy)
    busy_s = busy_total * 1e-9 / max(1, n_busy)

    gaps = _complement(window, _merge(busy_union_all))
    labelled = []
    idle_by = defaultdict(float)
    for s, e in gaps:
        over = {"backend.commit": _clip(s, e, commit),
                "backend.execute": _clip(s, e, execute)}
        over[ENGINE] = (e - s) - sum(over.values())
        for k, v in over.items():
            idle_by[k] += v * 1e-9
        labelled.append((max(over, key=over.get), (e - s) * 1e-9))
    labelled.sort(key=lambda x: -x[1])
    idle_by = {k: v for k, v in idle_by.items() if v > 0.0}
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "n_devices": n_busy,
        "loops": len(spans["loop"]),
        "span_s": {k: _length(_intersect(_merge(v), window)) * 1e-9
                   for k, v in spans.items() if k != "loop"},
        "device_ops": {k: v * 1e-9 for k, v in op_time.items()},
        "kernels": {k: {"seconds": kernel_time[k] * 1e-9,
                        "calls": kernel_calls[k]} for k in kernel_time},
        "idle_by_host": dict(idle_by),
        "gaps": labelled[:10],
    }


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: the ten device operations that
    took most time, and the idle time by what the host was doing (totals
    as ``all:<span>``, then the longest single gaps)."""
    ops = sorted(reduced["device_ops"].items(), key=lambda kv: -kv[1])[:10]
    totals = sorted(reduced["idle_by_host"].items(), key=lambda kv: -kv[1])
    gaps = [[f"all:{k}", v] for k, v in totals]
    gaps += [[k, v] for k, v in reduced["gaps"]][:10 - len(gaps)]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps}
