"""Benchmark harness: one module per paper table/figure + kernels +
roofline.  ``PYTHONPATH=src python -m benchmarks.run [--paper]``

Prints ``module,key,value`` CSV lines; full CSVs land in artifacts/bench/.
--paper uses the full Mandelbrot task count (slower); default is the
grouped quick mode (identical durations, fewer queue events).
--emit-json additionally writes machine-readable
``artifacts/bench/BENCH_<module>.json`` (timings + every result line +
best-effort key/value records) so the perf trajectory is diffable across
commits; ``scripts/ci.sh`` emits a small one every run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _records(lines: list[str]) -> list[dict]:
    """Best-effort parse of ``module,key,value[,...]`` lines into one
    record per line (a list, so multi-row series keep every point)."""
    out: list[dict] = []
    for line in lines:
        parts = line.split(",")
        kv = [p for p in parts[1:] if "=" in p]
        plain = [p for p in parts[1:] if "=" not in p]
        rec: dict = {"key": plain[0] if plain else parts[0]}
        for p in kv:
            k, _, v = p.partition("=")
            try:
                rec[k] = json.loads(v)
            except (ValueError, json.JSONDecodeError):
                rec[k] = v
        if len(plain) > 1:
            values = []
            for p in plain[1:]:
                try:
                    values.append(json.loads(p))
                except (ValueError, json.JSONDecodeError):
                    values.append(p)
            rec["values"] = values
        out.append(rec)
    return out


def provenance() -> dict:
    """Attribution stamp for every BENCH_*.json: which commit, when,
    where, on what stack.  Every field is best-effort — a bench emitted
    outside a git checkout or without jax still writes valid JSON."""
    import datetime
    import platform
    import subprocess
    prov: dict = {
        "date": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        prov["git_sha"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        prov["git_sha"] = None
    import jax
    import numpy
    prov["numpy"] = numpy.__version__
    prov["jax"] = jax.__version__
    return prov


def emit_json(name: str, lines: list[str], elapsed_s: float,
              error: str = "") -> str:
    from benchmarks import common
    common.ARTIFACTS.mkdir(parents=True, exist_ok=True)
    path = common.ARTIFACTS / f"BENCH_{name}.json"
    with open(path, "w") as f:
        json.dump(dict(module=name, elapsed_s=round(elapsed_s, 2),
                       lines=lines, records=_records(lines),
                       error=error, provenance=provenance()),
                  f, indent=2, sort_keys=True)
    return str(path)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", action="store_true",
                    help="full-scale Mandelbrot task count")
    ap.add_argument("--only", default="",
                    help="comma list of modules to run")
    ap.add_argument("--emit-json", action="store_true",
                    help="write artifacts/bench/BENCH_<module>.json")
    args = ap.parse_args(argv)
    quick = not args.paper

    from benchmarks import (decode_bench, fig3_performance,
                            fig4_resilience, fig5_flexibility,
                            fig_adaptive, fig_calibration, fig_cluster,
                            fig_scale, kernels_bench, roofline,
                            theory_table)
    modules = [
        ("fig3", fig3_performance),
        ("fig4", fig4_resilience),
        ("fig5", fig5_flexibility),
        ("fig_adaptive", fig_adaptive),
        ("fig_calibration", fig_calibration),
        ("fig_cluster", fig_cluster),
        ("fig_scale", fig_scale),
        ("theory", theory_table),
        ("kernels", kernels_bench),
        ("decode", decode_bench),
        ("roofline", roofline),
    ]
    if args.only:
        keep = set(args.only.split(","))
        modules = [(n, m) for n, m in modules if n in keep]

    failures = 0
    for name, mod in modules:
        t0 = time.time()
        lines, err = [], ""
        try:
            for line in mod.main(quick=quick):
                lines.append(line)
                print(line)
            print(f"{name},elapsed_s,{time.time() - t0:.1f}")
        except Exception as e:
            failures += 1
            err = f"{type(e).__name__}: {e}"
            print(f"{name},ERROR,{err}")
            traceback.print_exc()
        if args.emit_json:
            emit_json(name, lines, time.time() - t0, error=err)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
