"""A configuration, a traffic mix and a metric are added as new files and
new entries of BENCHMARK.json alone: the harness runs them with no edit
to any file the benchmark already has."""

import hashlib
import json

from chipbench import harness


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_config_traffic_and_metric_need_no_edit(tiny_root):
    before = _digests(tiny_root)
    bench = tiny_root / "chipbench"
    cfg = json.loads((bench / "configs" / "mandelbrot-t1.json").read_text())
    cfg.update(name="mandelbrot-wide", side=256, n_tasks=16, max_iters=16,
               P=2)
    (bench / "configs" / "mandelbrot-wide.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "gss-slow.json").write_text(json.dumps(
        {"technique": "GSS",
         "perturb": [{"count": 1, "worker": {"sleep_per_task": 0.001}}]}))
    (bench / "metrics" / "chunks_per_loop.py").write_text(
        "def read(ctx):\n"
        "    return sum(len(r.calls) for r in ctx.loops) / len(ctx.loops)\n")
    m = json.loads((tiny_root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "mandelbrot-wide",
                         "source": "https://arxiv.org/abs/1905.08073",
                         "file": "chipbench/configs/mandelbrot-wide.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "mandelbrot-wide.gss-slow",
                           "config": "mandelbrot-wide",
                           "traffic": "gss-slow", "chips": 1, "why": "test"})
    m["per_layer"].append({"name": "chunks_per_loop", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "loop_s",
                           "workloads": ["mandelbrot-wide.gss-slow"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = harness.load_cell(tiny_root, "mandelbrot-wide.gss-slow")
    out = harness.run_cell(cell, seed=3, seconds=0.2, trace=False,
                           t_start=0.0)
    assert out["correct"] is True
    assert {"loop_s", "setup_s"} <= set(out["metrics"])
    traced = harness.run_cell(cell, seed=3, seconds=0.2, trace=True,
                              t_start=0.0)
    assert traced["metrics"]["chunks_per_loop"]["value"] >= 2
    # an existing cell does not report the new metric
    old = harness.run_cell(harness.load_cell(tiny_root, "mandelbrot-ss"),
                           seed=3, seconds=0.1, trace=True, t_start=0.0)
    assert "chunks_per_loop" not in old["metrics"]
    after = _digests(tiny_root)
    assert {k: v for k, v in after.items() if k in before} == before
