"""The ``mandelbrot-px-fac`` cell (one-pixel tasks, ``mandelbrot-px``) on
a copy of the benchmark with its configuration shrunk to a size the
Pallas interpreter runs in seconds: it runs correct, what decides
``correct`` fails the bfloat16 control and a planted wrong pixel, and a
traced run reports the Mandelbrot roofline in this cell alone."""

import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, trace_reduce
from chipbench.conftest import REPO, TINY, copy_bench

CELL = "mandelbrot-px-fac"
SHRUNK = {"mandelbrot-px": {"side": 64, "n_tasks": 4096, "P": 4,
                            "max_iters": 32},
          "psia-t1": TINY["psia-t1"]}


def _url(source):
    """The URL a source names, without its fragment: words after it, or a
    part of the page, do not make another document."""
    return re.match(r"\S+", source).group().rstrip(",;.").split("#")[0]


def test_each_configuration_names_its_own_source():
    """Two deployments of one paper are two configurations only if the
    URLs of their sources or their cuts differ."""
    configs = json.loads((REPO / "BENCHMARK.json").read_text())["configs"]
    keys = [(_url(c["source"]), tuple(sorted(c["reduced"])))
            for c in configs]
    assert len(keys) == len(set(keys))
    px = next(c for c in configs if c["name"] == "mandelbrot-px")
    assert px["source"].endswith("#nameddest=table.1")


@pytest.fixture
def px_root(tmp_path):
    root = copy_bench(tmp_path)
    for name, sizes in SHRUNK.items():
        f = root / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(f.read_text())
        cfg.update(sizes)
        f.write_text(json.dumps(cfg))
    return root


def _run(root, cell=CELL, *, trace=False, **kw):
    return harness.run_cell(harness.load_cell(root, cell), seed=2**31 + 29,
                            seconds=0.2, trace=trace, t_start=0.0, **kw)


def test_one_pixel_tasks_run_correct(px_root):
    out = _run(px_root)
    assert out["correct"] is True
    assert {k: c["value"] for k, c in out["checks"].items()} == {
        "loops_failed": 0, "loops_differing": 0, "tasks_off": 0}
    assert {"loop_s", "setup_s"} <= set(out["metrics"])


def test_bfloat16_control_is_not_correct(px_root):
    c = harness.load_cell(px_root, CELL)
    ref = c.module("reference", "mandelbrot")
    out = _run(px_root, chunk_fn=ref.chunk_fn(c.cfg, jnp.bfloat16))
    assert out["correct"] is False
    assert out["checks"]["tasks_off"]["value"] > 0


def test_a_planted_wrong_pixel_is_caught(px_root):
    c = harness.load_cell(px_root, CELL)
    fn, prepare = c.module("apps", "mandelbrot").bind(c.cfg)
    prepare()
    wrong = 1234

    def planted(start, stop):
        px = np.array(fn(start, stop))
        if start <= wrong < stop:
            px[wrong - start] += 1
        return px
    out = _run(px_root, chunk_fn=planted)
    assert out["correct"] is False
    assert out["checks"]["tasks_off"]["value"] == 1


def test_traced_run_reports_the_mandelbrot_roofline_here_only(
        px_root, monkeypatch):
    """The CPU has no device plane, so the trace is given a time for each
    kernel; which cells read it is the manifest's ``workloads`` lists."""
    reduce_dir = trace_reduce.reduce_dir

    def with_kernels(tracedir, *, kernels):
        red = reduce_dir(tracedir, kernels=kernels)
        red["kernels"] = {k: {"seconds": 1.0, "calls": 1}
                          for k in ("mandelbrot", "spin_image")}
        return red
    peaks = json.loads((px_root / "chipbench" / "peaks.json").read_text())
    monkeypatch.setattr(trace_reduce, "reduce_dir", with_kernels)
    monkeypatch.setattr(harness.Context, "peaks", property(
        lambda self: peaks["devices"]["TPU v5 lite"]))

    out = _run(px_root, trace=True)
    assert out["correct"] is True
    assert "spin_image_roofline" not in out["metrics"]
    assert 0 < out["metrics"]["mandelbrot_roofline"]["value"] < 100
    psia = _run(px_root, "psia-fac", trace=True)
    assert "mandelbrot_roofline" not in psia["metrics"]
    assert "spin_image_roofline" in psia["metrics"]
