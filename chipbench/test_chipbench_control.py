"""What decides ``correct``, shown to fail: the control (the plain
reference in the program's place, computed in bfloat16 instead of the
float32 the configurations state) and faults planted in the timed path
underneath a whole run.  At a tiny size on the CPU; the same control at
the cells' own sizes is ``control.py`` on the chip."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.conftest import REPO, copy_bench

CELLS = ["psia-fac-1survivor", "mandelbrot-ss", "psia-fac",
         "mandelbrot-fac-slow1"]


def _run(root, cell, seconds=0.1, **kw):
    c = harness.load_cell(root, cell)
    return harness.run_cell(c, seed=7, seconds=seconds, trace=False,
                            t_start=0.0, **kw)


def _program(root, cell):
    c = harness.load_cell(root, cell)
    fn, prepare = c.module("apps", c.cfg["app"]).bind(c.cfg)
    prepare()
    return c, fn


@pytest.mark.parametrize("cell", ["psia-fac", "mandelbrot-ss"])
def test_control_in_lower_precision_is_not_correct(tiny_root, cell):
    c = harness.load_cell(tiny_root, cell)
    ref = c.module("reference", c.cfg["app"])
    out = _run(tiny_root, cell, chunk_fn=ref.chunk_fn(c.cfg, jnp.bfloat16))
    assert out["correct"] is False
    assert out["checks"]["tasks_off"]["value"] > 0


@pytest.mark.parametrize("cell", ["psia-fac", "mandelbrot-ss"])
def test_reference_in_the_programs_place_is_correct(tiny_root, cell):
    c = harness.load_cell(tiny_root, cell)
    ref = c.module("reference", c.cfg["app"])
    out = _run(tiny_root, cell, chunk_fn=ref.chunk_fn(c.cfg))
    assert out["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(tiny_root, cell):
    c, fn = _program(tiny_root, cell)

    def altered(start, stop):
        rows = np.array(fn(start, stop))
        if start == 0:
            rows[0].flat[0] += 1
        return rows
    out = _run(tiny_root, cell, chunk_fn=altered)
    assert out["correct"] is False
    assert out["checks"]["tasks_off"]["value"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_tasks_left_out(tiny_root, cell, monkeypatch):
    from repro.runtime import ChunkBackend
    commit = ChunkBackend.commit

    def half(self, chunk, wid, payload, newly):
        commit(self, chunk, wid, payload, [t for t in newly if t % 2])
    monkeypatch.setattr(ChunkBackend, "commit", half)
    out = _run(tiny_root, cell)
    assert out["correct"] is False


@pytest.mark.parametrize("cell", ["psia-fac-1survivor", "mandelbrot-ss"])
def test_commit_that_leaves_results_unchanged(tiny_root, cell, monkeypatch):
    from repro.runtime import ChunkBackend

    def nothing(self, chunk, wid, payload, newly):
        if self.results is None:
            self.results = np.zeros((self.n_tasks,) + payload.shape[1:],
                                    payload.dtype)
    monkeypatch.setattr(ChunkBackend, "commit", nothing)
    out = _run(tiny_root, cell)
    assert out["correct"] is False


def test_one_loop_that_differs_is_caught(tiny_root, monkeypatch):
    c, fn = _program(tiny_root, "mandelbrot-ss")
    loops = {"n": 0}
    run_loop = harness.run_loop

    def counting(*args):
        loops["n"] += 1             # 1: warm-up, 2: first timed loop
        return run_loop(*args)

    def flaky(start, stop):
        rows = np.array(fn(start, stop))
        if loops["n"] == 3 and start == 0:
            rows[0].flat[0] += 1
        return rows
    monkeypatch.setattr(harness, "run_loop", counting)
    out = _run(tiny_root, "mandelbrot-ss", seconds=1.0, chunk_fn=flaky)
    assert out["attempted"] >= 3
    assert out["correct"] is False
    assert out["checks"]["loops_differing"]["value"] == 1
    assert out["checks"]["tasks_off"]["value"] == 0


def test_a_loop_that_raises_is_a_failure(tiny_root):
    c, fn = _program(tiny_root, "mandelbrot-ss")
    calls = {"n": 0}

    def broken(start, stop):
        calls["n"] += 1
        if calls["n"] > 2 * c.cfg["n_tasks"]:       # after the warm-up
            raise RuntimeError("kernel failed")
        return fn(start, stop)
    out = _run(tiny_root, "mandelbrot-ss", chunk_fn=broken)
    assert out["correct"] is False
    assert out["failed"] == 1


def _command(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "psia-fac-1survivor",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_to_run_without_a_tpu():
    res = _command(REPO)
    assert res.returncode == 2
    assert res.stdout == ""
    assert "TPU" in res.stderr


def test_command_fails_without_the_program(tmp_path):
    copy_bench(tmp_path)
    res = _command(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
