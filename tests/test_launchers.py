"""End-to-end launcher tests: the train driver (with failures + restart)
and a reduced-scale dry-run in a subprocess (512-dev flag isolation)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
ENV = {**os.environ, "PYTHONPATH": str(REPO / "src")}


def test_train_driver_with_failures(tmp_path):
    from repro.launch.train import main
    losses = main([
        "--arch", "olmo-1b", "--smoke", "--steps", "6",
        "--global-batch", "8", "--seq-len", "32", "--n-workers", "4",
        "--n-tasks", "8", "--fail", "2:1", "--ckpt-dir",
        str(tmp_path / "ck"), "--ckpt-interval", "2",
    ])
    assert len(losses) == 6
    assert losses[-1] < losses[0]


def test_train_driver_nordlb_hang_restarts(tmp_path):
    """Without rDLB a failure hangs the step; the driver falls back to
    checkpoint/restart (the §3.1 baseline) and still finishes."""
    from repro.launch.train import main
    losses = main([
        "--arch", "olmo-1b", "--smoke", "--steps", "5",
        "--global-batch", "8", "--seq-len", "32", "--no-rdlb",
        "--fail", "3:1", "--ckpt-dir", str(tmp_path / "ck"),
        "--ckpt-interval", "1",
    ])
    assert len(losses) >= 5


def test_serve_driver():
    from repro.launch.serve import main
    stats = main(["--arch", "olmo-1b", "--smoke", "--requests", "4",
                  "--n-workers", "2", "--prompt-len", "4",
                  "--max-new-tokens", "2", "--fail-worker", "1"])
    assert not stats.hung


@pytest.mark.slow
def test_dryrun_subprocess_smoke(tmp_path):
    """Reduced dry-run in a subprocess: forces 16 host devices and lowers
    a smoke config on a (4,4) mesh for train+prefill+decode."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax
from repro.configs import get_smoke, input_specs, Shape
from repro.launch.steps import make_train_step, make_serve_step
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke("qwen3-4b")
with mesh:
    ts = make_train_step(cfg, mesh, num_microbatches=2)
    sh = Shape("t", 64, 16, "train")
    specs = input_specs(cfg, sh, ts.model)
    pa = ts.model.abstract()
    oa = jax.eval_shape(ts.opt.init, pa)
    c = ts.jit(specs, donate=False).lower(pa, oa, specs).compile()
    ca = c.cost_analysis()
    assert ca["flops"] > 0
    ss = make_serve_step(cfg, mesh)
    sd = input_specs(cfg, Shape("d", 64, 16, "decode"), ss.model)
    ss.jit_decode(sd["cache"], donate=False).lower(
        pa, sd["cache"], sd["tokens"], sd["pos"]).compile()
print("DRYRUN_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert "DRYRUN_OK" in r.stdout, r.stderr[-2000:]
