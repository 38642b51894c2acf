"""The phases of ``chip_smoke.py`` at tiny size on the CPU.

On the chip the script runs them at full size (paper-sized loops,
olmo-1b at published widths, a P=256 simulator batch) and refuses to
start without a TPU; here each phase function runs through the same
entry points with the kernels in the Pallas interpreter.
"""

import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_smoke

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


@pytest.mark.parametrize("phase", ["paper_loops", "serving", "devicesim"])
def test_phase_at_tiny_size(phase):
    if phase == "paper_loops":
        mandel, spin = chip_smoke.paper_loops(
            side=64, tile=16, max_iters=32, psia_n=64, cloud_n=256, P=4)
        for rec in (mandel, spin):
            assert rec["equal_to_failure_free"]
            assert rec["failed_workers"] == 3
            assert rec["duplicates_failed"] > 0
        assert mandel["oracle_mismatch_pixels"] == 0
        assert spin["oracle_mismatch_bins"] == 0
    elif phase == "serving":
        rec = chip_smoke.serving(get_smoke("olmo-1b"), n_requests=8,
                                 prompt_len=8, max_new=4)
        assert rec["token_identical_to_healthy"] and not rec["hung"]
        assert rec["fused_vs_loop_agree"] == 8
    else:
        rec = chip_smoke.device_sim(P=8, N=256, B=16)
        assert rec["n_invalid"] == 0 and rec["max_rel_t_par_err"] <= 1e-9


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out
