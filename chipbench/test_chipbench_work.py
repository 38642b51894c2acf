"""The work counters against hand counts at tiny shapes, and the
references at tiny shapes against the counts they imply."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.conftest import REPO


def _work(name):
    return harness.load_module(REPO / "chipbench" / "work" / f"{name}.py",
                               f"work_{name}")


def _reference(name):
    return harness.load_module(
        REPO / "chipbench" / "reference" / f"{name}.py", f"ref_{name}")


def test_spin_image_hand_count():
    w = _work("spin_image")
    cfg = {"cloud_n": 8, "n_alpha": 4, "n_beta": 2}
    ops, nbytes = w.call(cfg, 3, 5, None)
    assert ops == 25 * 2 * 8                     # 25 per pair, 16 pairs
    assert nbytes == 4 * (8 * 3 + 2 * 6 + 2 * 4 * 2)


def test_spin_image_full_loop_count():
    w = _work("spin_image")
    cfg = json.loads((REPO / "chipbench/configs/psia-t1.json").read_text())
    ops, nbytes = w.call(cfg, 0, 20000, None)
    assert ops == 25 * 20000 * 16384
    assert nbytes == 4 * (16384 * 3 + 20000 * 6 + 20000 * 4096)


def test_mandelbrot_hand_count():
    w = _work("mandelbrot")
    ref = np.zeros((4, 2, 2), np.int32)
    ref[1] = [[3, 0], [1, 256]]
    ref[2] = 5
    ops, nbytes = w.call({"tile": 2}, 1, 3, ref)
    assert ops == 10 * (3 + 1 + 256 + 4 * 5)
    assert nbytes == 12 * 2 * 4


def test_psia_reference_bins_every_point_once():
    ref = _reference("psia")
    cfg = {"cloud_seed": 0, "points_seed": 1, "n_tasks": 3, "cloud_n": 50,
           "n_alpha": 4, "n_beta": 4, "alpha_max": 100.0, "beta_max": 100.0}
    out = ref.compute(cfg)
    assert out.shape == (3, 4, 4)
    np.testing.assert_array_equal(out.sum(axis=(1, 2)), 50)


def test_psia_reference_hand_case():
    ref = _reference("psia")
    pts = jnp.array([[0.0, 0.0, 0.5], [1.5, 0.0, -0.5], [9.0, 0, 0]])
    ctr = jnp.zeros((1, 3))
    nrm = jnp.array([[0.0, 0.0, 1.0]])
    out = np.asarray(ref.spin_images(pts, ctr, nrm, n_alpha=2, n_beta=2,
                                     alpha_max=2.0, beta_max=1.0,
                                     dtype=jnp.float32))
    # (alpha 0, beta 0.5) -> bin (1, 0); (alpha 1.5, beta -0.5) -> (0, 1);
    # the third point lies outside the cylinder
    np.testing.assert_array_equal(out[0], [[0, 1], [1, 0]])


def test_mandelbrot_reference_hand_case():
    ref = _reference("mandelbrot")
    cr = jnp.array([[0.0, 2.0, -2.0, 1.0]])
    ci = jnp.zeros((1, 4))
    out = np.asarray(ref.escape_counts(cr, ci, max_iters=10,
                                       dtype=jnp.float32))
    # 0 never escapes; 2: z1 = 2 (|z|^2 = 4, not > 4), z2 = 6 -> 2;
    # -2 stays at 2 forever; 1: 1, 2, 5 -> 3
    np.testing.assert_array_equal(out[0], [10, 2, 10, 3])


@pytest.mark.parametrize("name", ["psia", "mandelbrot"])
def test_reference_chunk_fn_matches_compute(name, tiny_root):
    cfg_name = {"psia": "psia-t1", "mandelbrot": "mandelbrot-t1"}[name]
    cfg = json.loads((tiny_root / "chipbench" / "configs"
                      / f"{cfg_name}.json").read_text())
    ref = _reference(name)
    whole = ref.compute(cfg)
    fn = ref.chunk_fn(cfg)
    np.testing.assert_array_equal(fn(1, 3), whole[1:3])
