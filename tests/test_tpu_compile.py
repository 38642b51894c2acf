"""Compile the main-path kernels for a described TPU v5e chip.

Nothing runs here: each test lowers a kernel (or the device simulator's
batch program) at the widths the system uses on the chip and compiles it
with the TPU compiler that ships with JAX, for one chip of a v5e topology
that is described and not attached.  A Mosaic refusal (unaligned block,
unsupported relayout, too much VMEM) fails here instead of on the chip.
Each kernel test also asserts that the kernel is really in the compiled
program (a ``tpu_custom_call``), i.e. that it was not interpreted.

The topology is described inside a module-scoped fixture: only the test
process that runs these tests loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps import mandelbrot as mandel_app
from repro.apps import psia
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile() \
        .as_text()


@pytest.mark.parametrize("side,tile", [(mandel_app.SIDE, 128),
                                       (64, 64)])
def test_mandelbrot_compiles(one_chip, side, tile):
    hlo = _hlo(ops.mandelbrot, one_chip, ((side, side), jnp.float32),
               ((side, side), jnp.float32),
               max_iters=mandel_app.MAX_ITERS, bm=tile, bn=tile)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("tile,pixels", [(1, 512), (64, 64 * 64)])
def test_mandelbrot_chunk_compiles(one_chip, tile, pixels):
    """The runtime's chunk program: a FAC chunk of 512 one-pixel tasks,
    and one 64 x 64 tile, each in its lane-dense slab."""
    flat = ((mandel_app.SIDE ** 2,), jnp.float32)
    hlo = _hlo(mandel_app.mandelbrot_chunk, one_chip, flat, flat,
               ((), jnp.int32), n=mandel_app.slab_pixels(pixels),
               tile=tile, max_iters=mandel_app.MAX_ITERS)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bo", [1, 16, 9, 39])
def test_spin_image_compiles(one_chip, bo):
    hlo = _hlo(ops.spin_image, one_chip, ((psia.CLOUD, 3), jnp.float32),
               ((bo, 3), jnp.float32), ((bo, 3), jnp.float32),
               n_alpha=psia.N_ALPHA, n_beta=psia.N_BETA, alpha_max=3.0,
               beta_max=3.0, block_p=psia.BLOCK_P)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    s = ((32, 1024, 128), jnp.bfloat16)
    hlo = _hlo(ops.flash_attention, one_chip, s, s, s, causal=True)
    assert "tpu_custom_call" in hlo


def test_flash_decode_compiles(one_chip):
    # olmo-1b decode: B=8 rows x 16 heads, head_dim 128, 1024 cache slots
    kv = ((128, 1024, 128), jnp.bfloat16)
    hlo = _hlo(ops.flash_decode, one_chip, ((128, 128), jnp.bfloat16), kv,
               kv, ((1024,), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_wkv6_batched_compiles(one_chip):
    # rwkv6-1.6b prefill: B=8 x 32 heads of 64, prompt 128
    x = ((256, 128, 64), jnp.bfloat16)
    hlo = _hlo(ops.wkv6_batched, one_chip, x, x, x, x,
               ((256, 64), jnp.bfloat16), ((256, 64, 64), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_wkv6_decode_compiles(one_chip):
    x = ((256, 64), jnp.bfloat16)
    hlo = _hlo(ops.wkv6_decode, one_chip, x, x, x, x, x,
               ((256, 64, 64), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_devicesim_batch_compiles_in_float64(one_chip):
    """One Monte-Carlo batch program of the device simulator (P=256,
    N=2^15, 512 elements), in float64 as it runs on the chip."""
    from repro.core import devicesim
    from repro import api
    P, N, B = 256, 1 << 15, 512
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="SS"),
        cluster=api.ClusterSpec(n_workers=P),
        execution=api.ExecutionSpec(mode="virtual", h=1e-6))
    lo, why = devicesim.lower_run(spec, np.full(N, 0.01))
    assert lo is not None, why
    with jax.enable_x64(True):
        fn, args = devicesim.batch_program(
            [lo], np.zeros(B, np.int32), np.full((B, P), np.inf), "sorted")
        compiled = fn.lower(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args)).compile()
    assert "f64" in compiled.as_text()
