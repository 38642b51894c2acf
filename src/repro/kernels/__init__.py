"""Pallas TPU kernels for the framework's compute hot-spots.

mandelbrot        escape-time iteration (the paper's high-variance app)
spin_image        PSIA spin-image binning as MXU one-hot matmuls
flash_attention   online-softmax attention with causal block skip
rwkv6_scan        chunked WKV6 recurrence (state in VMEM scratch)

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jitted public
wrapper in ``ops.py``.  Kernels compile with Mosaic when lowered for the
TPU and run in the Pallas interpreter elsewhere (``dispatch.pallas_call``):
the CPU tests sweep shapes/dtypes through the interpreter, and
tests/test_tpu_compile.py compiles them for a described v5e chip.
"""

from repro.kernels import ops, ref  # noqa: F401
