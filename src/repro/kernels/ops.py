"""Public jitted wrappers for the Pallas kernels (the API the rest of the
framework calls).  Each kernel compiles with Mosaic when lowered for the
TPU and runs in the Pallas interpreter elsewhere
(``repro.kernels.dispatch.pallas_call``)."""

from __future__ import annotations

import jax

from repro.kernels.dispatch import status as kernel_status  # noqa: F401
from repro.kernels.flash_attention import (flash_attention,  # noqa: F401
                                           flash_decode)
from repro.kernels.mandelbrot import mandelbrot            # noqa: F401
from repro.kernels.rwkv6_scan import (wkv6, wkv6_batched,  # noqa: F401
                                      wkv6_decode)
from repro.kernels.spin_image import spin_image            # noqa: F401


def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True) -> jax.Array:
    """Multi-head convenience: q,k,v (B, S, H, D) -> (B, S, H, Dv)."""
    B, S, H, D = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, t.shape[-1])
    out = flash_attention(fold(q), fold(k), fold(v), causal=causal)
    return out.reshape(B, H, S, -1).transpose(0, 2, 1, 3)
