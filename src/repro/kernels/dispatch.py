"""Kernel dispatch: how a Pallas kernel is lowered, and which path ran.

``pallas_call`` compiles a kernel with Mosaic when the program is lowered
for the TPU and runs the same kernel in the Pallas interpreter on every
other platform.  The choice is a fact of the lowering platform
(``lax.platform_dependent``), not an option: a compile for a described
TPU takes the Mosaic path even in a CPU-only process.

The model layers select Pallas kernels behind ``ModelConfig.use_kernel``
with a jnp twin.  Every selection site records its outcome here, and
``status()`` exposes the chosen path so benchmarks/tests can assert on
what actually executed.  A kernel error is never caught: it propagates
to the caller.
"""

from __future__ import annotations

import threading

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)``, compiled (Mosaic) when lowered
    for the TPU and interpreted when lowered for any other platform."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)
    return call


_lock = threading.Lock()
_STATUS: dict[str, dict] = {}


def record(site: str, path: str) -> None:
    """Record that ``site`` (e.g. "wkv6", "gqa_decode") ran ``path``
    ("pallas" | "jnp")."""
    with _lock:
        _STATUS[site] = {"path": path}


def status(site: str | None = None) -> dict:
    """Latest path per site: {site: {"path": ...}}, or one site's record
    (empty dict if it never ran)."""
    with _lock:
        snap = {s: dict(st) for s, st in _STATUS.items()}
    return snap.get(site, {}) if site is not None else snap


def reset() -> None:
    """Forget everything (tests)."""
    with _lock:
        _STATUS.clear()
