"""Flash attention Pallas kernel (online softmax, causal block skip).

Grid (BH, n_q_blocks, n_kv_blocks): the first two axes are parallel, the
kv axis is sequential ("arbitrary") with the running (m, l, acc) state in
VMEM scratch — the canonical TPU flash tiling.  Block shapes default to
(128 q x 128 kv x Dh): MXU-aligned (128 lanes) and ~小 VMEM footprint
(q/k/v blocks + f32 acc ~ 128*Dh*(2*3+4) bytes).

Causal skip: kv blocks strictly above the diagonal contribute nothing;
the body is wrapped in pl.when so those grid steps do no FLOPs — on
hardware this halves the attention compute vs. the masked-full variant
(the §Perf hillclimb measures exactly this on the lowered HLO of the
pure-JAX twin in repro.models.attention.flash_attend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.dispatch import pallas_call

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, causal: bool, bq: int, bk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    run = (not causal) or (kj * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)                   # (bq, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0].astype(jnp.float32)                   # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * bq + jnp.arange(bq)
            k_pos = kj * bk + jnp.arange(bk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))        # (bq,)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=-1)
        acc_s[...] = (acc_s[...] * corr[:, None]
                      + jax.lax.dot_general(
                          p, v, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
        m_s[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_s, l_s, acc_s,
                   *, scale: float):
    """q_len=1 flash decode: one query row against kv-cache blocks.  The
    causal structure lives in ``valid`` (per-slot admissibility computed
    from the cache's absolute positions — handles rolling sliding-window
    slots, unwritten slots and the current token uniformly), so the kernel
    itself is position-agnostic."""
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                   # (1, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)                   # (bk, dv)
    ok = valid_ref[...] != 0                           # (1, bk)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok, s, NEG_INF)                      # (1, bk)
    m_prev = m_s[...]                                  # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # a fully-masked block leaves m_new at NEG_INF; exp(s - m_new) would
    # be exp(0)=1 there, so re-zero masked probabilities explicitly
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_s[...] = (acc_s[...] * corr
                  + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    m_s[...] = m_new

    @pl.when(kj == pl.num_programs(1) - 1)
    def _():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 valid: jax.Array, *, scale: float | None = None,
                 bk: int = 128) -> jax.Array:
    """Decode-variant flash attention: q (B, D) single-token queries vs a
    KV cache k/v (B, L, D|Dv) with a shared (L,) validity mask (int/bool;
    nonzero = slot participates).  Returns (B, Dv).  Each query row is a
    (1, 1, D) block of a (B, 1, D) view, which meets the TPU's tiling
    where a (1, D) block of (B, D) does not."""
    B, D = q.shape
    L, Dv = k.shape[1], v.shape[-1]
    bk = min(bk, L)
    assert L % bk == 0, (L, bk)
    scale = scale if scale is not None else D ** -0.5
    valid2 = valid.astype(jnp.int32)[None, :]           # (1, L)
    out = pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=(B, L // bk),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, Dv), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dv), jnp.float32),
        ],
    )(q[:, None, :], k, v, valid2)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """q,k,v: (B, S, D) per-head layout -> (B, S, Dv)."""
    B, S, D = q.shape
    Dv = v.shape[-1]
    bq, bk = min(bq, S), min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = scale if scale is not None else D ** -0.5
    return pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk),
        grid=(B, S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
    )(q, k, v)
