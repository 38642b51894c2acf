"""Chunked RWKV6 (WKV) recurrence as a Pallas kernel.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t v_t^T ;
               y_t = r_t^T (S_t-1 + diag(u) k_t v_t^T)
is sequential, which maps terribly onto the MXU if done step-by-step.
TPU adaptation: the CHUNKED-PARALLEL form (same math) — within a chunk of
C steps the interaction is a strictly-lower-triangular (C x C) matmul with
per-channel cumulative decay, plus a rank-C state update; across chunks a
(dk x dv) f32 state carried in VMEM scratch.

Grid (B*H, n_chunks): heads parallel, chunks sequential.  Chunk 32 keeps
the in-chunk cumulative log-decay within fp32 exp range for realistic
decay magnitudes (see models.rwkv6.wkv6_chunked — the jnp twin).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.dispatch import pallas_call


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, state_ref, y_ref, s_out_ref,
            s_s, *, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        s_s[...] = state_ref[0]

    rr = r_ref[0].astype(jnp.float32)                  # (C, dk)
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)                  # (C, dv)
    ww = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                   # (1, dk)
    C = chunk

    lw = jnp.log(jnp.maximum(ww, 1e-38))
    # cumulative log-decay prod_{<=t} as a lower-triangular matmul (Mosaic
    # has no cumsum), in full f32 precision
    incl = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
            ).astype(jnp.float32)
    la = jax.lax.dot_general(incl, lw, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    la_prev = la - lw                                  # prod_{<t}
    r_hat = rr * jnp.exp(la_prev)
    k_hat = kk * jnp.exp(-la)
    scores = jax.lax.dot_general(r_hat, k_hat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    tri = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])
    inner = jax.lax.dot_general(jnp.where(tri, scores, 0.0), vv,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    diag = ((rr * u) * kk).sum(-1, keepdims=True) * vv
    cross = jax.lax.dot_general(r_hat, s_s[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = inner + diag + cross

    # whole-chunk decay, as a (dk, 1) column for the row scaling of S
    decay_all = jnp.exp(jax.lax.dot_general(
        lw, jnp.ones((C, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))           # (dk, 1)
    k_tail = kk * jnp.exp(la[C - 1:C, :] - la)         # (C, dk)
    s_s[...] = (decay_all * s_s[...]
                + jax.lax.dot_general(k_tail, vv, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = s_s[...]


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_batched(r, k, v, w, u, state, *, chunk: int = 32):
    """Batched heads — the PREFILL entry: every (batch, head) pair is one
    grid row, so the whole layer runs in a single ``pallas_call`` instead
    of a vmapped per-head launch.  r,k,w: (BH, T, dk); v: (BH, T, dv);
    u: (BH, dk); state: (BH, dk, dv) f32.
    Returns (y (BH, T, dv) f32, final state (BH, dk, dv) f32) — the state
    output is what lets the serve path chain prefill -> fused decode."""
    BH, T, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    return pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(BH, T // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, T, dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(r, k, v, w, u[:, None, :], state)


def _decode_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, state_ref,
                   y_ref, s_out_ref):
    """C=1 degenerate case of ``_kernel``: the strictly-lower-triangular
    in-chunk matmul vanishes, leaving one rank-1 state update and one
    (1, dk) x (dk, dv) contraction — y = r (S + diag(u) k v^T);
    S' = diag(w) S + k v^T.  k, w and u arrive as (dk, 1) columns so the
    outer product and the diagonal scalings are plain broadcasts."""
    rr = r_ref[0].astype(jnp.float32)                  # (1, dk)
    kk = k_ref[0].astype(jnp.float32)                  # (dk, 1)
    vv = v_ref[0].astype(jnp.float32)                  # (1, dv)
    ww = w_ref[0].astype(jnp.float32)                  # (dk, 1)
    u = u_ref[0].astype(jnp.float32)                   # (dk, 1)
    S = state_ref[0]                                   # (dk, dv) f32
    kv = kk * vv                                       # (dk, dv)
    y_ref[0] = jax.lax.dot_general(
        rr, S + u * kv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (1, dv)
    s_out_ref[0] = ww * S + kv


@jax.jit
def wkv6_decode(r, k, v, w, u, state):
    """Single-step fused WKV6 state update (the serving decode step).
    r,k,w,u: (BH, dk); v: (BH, dv); state: (BH, dk, dv) f32.
    Returns (y (BH, dv) f32, new state (BH, dk, dv) f32).  Per-row
    vectors are passed as (BH, 1, x) rows or (BH, dk, 1) columns: their
    blocks then span the full trailing dims, as the TPU's tiling needs."""
    BH, dk = r.shape
    dv = v.shape[-1]
    row = lambda t: t[:, None, :]
    col = lambda t: t[:, :, None]
    y, s_new = pallas_call(
        _decode_kernel,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, 1, dk), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dv), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b: (b, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, 1, dv), jnp.float32),
                   jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32)],
    )(row(r), col(k), row(v), col(w), col(u), state)
    return y[:, 0], s_new


def wkv6(r, k, v, w, u, state, *, chunk: int = 32):
    """Single-head convenience twin of models.rwkv6.wkv6_chunked:
    r,k,w: (T, dk); v: (T, dv); u: (dk,); state: (dk, dv).
    Returns (y (T, dv), final_state f32)."""
    y, final = wkv6_batched(r[None], k[None], v[None], w[None], u[None],
                            state[None].astype(jnp.float32), chunk=chunk)
    return y[0].astype(r.dtype), final[0]
