"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------------------- mandelbrot
def mandelbrot(c_real: jax.Array, c_imag: jax.Array,
               max_iters: int) -> jax.Array:
    """Escape-time counts (int32), same semantics as the kernel: the count
    is the number of iterations before |z|^2 exceeded 4 (max_iters if
    bounded)."""
    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        escaped = zr2 + zi2 > 4.0
        nzr = zr2 - zi2 + c_real
        nzi = 2.0 * zr * zi + c_imag
        zr = jnp.where(escaped, zr, nzr)
        zi = jnp.where(escaped, zi, nzi)
        cnt = cnt + jnp.where(escaped, 0, 1).astype(jnp.int32)
        return zr, zi, cnt
    zr = jnp.zeros_like(c_real)
    zi = jnp.zeros_like(c_imag)
    cnt = jnp.zeros(c_real.shape, jnp.int32)
    _, _, cnt = jax.lax.fori_loop(0, max_iters, body, (zr, zi, cnt))
    return cnt


# -------------------------------------------------------------- spin image
def spin_image(points: jax.Array, centers: jax.Array, normals: jax.Array,
               *, n_alpha: int, n_beta: int, alpha_max: float,
               beta_max: float) -> jax.Array:
    """Spin images (Johnson 97 / PSIA): for each oriented point (center,
    normal), histogram the cloud in (alpha, beta) cylinder coordinates.

    points: (Np, 3); centers/normals: (Bo, 3) -> (Bo, n_beta, n_alpha)."""
    dx, dy, dz = (points[None, :, k] - centers[:, k, None]
                  for k in range(3))                          # (Bo,Np)
    nx, ny, nz = (normals[:, k, None] for k in range(3))
    beta = (dx * nx + dy * ny) + dz * nz
    r2 = (dx * dx + dy * dy) + dz * dz
    alpha = jnp.sqrt(jnp.maximum(r2 - beta * beta, 0.0))
    ai = jnp.floor(alpha / alpha_max * n_alpha).astype(jnp.int32)
    bi = jnp.floor((beta + beta_max) / (2 * beta_max)
                   * n_beta).astype(jnp.int32)
    valid = ((ai >= 0) & (ai < n_alpha) & (bi >= 0) & (bi < n_beta))
    a_oh = jax.nn.one_hot(jnp.where(valid, ai, 0), n_alpha,
                          dtype=jnp.float32) * valid[..., None]
    b_oh = jax.nn.one_hot(jnp.where(valid, bi, 0), n_beta,
                          dtype=jnp.float32) * valid[..., None]
    return jnp.einsum("bpj,bpa->bja", b_oh, a_oh)           # (Bo,nb,na)


# -------------------------------------------------------------- attention
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, scale: float | None = None) -> jax.Array:
    """Exact softmax attention. q,k,v: (B, S, D) (already per-head)."""
    S = q.shape[-2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------------ wkv6
def wkv6(r, k, v, w, u, state):
    """Sequential RWKV6 recurrence (per head).  r,k,w: (T, dk); v: (T, dv);
    u: (dk,); state: (dk, dv) fp32.  Returns (y (T, dv) fp32, state)."""
    r, k, v, w = (x.astype(jnp.float32) for x in (r, k, v, w))
    u = u.astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[:, None] * v_t[None, :]
        y = ((S + u[:, None] * kv) * r_t[:, None]).sum(0)
        S = w_t[:, None] * S + kv
        return S, y

    state, y = jax.lax.scan(step, state.astype(jnp.float32), (r, k, v, w))
    return y, state


def attention_decode(q, k, v, valid, *, scale=None):
    """Exact single-token attention over a KV cache (flash_decode oracle).
    q: (B, D); k: (B, L, D); v: (B, L, Dv); valid: (L,) bool -> (B, Dv)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bd,bld->bl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bl,bld->bd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
