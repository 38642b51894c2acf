"""Attention variants for the assigned architectures.

GQA/MQA     qwen2/qwen3/olmo/deepseek-coder/paligemma/whisper/hymba
  - optional QKV bias (qwen2), qk-norm (qwen3), sliding window (hymba)
MLA         deepseek-v2/v3 multi-head latent attention
  - train/prefill: expand compressed kv and run standard attention
  - decode: ABSORBED form — attention runs directly over the compressed
    c_kv cache (rank 512) + shared rope keys (64), never materializing
    per-head K/V for the whole context.  Cache cost per token is
    (kv_lora_rank + rope_head_dim) elements vs 2·H·Dh for GQA — the
    memory-side reason MLA exists; we reproduce it because it changes the
    decode roofline terms materially.

Full-sequence paths take a mask mode ("causal" | "prefix") and an optional
window; decode paths take a cache pytree and the current position.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import (ParamSpec, apply_rope, constrain, dense,
                                 dense_specs, rms_norm)
from repro.models.config import ModelConfig

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ------------------------------------------------------------------- masks
def causal_mask(sq: int, sk: int, *, offset: int = 0,
                window: int = 0, prefix_len: int = 0) -> jax.Array:
    """(sq, sk) boolean mask. offset = absolute position of query 0 minus
    key 0 (for decode-style partial queries). window>0 = sliding window.
    prefix_len>0 = bidirectional attention within the first prefix_len keys
    (PaliGemma prefix-LM)."""
    q_pos = jnp.arange(sq)[:, None] + offset
    k_pos = jnp.arange(sk)[None, :]
    m = q_pos >= k_pos
    if window > 0:
        m &= (q_pos - k_pos) < window
    if prefix_len > 0:
        m |= k_pos < prefix_len
    return m


def _attend(q, k, v, mask, scale, *, scores_bf16: bool = False) -> jax.Array:
    """q:(B,Sq,H,Dh) k,v:(B,Sk,H,Dh) mask broadcastable to (B,H,Sq,Sk).

    K/V are pre-repeated to H heads (GQA replication = what TP does anyway),
    so every einsum shards cleanly over the "heads"->model axis.

    scores_bf16 (§Perf knob, default off): materialize the S^2 score /
    probability tensors in bf16 — halves the dominant HBM traffic of
    dense attention.  Row max is still subtracted in fp32 (the softmax
    shift), so only the probability mantissae lose precision; acceptable
    for inference, documented risk for training."""
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if scores_bf16:
        m = jnp.max(jnp.where(mask, scores, NEG_INF), axis=-1, keepdims=True)
        s16 = jnp.where(mask, scores - m, NEG_INF).astype(jnp.bfloat16)
        p = jnp.exp(s16.astype(jnp.float32)).astype(jnp.bfloat16)
        denom = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        probs = (p / denom.astype(jnp.bfloat16)).astype(v.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


def _repeat_kv(k: jax.Array, g: int) -> jax.Array:
    return jnp.repeat(k, g, axis=2) if g > 1 else k


# ------------------------------------------------- chunked (flash) attention
FLASH_THRESHOLD = 8192      # default; ModelConfig.flash_threshold overrides
Q_CHUNK = 1024
KV_CHUNK = 1024


def _chunk_for(S: int, target: int = Q_CHUNK) -> int:
    """Largest divisor of S that is <= target (handles e.g. hymba's 4224)."""
    c = min(target, S)
    while S % c:
        c -= 1
    return c


def flash_attend(q, k, v, scale, *, window: int = 0, prefix_len: int = 0,
                 causal: bool = True, causal_skip: bool = False,
                 q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK):
    """Memory-efficient attention: O(S * chunk) peak instead of O(S^2).

    q,k,v: (B,S,H,Dh) (k/v already repeated to H heads).  Pure-JAX online
    softmax — the same tiling the Pallas kernel (repro.kernels.
    flash_attention) performs in VMEM on real TPU; this path keeps the
    dry-run memory analysis honest for the 32k cells.  The baseline scans
    ALL kv chunks per q chunk (masked); the causal-skip variant
    (`causal_skip=True` in ops) is a §Perf hillclimb change.
    """
    B, S, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    nq, nk = S // q_chunk, Sk // kv_chunk
    assert S % q_chunk == 0 and Sk % kv_chunk == 0
    qs = q.transpose(1, 0, 2, 3).reshape(nq, q_chunk, B, H, D)
    ks = k.transpose(1, 0, 2, 3).reshape(nk, kv_chunk, B, H, D)
    vs = v.transpose(1, 0, 2, 3).reshape(nk, kv_chunk, B, H, Dv)

    def q_block(args, n_kv: int = None):
        qi, qb = args                                   # (), (qc,B,H,D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, args2):
            m, l, acc = carry
            ki, kb, vb = args2
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("qbhd,kbhd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            if prefix_len > 0:
                mask |= k_pos[None, :] < prefix_len
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))           # (B,H,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,kbhd->bhqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, H, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, Dv), jnp.float32)
        n = nk if n_kv is None else n_kv
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(n), ks[:n], vs[:n]))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                      # (B,H,qc,D)

    if causal_skip and causal and prefix_len == 0 and q_chunk == kv_chunk:
        # triangle schedule: q chunk i only scans kv chunks 0..i — halves
        # the FLOPs/traffic of the masked-full baseline (the Pallas kernel
        # does the same with pl.when).  Outer loop unrolled (nq is small).
        outs = [q_block((jnp.int32(i), qs[i]), n_kv=i + 1)
                for i in range(nq)]
        outs = jnp.stack(outs)                          # (nq,B,H,qc,Dv)
    else:
        outs = jax.lax.map(q_block, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, Dv)
    return out


# ==================================================================== GQA
def gqa_specs(cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    dh, h, kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    dt = cfg.param_dtype
    s = {
        "q": dense_specs(d, h * dh, ("embed", "heads"), bias=cfg.qkv_bias,
                         dtype=dt),
        "k": dense_specs(d, kv * dh, ("embed", "kv_heads"),
                         bias=cfg.qkv_bias, dtype=dt),
        "v": dense_specs(d, kv * dh, ("embed", "kv_heads"),
                         bias=cfg.qkv_bias, dtype=dt),
        "o": dense_specs(h * dh, d, ("heads", "embed"), dtype=dt),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=dt)
        s["k_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=dt)
    return s


def _gqa_qkv(p, cfg: ModelConfig, x, positions, rope: bool = True):
    B, S, _ = x.shape
    dh, h, kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense(p["q"], x).reshape(B, S, h, dh)
    k = dense(p["k"], x).reshape(B, S, kv, dh)
    v = dense(p["v"], x).reshape(B, S, kv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, cfg: ModelConfig, x, positions, *,
                window: int = 0, prefix_len: int = 0,
                rope: bool = True,
                kv_override: Optional[tuple] = None) -> jax.Array:
    """Full-sequence (train / prefill) GQA.  kv_override supplies external
    K/V (whisper cross-attention) already shaped (B,Sk,Kv,Dh)."""
    B, S, _ = x.shape
    dh, h, kvh = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = h // kvh
    q, k, v = _gqa_qkv(p, cfg, x, positions, rope=rope)
    if kv_override is not None:
        k, v = kv_override
        mask = jnp.ones((S, k.shape[1]), dtype=bool)       # cross: no mask
    else:
        mask = None
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(_repeat_kv(k, g), ("batch", "seq", "heads", None))
    v = constrain(_repeat_kv(v, g), ("batch", "seq", "heads", None))
    thresh = getattr(cfg, "flash_threshold", FLASH_THRESHOLD)
    if mask is None and S >= thresh:
        c = _chunk_for(S)
        out = flash_attend(q, k, v, dh ** -0.5, window=window,
                           prefix_len=prefix_len, q_chunk=c, kv_chunk=c,
                           causal_skip=getattr(cfg, "flash_causal_skip",
                                               False))
    else:
        if mask is None:
            mask = causal_mask(S, S, window=window, prefix_len=prefix_len)
        out = _attend(q, k, v, mask, dh ** -0.5,
                      scores_bf16=getattr(cfg, "attn_scores_bf16", False))
    out = constrain(out.reshape(B, S, h * dh), ("batch", "seq", "heads"))
    return dense(p["o"], out)


def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   window: int = 0) -> dict:
    """Cache pytree (abstract-friendly). Rolling buffer when window>0."""
    L = min(window, max_len) if window > 0 else max_len
    dh, kv = cfg.head_dim, cfg.n_kv_heads
    dt = cfg.param_dtype
    return {
        "k": jnp.zeros((batch, L, kv, dh), dt),
        "v": jnp.zeros((batch, L, kv, dh), dt),
        "pos": jnp.full((L,), -1, jnp.int32),   # absolute pos held per slot
    }


def gqa_decode(p, cfg: ModelConfig, x, cache: dict, pos: jax.Array, *,
               window: int = 0, rope: bool = True,
               cross_kv: Optional[tuple] = None):
    """One-token decode. x: (B,1,D); pos: scalar absolute position.

    With ``cfg.use_kernel`` the cache attention runs through the Pallas
    ``flash_decode`` kernel (q_len=1 online softmax over kv-cache blocks,
    the per-slot validity mask standing in for the causal structure); the
    jnp ``_attend`` path below is its parity oracle.  The path taken is
    recorded in repro.kernels.dispatch; a kernel error propagates."""
    B = x.shape[0]
    dh, h, kvh = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = h // kvh
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = _gqa_qkv(p, cfg, x, positions, rope=rope)
    valid = None
    if cross_kv is not None:
        k, v = cross_kv
        mask = jnp.ones((1, 1, 1, k.shape[1]), dtype=bool)
        new_cache = cache
    else:
        L = cache["k"].shape[1]
        slot = pos % L if window > 0 else pos
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, 1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, 1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((1,), pos, jnp.int32), slot, 0)
        new_cache = {"k": k, "v": v, "pos": cpos}
        valid = (cpos >= 0) & (cpos <= pos)
        if window > 0:
            valid &= cpos > pos - window
        mask = valid[None, None, None, :]
    if (getattr(cfg, "use_kernel", False) and valid is not None
            and k.shape[1] % min(128, k.shape[1]) == 0):
        from repro.kernels import dispatch
        from repro.kernels.flash_attention import flash_decode
        L = k.shape[1]
        kf = _repeat_kv(k, g).transpose(0, 2, 1, 3).reshape(B * h, L, dh)
        vf = _repeat_kv(v, g).transpose(0, 2, 1, 3).reshape(B * h, L, dh)
        out = flash_decode(q.reshape(B * h, dh), kf, vf, valid,
                           scale=dh ** -0.5, bk=min(128, L))
        dispatch.record("gqa_decode", "pallas")
        return dense(p["o"], out.reshape(B, 1, h * dh)), new_cache
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(_repeat_kv(k, g), ("batch", "cache_seq", "heads", None))
    v = constrain(_repeat_kv(v, g), ("batch", "cache_seq", "heads", None))
    out = _attend(q, k, v, mask, dh ** -0.5)
    out = out.reshape(B, 1, h * dh)
    return dense(p["o"], out), new_cache


def gqa_prefill(p, cfg: ModelConfig, x, cache: dict, *, pos_offset: int = 0,
                window: int = 0, rope: bool = True):
    """Prompt prefill into an EMPTY decode cache: one full-sequence causal
    (+ sliding-window) pass that writes the same K/V values the per-token
    ``gqa_decode`` loop would, S positions at once.  This is what turns
    the serve path's prompt walk (S sequential decode steps) into a
    single parallel pass.

    x: (B,S,D).  ``pos_offset`` shifts absolute positions exactly like
    the decode path does (vlm patch prefix / hymba meta tokens — those
    slots stay unwritten with pos -1, matching a decode loop that never
    fed them); slot assignment follows the same ``pos % L`` rolling rule.
    Returns (attn_out (B,S,D), filled cache)."""
    B, S, _ = x.shape
    dh, h, kvh = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    g = h // kvh
    abs_pos = pos_offset + jnp.arange(S)
    positions = jnp.broadcast_to(abs_pos[None], (B, S))
    q, k, v = _gqa_qkv(p, cfg, x, positions, rope=rope)
    L = cache["k"].shape[1]
    nkeep = min(S, L)                       # rolling window keeps the tail
    keep = np.arange(pos_offset + S - nkeep, pos_offset + S)
    slots = keep % L if window > 0 else keep
    ck = cache["k"].at[:, slots].set(k[:, -nkeep:].astype(cache["k"].dtype))
    cv = cache["v"].at[:, slots].set(v[:, -nkeep:].astype(cache["v"].dtype))
    cpos = cache["pos"].at[slots].set(jnp.asarray(keep, jnp.int32))
    new_cache = {"k": ck, "v": cv, "pos": cpos}
    mask = causal_mask(S, S, window=window)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(_repeat_kv(k, g), ("batch", "seq", "heads", None))
    v = constrain(_repeat_kv(v, g), ("batch", "seq", "heads", None))
    out = _attend(q, k, v, mask, dh ** -0.5)
    out = out.reshape(B, S, h * dh)
    return dense(p["o"], out), new_cache


# ==================================================================== MLA
def mla_specs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    c, qc = cfg.kv_lora_rank, cfg.q_lora_rank
    dt = cfg.param_dtype
    s: dict = {
        # compressed kv path: d -> (c_kv || k_rope)
        "dkv": dense_specs(d, c + dr, ("embed", "kv_lora"), dtype=dt),
        "kv_norm": ParamSpec((c,), (None,), init="ones", dtype=dt),
        "uk": ParamSpec((c, h, dn), ("kv_lora", "heads", None), dtype=dt),
        "uv": ParamSpec((c, h, dv), ("kv_lora", "heads", None), dtype=dt),
        "o": dense_specs(h * dv, d, ("heads", "embed"), dtype=dt),
    }
    if qc > 0:   # v3: compressed q
        s["dq"] = dense_specs(d, qc, ("embed", "q_lora"), dtype=dt)
        s["q_norm"] = ParamSpec((qc,), (None,), init="ones", dtype=dt)
        s["uq"] = ParamSpec((qc, h, dn + dr), ("q_lora", "heads", None),
                            dtype=dt)
    else:        # v2-lite: direct q
        s["q"] = ParamSpec((d, h, dn + dr), ("embed", "heads", None),
                           dtype=dt)
    return s


def _mla_q(p, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    h = cfg.n_heads
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank > 0:
        cq = rms_norm(dense(p["dq"], x), p["q_norm"])
        q = jnp.einsum("bsq,qhd->bshd", cq, p["uq"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["q"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope      # (B,S,H,dn), (B,S,H,dr)


def _mla_ckv(p, cfg: ModelConfig, x, positions):
    c = cfg.kv_lora_rank
    ckv_kr = dense(p["dkv"], x)
    c_kv = rms_norm(ckv_kr[..., :c], p["kv_norm"])       # (B,S,c)
    k_rope = apply_rope(ckv_kr[..., c:], positions, cfg.rope_theta)  # (B,S,dr)
    return c_kv, k_rope


def mla_forward(p, cfg: ModelConfig, x, positions) -> jax.Array:
    """Full-sequence MLA: expand compressed kv, standard causal attention."""
    B, S, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_ckv(p, cfg, x, positions)
    k_nope = jnp.einsum("bsc,chd->bshd", c_kv, p["uk"])
    v = jnp.einsum("bsc,chd->bshd", c_kv, p["uv"])
    scale = (dn + dr) ** -0.5
    if S >= getattr(cfg, "flash_threshold", FLASH_THRESHOLD):
        # fold the shared rope key into per-head K and run standard flash
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, h, dr))], axis=-1)
        q_full = constrain(q_full, ("batch", "seq", "heads", None))
        k_full = constrain(k_full, ("batch", "seq", "heads", None))
        v = constrain(v, ("batch", "seq", "heads", None))
        c = _chunk_for(S)
        out = flash_attend(q_full, k_full, v, scale, q_chunk=c, kv_chunk=c,
                           causal_skip=getattr(cfg, "flash_causal_skip",
                                               False))
    else:
        scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope,
                               preferred_element_type=jnp.float32)) * scale
        mask = causal_mask(S, S)
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
    out = constrain(out.reshape(B, S, h * dv), ("batch", "seq", "heads"))
    return dense(p["o"], out)


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = cfg.param_dtype
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dt),
    }


def mla_decode(p, cfg: ModelConfig, x, cache: dict, pos: jax.Array):
    """One-token decode in the ABSORBED form over the compressed cache."""
    B = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)        # (B,1,H,*)
    c_new, kr_new = _mla_ckv(p, cfg, x, positions)       # (B,1,c),(B,1,dr)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, 1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, 1)
    # absorb W_uk into the query:  q_c = q_nope @ W_uk  -> (B,H,c)
    q_c = jnp.einsum("bqhd,chd->bhc", q_nope, p["uk"])
    q_c = constrain(q_c, ("batch", "heads", None))
    scale = (dn + dr) ** -0.5
    scores = (jnp.einsum("bhc,bsc->bhs", q_c, c_cache,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsd->bhs", q_rope, kr_cache,
                           preferred_element_type=jnp.float32)) * scale
    S = c_cache.shape[1]
    valid = jnp.arange(S)[None, None, :] <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_cache.dtype)
    ctx_c = jnp.einsum("bhs,bsc->bhc", probs, c_cache)   # (B,H,c)
    out = jnp.einsum("bhc,chd->bhd", ctx_c, p["uv"])     # absorb W_uv
    out = out.reshape(B, 1, h * dv)
    new_cache = {"c_kv": c_cache, "k_rope": kr_cache}
    return dense(p["o"], out), new_cache


def mla_prefill(p, cfg: ModelConfig, x, cache: dict):
    """Prompt prefill into the compressed decode cache — the vectorized
    twin of ``mla_decode`` (same ABSORBED einsums so prefill numerics
    match the per-token decode loop, S queries at once), writing
    c_kv / k_rope for positions 0..S-1.  x: (B,S,D)."""
    B, S, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q_nope, q_rope = _mla_q(p, cfg, x, positions)        # (B,S,H,*)
    c_new, kr_new = _mla_ckv(p, cfg, x, positions)       # (B,S,c),(B,S,dr)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), 0, 1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), 0, 1)
    ck, kr = c_cache[:, :S], kr_cache[:, :S]     # attend over STORED dtype
    q_c = jnp.einsum("bqhd,chd->bqhc", q_nope, p["uk"])
    q_c = constrain(q_c, ("batch", "seq", "heads", None))
    scale = (dn + dr) ** -0.5
    scores = (jnp.einsum("bqhc,bsc->bhqs", q_c, ck,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, kr,
                           preferred_element_type=jnp.float32)) * scale
    mask = causal_mask(S, S)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    ctx_c = jnp.einsum("bhqs,bsc->bqhc", probs, ck)
    out = jnp.einsum("bqhc,chd->bqhd", ctx_c, p["uv"])
    out = out.reshape(B, S, h * dv)
    new_cache = {"c_kv": c_cache, "k_rope": kr_cache}
    return dense(p["o"], out), new_cache
