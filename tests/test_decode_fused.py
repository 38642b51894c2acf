"""Device-resident fused decode: token parity vs the per-token loop
across model families, executor wiring (fused on/off, batch on/off,
mid-decode duplicate races), Pallas decode kernels vs their jnp twins,
and the kernel telemetry contract (a kernel error propagates)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.kernels import dispatch, ops, ref
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.runtime import RDLBServeExecutor, Request
from repro.runtime.serve_executor import FusedGenerator, greedy_decode_group

CONFIGS = {
    "dense": ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=2,
                         n_kv_heads=2, d_ff=128, vocab_size=128,
                         dtype="float32"),
    "mla": ModelConfig(family="dense", n_layers=2, d_model=64, n_heads=2,
                       n_kv_heads=2, d_ff=128, vocab_size=128,
                       dtype="float32", mla=True, kv_lora_rank=16,
                       rope_head_dim=8, v_head_dim=16, nope_head_dim=16),
    "rwkv": ModelConfig(family="rwkv", n_layers=2, d_model=64, n_heads=2,
                        d_ff=128, vocab_size=128, dtype="float32",
                        rwkv_head_dim=16),
    "hybrid": ModelConfig(family="hybrid", n_layers=2, d_model=32,
                          n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                          dtype="float32", n_meta_tokens=4,
                          sliding_window=8, ssm_state=4,
                          global_layers=(1,)),
}


def _model(key):
    cfg = CONFIGS[key]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ------------------------------------------------- fused-vs-loop parity
@pytest.mark.parametrize("arch", list(CONFIGS))
def test_fused_token_parity(arch):
    """FusedGenerator (prefill + lax.scan) emits the exact tokens the
    per-token decode loop does — B=3 exercises the pad-to-pow2 rows."""
    cfg, model, params = _model(arch)
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    gen = FusedGenerator(model)
    rng = np.random.default_rng(0)
    for B, S, new in [(1, 7, 4), (3, 12, 5)]:
        prompts = rng.integers(0, cfg.vocab_size,
                               size=(B, S)).astype(np.int32)
        want = greedy_decode_group(model, params, decode, prompts, new)
        got = gen(params, prompts, new)
        assert got.shape == (B, new)
        assert np.array_equal(got, want), f"{arch} B={B} S={S}"


def test_fused_single_token_generation():
    """max_new=1 degenerates to prefill + argmax, no scan steps."""
    cfg, model, params = _model("dense")
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    gen = FusedGenerator(model)
    prompts = np.arange(10, dtype=np.int32)[None, :] % cfg.vocab_size
    want = greedy_decode_group(model, params, decode, prompts, 1)
    assert np.array_equal(gen(params, prompts, 1), want)


# ------------------------------------------------------ executor wiring
def _serve(model, params, prompts, new, n_workers=2, **kw):
    reqs = [Request(i, p, max_new_tokens=new)
            for i, p in enumerate(prompts)]
    ex = RDLBServeExecutor(model, params,
                           spec=api.serve_spec(n_workers=n_workers), **kw)
    stats = ex.serve(reqs)
    assert not stats.hung
    return [r.output for r in reqs]


@pytest.mark.parametrize("batch_decode", [False, True])
def test_executor_fused_matches_loop(batch_decode):
    """fused_decode=True must be invisible in outputs for both the
    batched group path and the per-request baseline path."""
    cfg, model, params = _model("dense")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
               for _ in range(5)]
    loop = _serve(model, params, prompts, 3, batch_decode=batch_decode,
                  fused_decode=False)
    fused = _serve(model, params, prompts, 3, batch_decode=batch_decode,
                   fused_decode=True)
    for a, b in zip(loop, fused):
        assert np.array_equal(a, b)


def test_threaded_duplicate_race_token_identical():
    """A mid-decode worker failure forces duplicate decode tasks racing
    in threads; first-completion-wins must still yield the same tokens
    as an unfailed single-worker run (fused path on, the default)."""
    cfg, model, params = _model("dense")
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(6)]
    reqs = [Request(i, p, max_new_tokens=2)
            for i, p in enumerate(prompts)]
    ex = RDLBServeExecutor(model, params, spec=api.serve_spec(n_workers=3))
    stats = ex.serve(reqs, fail_at={1: 1})
    assert not stats.hung
    assert all(r.output is not None for r in reqs)
    calm = _serve(model, params, prompts, 2, n_workers=1)
    for r, want in zip(reqs, calm):
        assert np.array_equal(r.output, want)


# ------------------------------------------------- decode kernel parity
def test_wkv6_decode_kernel_matches_ref():
    """Single-step WKV6 (C=1 degenerate case) against explicit einsum."""
    BH, dh = 6, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r = jax.random.normal(ks[0], (BH, dh))
    k = jax.random.normal(ks[1], (BH, dh))
    v = jax.random.normal(ks[2], (BH, dh))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (BH, dh)) * 0.4))
    u = jax.random.normal(ks[4], (BH, dh))
    s = jax.random.normal(ks[5], (BH, dh, dh))
    y, s_new = ops.wkv6_decode(r, k, v, w, u, s)
    kv = jnp.einsum("bk,bv->bkv", k, v)
    want_y = jnp.einsum("bk,bkv->bv", r, s + u[:, :, None] * kv)
    want_s = w[:, :, None] * s + kv
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(want_s),
                               atol=1e-4)


def test_wkv6_decode_equals_one_step_scan():
    """One kernel decode step == wkv6 chunked scan run on T=1."""
    BH, dh = 4, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    r = jax.random.normal(ks[0], (BH, dh))
    k = jax.random.normal(ks[1], (BH, dh))
    v = jax.random.normal(ks[2], (BH, dh))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (BH, dh)) * 0.4))
    u = jax.random.normal(ks[4], (BH, dh))
    s = jax.random.normal(ks[5], (BH, dh, dh))
    y, s_new = ops.wkv6_decode(r, k, v, w, u, s)
    for b in range(BH):
        want_y, want_s = ref.wkv6(r[b:b + 1], k[b:b + 1], v[b:b + 1],
                                  w[b:b + 1], u[b], s[b])
        np.testing.assert_allclose(np.asarray(y[b]),
                                   np.asarray(want_y[0]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_new[b]),
                                   np.asarray(want_s), atol=1e-4)


@pytest.mark.parametrize("nvalid", [1, 7, 128, 130, 256])
def test_flash_decode_matches_ref(nvalid):
    """q_len=1 flash decode vs dense softmax, including blocks that are
    entirely masked (the exp(-inf - -inf) hazard)."""
    B, L, dh = 3, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, dh))
    k = jax.random.normal(ks[1], (B, L, dh))
    v = jax.random.normal(ks[2], (B, L, dh))
    valid = jnp.arange(L) < nvalid
    got = ops.flash_decode(q, k, v, valid, bk=128)
    want = ref.attention_decode(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


def test_flash_decode_scattered_mask():
    """Rolling-window caches produce non-contiguous validity."""
    B, L, dh = 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, dh))
    k = jax.random.normal(ks[1], (B, L, dh))
    v = jax.random.normal(ks[2], (B, L, dh))
    valid = (jnp.arange(L) % 3) == 0
    got = ops.flash_decode(q, k, v, valid, bk=64)
    want = ref.attention_decode(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


# --------------------------------------------- use_kernel model routing
def test_rwkv_use_kernel_matches_jnp():
    """RWKV forward + decode through the Pallas kernels must agree with
    the jnp twins, and telemetry must show the kernel actually ran."""
    dispatch.reset()
    cfg, model, params = _model("rwkv")
    tokens = jnp.arange(2 * 32, dtype=jnp.int32).reshape(2, 32) % 128
    logits_jnp, _ = model.forward(params, tokens, use_kernel=False)
    logits_ker, _ = model.forward(params, tokens, use_kernel=True)
    np.testing.assert_allclose(np.asarray(logits_ker),
                               np.asarray(logits_jnp), atol=1e-3)
    assert dispatch.status("wkv6")["path"] == "pallas"
    # decode step (S=1 -> wkv6_decode kernel)
    cache = model.init_cache(2, 8)
    lj, _ = model.forward(params, tokens[:, :1], cache, use_kernel=False)
    lk, _ = model.forward(params, tokens[:, :1], cache, use_kernel=True)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lj), atol=1e-4)


def test_gqa_decode_use_kernel_matches_jnp():
    """Dense decode_step with cfg.use_kernel routes attention through
    flash_decode and matches the jnp path bit-for-bit in argmax terms."""
    dispatch.reset()
    cfg, model, params = _model("dense")
    cfg_k = CONFIGS["dense"].replace(use_kernel=True)
    model_k = build_model(cfg_k)
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, cfg.vocab_size, size=(2, 6)).astype(np.int32)
    total = 6 + 3
    cache = model.init_cache(2, total)
    cache_k = model_k.init_cache(2, total)
    for pos in range(total - 1):
        tok = jnp.asarray(prompts[:, pos:pos + 1]) if pos < 6 else tok_next
        logits, cache = model.decode_step(params, cache, tok,
                                          jnp.int32(pos))
        logits_k, cache_k = model_k.decode_step(params, cache_k, tok,
                                                jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(logits_k),
                                   np.asarray(logits), atol=1e-4)
        tok_next = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    assert dispatch.status("gqa_decode")["path"] == "pallas"


# ------------------------------------------------- kernel errors propagate
def test_kernel_error_propagates(monkeypatch):
    """A broken kernel is an error on the main path: no jnp fallback
    hides it, and telemetry never reports a kernel run that failed."""
    from repro.kernels import rwkv6_scan

    def boom(*a, **kw):
        raise RuntimeError("injected kernel failure")

    dispatch.reset()
    monkeypatch.setattr(rwkv6_scan, "wkv6_batched", boom)
    monkeypatch.setattr(rwkv6_scan, "wkv6_decode", boom)
    cfg, model, params = _model("rwkv")
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 128
    with pytest.raises(RuntimeError, match="injected kernel failure"):
        model.forward(params, tokens, use_kernel=True)
    assert dispatch.status("wkv6") == {}


def test_fallback_status_is_queryable_via_ops():
    dispatch.reset()
    dispatch.record("wkv6", "pallas")
    assert ops.kernel_status("wkv6")["path"] == "pallas"
    assert ops.kernel_status() == {"wkv6": {"path": "pallas"}}
