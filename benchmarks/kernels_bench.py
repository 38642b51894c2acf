"""Kernel micro-benchmarks (interpret mode on CPU: correctness-scale
timings only — the TPU numbers come from the §Roofline dry-run analysis).

Prints name,us_per_call,check columns.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.kernels import ops, ref


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6, out


def main(quick: bool = True):
    rows, lines = [], []
    # mandelbrot
    xs = jnp.linspace(-2, 1, 256)
    ys = jnp.linspace(-1.5, 1.5, 256)
    cr, ci = jnp.meshgrid(xs, ys)
    us, got = _time(ops.mandelbrot, cr, ci, max_iters=64, bm=128, bn=128)
    ok = bool(np.array_equal(np.asarray(got),
                             np.asarray(ref.mandelbrot(cr, ci, 64))))
    rows.append(("mandelbrot_256x256_64it", us, ok))
    # spin image
    pts = jax.random.normal(jax.random.PRNGKey(0), (4096, 3))
    ctr = jax.random.normal(jax.random.PRNGKey(1), (8, 3)) * 0.2
    nrm = jax.random.normal(jax.random.PRNGKey(2), (8, 3))
    nrm = nrm / jnp.linalg.norm(nrm, axis=-1, keepdims=True)
    kw = dict(n_alpha=64, n_beta=64, alpha_max=2.5, beta_max=2.5)
    us, got = _time(ops.spin_image, pts, ctr, nrm, block_p=512, **kw)
    ok = bool(np.allclose(np.asarray(got),
                          np.asarray(ref.spin_image(pts, ctr, nrm, **kw)),
                          atol=1e-4))
    rows.append(("spin_image_4096x8_64x64", us, ok))
    # flash attention
    q = jax.random.normal(jax.random.PRNGKey(3), (4, 512, 64))
    k = jax.random.normal(jax.random.PRNGKey(4), (4, 512, 64))
    v = jax.random.normal(jax.random.PRNGKey(5), (4, 512, 64))
    us, got = _time(ops.flash_attention, q, k, v, causal=True)
    ok = bool(np.allclose(np.asarray(got),
                          np.asarray(ref.attention(q, k, v)), atol=1e-4))
    rows.append(("flash_attention_4x512x64", us, ok))
    # wkv6
    T, dk = 256, 64
    r = jax.random.normal(jax.random.PRNGKey(6), (T, dk))
    kk = jax.random.normal(jax.random.PRNGKey(7), (T, dk))
    vv = jax.random.normal(jax.random.PRNGKey(8), (T, dk))
    w = jnp.exp(-jnp.exp(jax.random.normal(jax.random.PRNGKey(9),
                                           (T, dk)) * 0.4))
    u = jax.random.normal(jax.random.PRNGKey(10), (dk,))
    s0 = jnp.zeros((dk, dk))
    us, got = _time(lambda *a: ops.wkv6(*a, chunk=32)[0], r, kk, vv, w, u,
                    s0)
    want, _ = ref.wkv6(r, kk, vv, w, u, s0)
    ok = bool(np.allclose(np.asarray(got, np.float32), np.asarray(want),
                          atol=1e-3, rtol=1e-2))
    rows.append(("wkv6_256x64", us, ok))
    # wkv6 single-step decode (C=1 degenerate case, serving hot path)
    BH, dh = 8, 64
    rd = jax.random.normal(jax.random.PRNGKey(11), (BH, dh))
    kd = jax.random.normal(jax.random.PRNGKey(12), (BH, dh))
    vd = jax.random.normal(jax.random.PRNGKey(13), (BH, dh))
    wd = jnp.exp(-jnp.exp(jax.random.normal(jax.random.PRNGKey(14),
                                            (BH, dh)) * 0.4))
    ud = jax.random.normal(jax.random.PRNGKey(15), (BH, dh))
    sd = jax.random.normal(jax.random.PRNGKey(16), (BH, dh, dh))
    us, got = _time(ops.wkv6_decode, rd, kd, vd, wd, ud, sd)
    yd, std = got
    want_y = jnp.einsum("bk,bkv->bv", rd,
                        sd + ud[:, :, None] *
                        jnp.einsum("bk,bv->bkv", kd, vd))
    want_s = wd[:, :, None] * sd + jnp.einsum("bk,bv->bkv", kd, vd)
    ok = (bool(np.allclose(np.asarray(yd), np.asarray(want_y),
                           atol=1e-4)) and
          bool(np.allclose(np.asarray(std), np.asarray(want_s),
                           atol=1e-4)))
    rows.append(("wkv6_decode_8x64", us, ok))
    # flash decode (q_len=1 vs KV cache, serving hot path)
    L, dh = 256, 64
    qd = jax.random.normal(jax.random.PRNGKey(17), (4, dh))
    kc = jax.random.normal(jax.random.PRNGKey(18), (4, L, dh))
    vc = jax.random.normal(jax.random.PRNGKey(19), (4, L, dh))
    valid = (jnp.arange(L) < 130)
    us, got = _time(ops.flash_decode, qd, kc, vc, valid, bk=128)
    ok = bool(np.allclose(np.asarray(got),
                          np.asarray(ref.attention_decode(qd, kc, vc,
                                                          valid)),
                          atol=1e-4))
    rows.append(("flash_decode_4x256x64", us, ok))

    common.write_csv("kernels", ["kernel", "us_per_call", "matches_ref"],
                     rows)
    for name, us, ok in rows:
        lines.append(f"kernels,{name},{us:.0f}us,ref_match={ok}")
        assert ok, name
    lines.extend(serve_throughput(quick))
    if not quick:
        # --paper: the headline decode claim (>=5x fused vs loop at B=16)
        from benchmarks import decode_bench
        lines.extend(decode_bench.decode_series(quick=False, Bs=(16,)))
    return lines


def serve_throughput(quick: bool = True):
    """Serve-path throughput: per-request token loop vs one padded jitted
    batch per chunk (the engine's batched-decode layer), same requests,
    same outputs.  Reports req/s and the batched speedup."""
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro import api
    from repro.runtime import RDLBServeExecutor, Request

    cfg = ModelConfig(family="dense", n_layers=2, d_model=128, n_heads=4,
                      n_kv_heads=4, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_req, s, new = (16, 8, 8) if quick else (64, 16, 16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
               for _ in range(n_req)]

    def run(batch_decode: bool) -> tuple[float, list]:
        reqs = [Request(i, p, max_new_tokens=new)
                for i, p in enumerate(prompts)]
        ex = RDLBServeExecutor(
            model, params, spec=api.serve_spec(technique="GSS", n_workers=1),
            batch_decode=batch_decode)
        ex.serve(reqs)        # warm-up: jit compile at these shapes
        reqs = [Request(i, p, max_new_tokens=new)
                for i, p in enumerate(prompts)]
        t0 = time.time()
        ex.serve(reqs)
        return n_req / (time.time() - t0), reqs

    rps_per, out_per = run(batch_decode=False)
    rps_bat, out_bat = run(batch_decode=True)
    ok = all(np.array_equal(a.output, b.output)
             for a, b in zip(out_per, out_bat))
    speedup = rps_bat / rps_per
    rows = [("per_request", rps_per, ok), ("batched", rps_bat, ok)]
    common.write_csv("serve_throughput",
                     ["decode_mode", "req_per_s", "outputs_match"], rows)
    lines = [f"serve,decode_per_request,{rps_per:.1f}req/s,match={ok}",
             f"serve,decode_batched,{rps_bat:.1f}req/s,match={ok}",
             f"serve,batched_speedup,{speedup:.2f}x,match={ok}"]
    assert ok, "batched decode diverged from per-request decode"
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
