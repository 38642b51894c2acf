#!/usr/bin/env python3
"""Smoke run of the rDLB main path on one TPU chip.

    python3 chip_smoke.py [--seed 0]

Three phases, each through the entry points a user calls:

1. ``paper_loops``: the paper's Table-1 loops with their Pallas kernels as
   task bodies, one kernel launch per chunk, scheduled by the rDLB engine
   (``api.build``) with P=4 workers, FAC and rDLB on.  Mandelbrot is
   512x512 pixels at 256 iterations as 64 tiles of 64x64; PSIA is 20,000
   spin images over a 16,384-point cloud.  Each loop runs once with every
   worker healthy and once with 3 of the 4 workers failing mid-run; the
   two results must be equal bit for bit.  Both are compared with the
   plain jnp oracles of ``repro.kernels.ref``, run on the same device.
2. ``serving``: olmo-1b at its published widths (random weights from
   ``--seed``) through ``RDLBServeExecutor``: 8 requests of 128 prompt
   tokens and 32 new tokens over 4 replicas, one replica failing after its
   first request.  The outputs must equal a healthy run token for token.
3. ``devicesim``: one Monte-Carlo batch of the device-resident simulator
   (P=256, N=2^15, 512 elements) against the scalar engine.

Each phase prints one JSON line with its numbers (wall and compile
seconds, compile counts, mismatch counts).  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before any phase.  The phases are
functions taking their sizes, so a CPU test runs them at tiny size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Backend compilations (a persistent-cache hit is not one) and their
    seconds, counted inside the ``with`` block."""

    def __enter__(self) -> "Compiles":
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.n += 1
            self.secs += secs


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def _kernel_compiled(jitted, *args, **static) -> bool:
    """True iff the compiled program holds a Mosaic kernel (not the
    interpreter's XLA ops)."""
    hlo = jitted.lower(*args, **static).compile().as_text()
    return "tpu_custom_call" in hlo


# ------------------------------------------------------------ paper loops
def _run_loop(chunk_fn, n_tasks: int, P: int, fail_after):
    """One FAC + rDLB run of ``n_tasks`` through the engine; workers 1..P-1
    fail-stop after ``fail_after`` tasks (None = all healthy)."""
    from repro import api
    from repro.runtime import ChunkBackend
    workers = tuple(api.WorkerSpec(fail_after_tasks=None if w == 0
                                   else fail_after) for w in range(P))
    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        robustness=api.RobustnessSpec(rdlb_enabled=True),
        cluster=api.ClusterSpec(n_workers=P, workers=workers),
        n_tasks=n_tasks)
    backend = ChunkBackend(chunk_fn, n_tasks)
    t0 = time.perf_counter()
    stats = api.run(spec, api.build(spec, backend))
    wall = time.perf_counter() - t0
    _check(not stats.hung and stats.n_finished == n_tasks,
           f"loop of {n_tasks} tasks hung or lost tasks")
    return backend.results, stats, wall


def _loop_pair(name: str, chunk_fn, n_tasks: int, P: int) -> tuple:
    """Healthy run, then P-1 fail-stops; returns (result, record)."""
    with Compiles() as comp:
        healthy, st_h, wall_h = _run_loop(chunk_fn, n_tasks, P, None)
    fail_after = max(1, n_tasks // (2 * P))      # inside the first chunk
    failed, st_f, wall_f = _run_loop(chunk_fn, n_tasks, P, fail_after)
    _check(st_f.survivors == [0],
           f"{name}: expected workers 1..{P - 1} to fail, survivors "
           f"{st_f.survivors}")
    equal = bool(np.array_equal(healthy, failed))
    _check(equal, f"{name}: result with {P - 1} failed workers differs "
                  "from the failure-free run")
    sizes = sorted({c.size for c in st_h.assignment_log})
    return healthy, dict(
        app=name, n_tasks=n_tasks, P=P, failed_workers=P - 1,
        wall_s_healthy=wall_h, wall_s_failed=wall_f,
        duplicates_failed=st_f.n_duplicates,
        wasted_tasks_failed=st_f.wasted_tasks,
        equal_to_failure_free=equal,
        distinct_chunk_sizes=sizes,
        compiles_healthy=comp.n, compile_s_healthy=comp.secs)


def paper_loops(*, side: int = 512, tile: int = 64, max_iters: int = 256,
                psia_n: int = 20_000, cloud_n: int = 16_384,
                P: int = 4) -> list[dict]:
    import jax.numpy as jnp
    from repro.apps import mandelbrot, psia
    from repro.kernels import ops, ref

    # ---- Mandelbrot: tiles [start, stop) as one device program
    n = mandelbrot.n_tiles(side, tile)
    tiles, rec_m = _loop_pair(
        "mandelbrot",
        lambda a, b: mandelbrot.compute_tiles(a, b, side=side, tile=tile,
                                              max_iters=max_iters),
        n, P)
    per_row = side // tile
    img = (tiles.reshape(per_row, per_row, tile, tile)
           .transpose(0, 2, 1, 3).reshape(side, side))
    cr, ci = mandelbrot.grid(side)
    want = np.asarray(jax.jit(ref.mandelbrot, static_argnums=2)(
        cr, ci, max_iters))
    rec_m["oracle_mismatch_pixels"] = int((img != want).sum())
    rec_m["oracle_max_count_diff"] = int(np.abs(img - want).max())
    rec_m["mosaic_kernel"] = _kernel_compiled(
        mandelbrot.mandelbrot_chunk, *mandelbrot.plane(side), np.int32(0),
        n=mandelbrot.slab_pixels(tile * tile), tile=tile,
        max_iters=max_iters)

    # ---- PSIA: spin images of oriented points [start, stop), one launch
    images, rec_p = _loop_pair(
        "psia",
        lambda a, b: psia.compute_tasks(np.arange(a, b), n=psia_n,
                                        cloud_n=cloud_n),
        psia_n, P)
    pts = psia.cloud(cloud_n)
    ctr, nrm = psia.oriented_points(psia_n)
    kw = dict(n_alpha=psia.N_ALPHA, n_beta=psia.N_BETA, alpha_max=3.0,
              beta_max=3.0)
    batch = 64
    oracle = jax.jit(lambda x, c, v: ref.spin_image(x, c, v, **kw))
    pad = (-psia_n) % batch
    ctr_p = jnp.pad(ctr, ((0, pad), (0, 0)))
    nrm_p = jnp.pad(nrm, ((0, pad), (0, 0)))
    diff = np.zeros(psia_n)
    n_bins = 0
    with jax.default_matmul_precision("highest"):
        for s in range(0, psia_n, batch):
            got = images[s:s + batch]
            want = np.asarray(oracle(pts, ctr_p[s:s + batch],
                                     nrm_p[s:s + batch]))[:len(got)]
            d = np.abs(got - want)
            diff[s:s + len(got)] = d.reshape(len(got), -1).max(axis=1)
            n_bins += int((d != 0).sum())
    rec_p["oracle_max_bin_diff"] = float(diff.max())
    rec_p["oracle_mismatch_bins"] = n_bins
    rec_p["oracle_mismatch_images"] = int((diff != 0).sum())
    rec_p["mosaic_kernel"] = _kernel_compiled(
        ops.spin_image, pts, ctr[:16], nrm[:16], block_p=psia.BLOCK_P,
        **kw)
    if jax.devices()[0].platform == "tpu":
        _check(rec_m["mosaic_kernel"] and rec_p["mosaic_kernel"],
               "a paper-loop kernel was not compiled with Mosaic")
    return [rec_m, rec_p]


# ---------------------------------------------------------------- serving
def serving(cfg=None, *, n_requests: int = 8, prompt_len: int = 128,
            max_new: int = 32, n_replicas: int = 4, seed: int = 0) -> dict:
    from repro import api
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime import RDLBServeExecutor, Request

    cfg = cfg or get_config("olmo-1b")
    t0 = time.perf_counter()
    model = build_model(cfg)
    # one compiled init: eager init dispatches (and compiles) op by op
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    spec = api.serve_spec(technique="SS", n_workers=n_replicas,
                          rdlb_enabled=True)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(n_requests, prompt_len),
                           dtype=np.int32)

    def serve(ex, fail_at=None):
        reqs = [Request(i, prompts[i], max_new_tokens=max_new)
                for i in range(n_requests)]
        with Compiles() as comp:
            t0 = time.perf_counter()
            st = ex.serve(reqs, fail_at=fail_at)
            wall = time.perf_counter() - t0
        _check(not st.hung, "serving hung")
        _check(all(r.output is not None for r in reqs),
               "a request was never answered")
        return np.stack([r.output for r in reqs]), st, wall, comp

    fused = RDLBServeExecutor(model, params, spec=spec)
    healthy, _, wall_h, comp_h = serve(fused)
    failed, st_f, wall_f, comp_f = serve(fused, fail_at={1: 1})
    _check(1 in fused.dead, "replica 1 did not fail")
    identical = bool(np.array_equal(healthy, failed))
    _check(identical, "outputs with a failed replica differ from the "
                      "healthy run")
    loop = RDLBServeExecutor(model, params, spec=spec, fused_decode=False)
    looped, _, wall_l, _ = serve(loop)
    agree = int(sum(np.array_equal(a, b) for a, b in zip(healthy, looped)))
    return dict(
        model=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, requests=n_requests, prompt_len=prompt_len,
        max_new=max_new, replicas=n_replicas, failed_replicas=1,
        init_s=init_s, wall_s_healthy=wall_h, wall_s_failed=wall_f,
        wall_s_loop=wall_l, compiles_healthy=comp_h.n,
        compile_s_healthy=comp_h.secs, compiles_failed=comp_f.n,
        duplicates_failed=st_f.n_duplicates,
        token_identical_to_healthy=identical, hung=False,
        fused_vs_loop_agree=agree)


# -------------------------------------------------------------- devicesim
def device_sim(*, P: int = 256, N: int = 1 << 15, B: int = 512,
               t: float = 0.01, h: float = 1e-6,
               max_rel_err: float = 1e-9) -> dict:
    from repro import api
    from repro.core import devicesim

    techniques = ("SS", "STATIC", "mFSC", "FSC")
    tt = np.full(N, t)
    specs = [api.RunSpec(scheduling=api.SchedulingSpec(technique=k),
                         cluster=api.ClusterSpec(n_workers=P),
                         execution=api.ExecutionSpec(h=h))
             for k in techniques]
    lows = []
    for k, spec in zip(techniques, specs):
        lo, why = devicesim.lower_run(spec, tt)
        _check(lo is not None, f"{k} did not lower: {why}")
        lows.append(lo)
    tech_of = np.arange(B, dtype=np.int32) % len(techniques)
    with Compiles() as comp:
        t0 = time.perf_counter()
        devicesim.simulate_many(lows, tech_of=tech_of)
        cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = devicesim.simulate_many(lows, tech_of=tech_of)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    scalar = np.array([api.simulate(s, tt).t_par for s in specs])
    scalar_s = time.perf_counter() - t0
    want = scalar[tech_of]
    rel = np.abs(res.t_par - want) / want
    n_invalid = int((~res.valid).sum())
    rec = dict(P=P, N=N, batch=B, cold_s=cold, warm_s=warm,
               scalar_4_runs_s=scalar_s,
               compiles_cold=comp.n, compile_s_cold=comp.secs,
               max_rel_t_par_err=float(rel.max()), n_invalid=n_invalid)
    _check(n_invalid == 0, f"{n_invalid} elements came back valid=False")
    _check(bool(rel.max() <= max_rel_err),
           f"t_par off the scalar engine by {rel.max():.3e} (relative)")
    return rec


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)

    phases = [("paper_loops", paper_loops),
              ("serving", lambda: serving(seed=args.seed)),
              ("devicesim", device_sim)]
    ok = True
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            recs = run()
            for rec in recs if isinstance(recs, list) else [recs]:
                print(json.dumps({"phase": name, "ok": True, **rec}),
                      flush=True)
        except Exception as e:
            ok = False
            traceback.print_exc()
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
        print(json.dumps({"phase": name,
                          "phase_wall_s": time.perf_counter() - t0}),
              flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
