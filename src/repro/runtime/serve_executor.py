"""rDLB serving executor: robust continuous batching.

Tasks = inference REQUESTS (prompt -> generate k tokens).  Workers are
model replicas.  The same unified engine (repro.core.engine) schedules
requests through the RobustQueue; with rDLB, once every request is
assigned, idle replicas DUPLICATE in-flight requests of stragglers/failed
replicas — first completion wins (greedy decode is deterministic, so
duplicates are interchangeable).  This is the paper's idle-tail insight
applied to serving: P99 latency under a slow/failed replica collapses to
~P50 because the tail is re-executed elsewhere.

Three performance layers on top of the shared engine:

  * BATCHED DECODE (``batch_decode=True``): a chunk's requests are grouped
    by (prompt length, max_new_tokens) and each group decodes as ONE
    padded, jitted batch call — (B, 1) tokens through ``decode_step`` —
    instead of a per-request Python token loop.  The batch dimension is
    padded up to a power of two so jit recompiles stay bounded.
  * DEVICE-RESIDENT GENERATION (``fused_decode=True``, the default): the
    per-token Python loop is replaced by :class:`FusedGenerator` — one
    jitted call per (padded B, prompt_len, max_new) bucket that prefills
    the cache in a single full-sequence pass, then runs max_new fused
    (decode_step + on-device argmax + token feedback) steps inside a
    ``lax.scan`` with the cache donated between steps.  Zero host
    round-trips per token; token-identical to the loop.
  * CONCURRENT MODE (``execution.mode="threaded"`` on the spec, or
    ``serve(concurrent=True)``): replicas run as real OS threads; rDLB
    duplicates genuinely race their originals in wall-clock time, and
    first-completion-wins is physical rather than an artifact of
    round-robin ordering.

Configuration is a declarative :class:`repro.api.RunSpec`
(``RDLBServeExecutor(model, params, spec=spec)``, built with
``api.serve_spec``); replica perturbations are declared on its cluster.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.runtime.backends import ServeBackend

# Buffer donation is a no-op on CPU backends (jax warns per compile);
# on TPU the same donate_argnums reuses the cache buffers in place.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 8
    output: Optional[np.ndarray] = None
    completed_by: Optional[int] = None
    duplicated: bool = False


@dataclasses.dataclass
class ServeStats:
    n_requests: int
    n_duplicates: int
    wasted_requests: int
    hung: bool
    by_worker: dict


def _pad_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def greedy_decode_group(model, params, decode_step, prompts: np.ndarray,
                        max_new: int) -> np.ndarray:
    """Greedy-decode a (B, S) group of equal-length prompts as one
    padded jitted batch: B is padded to a power of two (bounded jit
    recompiles); pad rows replicate row 0 and are discarded.

    Rows are independent through attention/cache, so batched decode is
    interchangeable with the per-request loop.  Module-level because the
    process-cluster runner (repro.cluster.runners.ServeTaskRunner) runs
    the SAME code in the worker process — outputs stay token-identical
    across execution modes.
    """
    B, S = prompts.shape
    Bp = _pad_pow2(B)
    total = S + max_new
    toks = np.empty((Bp, total), dtype=np.int32)
    toks[:B, :S] = prompts
    toks[B:, :S] = prompts[0]
    cache = model.init_cache(Bp, total)
    for pos in range(total - 1):
        tok = jnp.asarray(toks[:, pos:pos + 1])
        logits, cache = decode_step(params, cache, tok, jnp.int32(pos))
        if pos >= S - 1:
            toks[:, pos + 1] = np.asarray(
                jnp.argmax(logits[:, -1, :], axis=-1), dtype=np.int32)
    return toks[:B, S:]


class FusedGenerator:
    """Device-resident greedy generation: prefill + fused decode scan.

    One jitted call per (padded B, prompt_len, max_new) shape bucket —
    the same ``_pad_pow2`` buckets the grouped loop path uses, so one
    compile serves a bucket.  Inside the call:

      1. ``model.prefill`` fills the decode cache for all S prompt
         positions in one full-sequence pass (models without a prefill
         method — whisper — fall back to an in-graph ``lax.scan`` over
         the prompt, still device-resident);
      2. a ``lax.scan`` runs max_new fused steps — decode_step, greedy
         argmax ON DEVICE, and the sampled token fed straight back as the
         next step's input.  No host round-trip per token, one jit
         dispatch per request group instead of S + max_new.

    The cache is donated into the call (in-place buffer reuse on TPU;
    harmless no-op on CPU).  Token-identical to ``greedy_decode_group``:
    prefill writes the same cache values and the scan computes the same
    argmax chain — asserted across model families in
    tests/test_decode_fused.py.
    """

    def __init__(self, model):
        self.model = model
        self._gen = jax.jit(self._generate, static_argnames=("max_new",),
                            donate_argnums=(1,))

    def _generate(self, params, cache, prompts, *, max_new: int):
        model = self.model
        B, S = prompts.shape
        if hasattr(model, "prefill"):
            logits, cache = model.prefill(params, cache, prompts)
        else:
            if S > 1:
                def pstep(cache, inp):
                    tok, pos = inp
                    _, cache = model.decode_step(params, cache,
                                                 tok[:, None], pos)
                    return cache, None
                cache, _ = jax.lax.scan(
                    pstep, cache,
                    (prompts[:, :-1].T, jnp.arange(S - 1, dtype=jnp.int32)))
            logits, cache = model.decode_step(
                params, cache, prompts[:, -1:], jnp.int32(S - 1))
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

        def step(carry, pos):
            cache, tok = carry
            logits, cache = model.decode_step(params, cache, tok[:, None],
                                              pos)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return (cache, nxt), tok

        (cache, last), emitted = jax.lax.scan(
            step, (cache, tok), S + jnp.arange(max_new - 1, dtype=jnp.int32))
        return jnp.concatenate([emitted.T, last[:, None]], axis=1)

    def __call__(self, params, prompts: np.ndarray,
                 max_new: int) -> np.ndarray:
        """prompts: (B, S) int32 -> generated tokens (B, max_new)."""
        B, S = prompts.shape
        Bp = _pad_pow2(B)
        buf = np.empty((Bp, S), dtype=np.int32)
        buf[:B] = prompts
        buf[B:] = prompts[0]
        cache = self.model.init_cache(Bp, S + max_new)
        toks = self._gen(params, cache, jnp.asarray(buf), max_new=max_new)
        return np.asarray(toks)[:B]


def decode_request_groups(model, params, decode_step, reqs: list,
                          *, batch_decode: bool = True,
                          generator: Optional[FusedGenerator] = None) -> dict:
    """Decode a chunk of requests -> {rid: tokens}.

    Batched mode groups by (prompt_len, max_new_tokens) — each group is
    one padded batch call; singleton shapes fall out naturally.  With a
    ``generator`` the group decodes device-resident (FusedGenerator);
    otherwise through the per-token ``greedy_decode_group`` loop."""
    def decode_group(prompts: np.ndarray, max_new: int) -> np.ndarray:
        if generator is not None:
            return generator(params, prompts, max_new)
        return greedy_decode_group(model, params, decode_step, prompts,
                                   max_new)
    if not batch_decode:
        return {r.rid: decode_group(r.prompt[None, :], r.max_new_tokens)[0]
                for r in reqs}
    groups: dict[tuple, list] = {}
    for r in reqs:
        groups.setdefault((len(r.prompt), r.max_new_tokens), []).append(r)
    out: dict[int, np.ndarray] = {}
    for (S, max_new), rs in groups.items():
        prompts = np.stack([r.prompt for r in rs]).astype(np.int32)
        toks = decode_group(prompts, max_new)
        for r, t in zip(rs, toks):
            out[r.rid] = t
    return out


class RDLBServeExecutor:
    """Robust continuous batching, configured by a declarative
    :class:`repro.api.RunSpec` (``spec=``).

    The spec's cluster is the one perturbation vocabulary: declare dead
    replicas (``alive=False``), stragglers (``sleep_per_task`` /
    ``speed``) or count-based fail-stops (``fail_after_tasks``) there.
    The live ``dead``/``slow`` sets (observed replica deaths persist
    across ``serve()`` calls) overlay it each call, through
    ``ClusterSpec.with_serve_state``.
    """

    def __init__(self, model, params, *, spec: api.RunSpec,
                 batch_decode: bool = True,
                 fused_decode: bool = True,
                 adaptive: Optional[Any] = None):
        self.spec = spec
        self.model = model
        self.params = params
        self.n_workers = spec.cluster.n_workers
        self.batch_decode = batch_decode
        self.fused_decode = fused_decode
        self.adaptive = adaptive        # repro.adaptive policy (requests
                                        # are unit-cost tasks)
        # donate the cache: each decode step reuses its buffers in place
        # on TPU instead of copying the full KV/state cache per token
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self._fused = FusedGenerator(model) if fused_decode else None
        # Live perturbation state, mutated between serve() calls;
        # overlaid on the spec's cluster each serve().
        # Spec-declared deaths seed the set so fail-stops persist.
        self.dead: set[int] = {wid for wid, w in
                               enumerate(spec.cluster.worker_specs())
                               if not w.alive}
        self.slow: dict[int, float] = {}      # wid -> extra s per request

    def fail_worker(self, wid: int) -> None:
        self.dead.add(wid)

    # ------------------------------------------------------------- decode
    def _generate(self, req: Request) -> np.ndarray:
        """Greedy decode, one request at a time (the pre-batching path,
        kept as the ``batch_decode=False`` baseline)."""
        return greedy_decode_group(self.model, self.params, self._decode,
                                   req.prompt[None, :],
                                   req.max_new_tokens)[0]

    def _generate_chunk(self, reqs: list[Request]) -> dict:
        """Decode a chunk of requests -> {rid: tokens} (module-level
        ``decode_request_groups`` — shared with the process-mode child
        runner, so every mode decodes identically)."""
        return decode_request_groups(self.model, self.params,
                                     self._decode, reqs,
                                     batch_decode=self.batch_decode,
                                     generator=self._fused)

    # -------------------------------------------------------------- serve
    def serve(self, requests: list[Request],
              *, fail_at: Optional[dict] = None,
              max_rounds: Optional[int] = None,
              concurrent: Optional[bool] = None) -> ServeStats:
        """Process a batch of requests; fail_at: {wid: after_n_requests}."""
        N = len(requests)
        spec = self.spec
        if concurrent is not None:
            spec = spec.override("execution.mode",
                                 "threaded" if concurrent else "virtual")
        # One perturbation vocabulary: dead/slow/fail_at overlay onto the
        # spec cluster via ClusterSpec.with_serve_state — slow (extra
        # seconds per request) maps to BOTH modes there: a real sleep in
        # threaded mode, a speed divisor in virtual time (nominal cost is
        # 1 virtual second per request).  Process mode realizes both
        # fields physically, so the overlay skips the speed composition
        # there (speed_compose=False: sleep_per_task alone carries it).
        cluster = spec.cluster.with_serve_state(
            dead=self.dead, slow=self.slow, fail_at=fail_at or {},
            speed_compose=spec.execution.mode != "process")
        spec = spec.replace(cluster=cluster, n_tasks=N)
        if max_rounds is not None:
            spec = spec.override("execution.horizon", float(max_rounds))
        backend = ServeBackend(requests, self._generate_chunk)
        factory = None
        if spec.execution.mode == "process":
            # replicas as real OS processes: ship the decode RECIPE
            # (config + numpy params + request triples); the child
            # rebuilds the model and runs the same grouped decode
            from repro.cluster import ServeTaskRunner  # lazy import
            cfg = getattr(self.model, "cfg", None)
            if cfg is None:
                raise ValueError("process mode needs a model with .cfg "
                                 "(rebuildable via models.build_model)")
            params_np = jax.tree_util.tree_map(np.asarray, self.params)
            factory = ServeTaskRunner(
                cfg, params_np,
                [(r.rid, np.asarray(r.prompt, dtype=np.int32),
                  int(r.max_new_tokens)) for r in requests],
                batch_decode=self.batch_decode,
                fused_decode=self.fused_decode)
        eng = api.build(spec, backend, n_tasks=N, adaptive=self.adaptive,
                        factory=factory)
        stats = api.run(spec, eng)
        for ew in eng.workers:              # fail-stops persist
            if not ew.alive:
                self.dead.add(ew.wid)
        queue = eng.queue
        return ServeStats(N, queue.n_duplicates, queue.wasted_tasks,
                          stats.hung, dict(stats.by_worker))
