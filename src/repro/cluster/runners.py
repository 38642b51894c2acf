"""Picklable runners that rebuild the real JAX compute in a child.

The training/serving executors' backends hold jitted closures, which
cannot cross a process boundary.  These runners carry the *recipe*
instead — a ``ModelConfig`` plus numpy-converted params/batch — and
rebuild the model inside the worker process on first use (``setup()``
runs post-spawn, so the child pays the JAX import/compile, not the
master at pickle time).

They declare ``start_method = "spawn"``: a forked child must never run
XLA inherited mid-fork; a spawned interpreter initializes JAX cleanly.
The children run JAX on the CPU (``master._child_env``): one process
holds a chip, so this mode is a CPU chaos path, never a TPU path.

Numerics parity: the child computes with the same model code, params
and greedy decode as the in-process paths, so duplicates remain
interchangeable (first-completion-wins) and gradients are the same
per-task values the threaded executor would commit.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence


@dataclasses.dataclass
class TrainTaskRunner:
    """Per-task microbatch gradients, recomputed in the worker process.

    ``params``/``batch`` are numpy pytrees (converted by the executor —
    numpy crosses the pickle boundary cheaply and jit consumes it
    directly).  Payload per task: ``(loss, grads)`` with numpy-leaf
    grads, which the master-side ``TrainBackend.commit`` accumulates
    exactly-once by task id.
    """
    cfg: Any                     # repro.models.config.ModelConfig
    params: Any                  # numpy pytree
    batch: Any                   # dict of numpy arrays
    n_tasks: int

    start_method = "spawn"

    def setup(self) -> None:
        import jax
        from repro.models import build_model
        model = build_model(self.cfg)
        self._grad = jax.jit(
            jax.value_and_grad(lambda p, b: model.loss(p, b)[0]))

    def __call__(self, tasks: Sequence[int]) -> dict:
        import jax
        import numpy as np
        from repro.data import chunk_batch
        B = self.batch["tokens"].shape[0]
        rows = B // self.n_tasks
        out = {}
        for t in tasks:
            loss, grads = self._grad(
                self.params, chunk_batch(self.batch, t * rows, rows))
            out[t] = (float(loss),
                      jax.tree_util.tree_map(np.asarray, grads))
        return out


@dataclasses.dataclass
class ServeTaskRunner:
    """Greedy request decoding, recomputed in the worker process.

    ``requests`` is the picklable projection of the serve batch:
    ``(rid, prompt, max_new_tokens)`` triples indexed by task id.
    Decoding goes through the SAME grouped/padded path as the
    in-process executor (``repro.runtime.serve_executor``), so outputs
    are token-identical across execution modes.
    """
    cfg: Any                     # repro.models.config.ModelConfig
    params: Any                  # numpy pytree
    requests: Any                # list of (rid, prompt np.int32, max_new)
    batch_decode: bool = True
    fused_decode: bool = True    # device-resident prefill + fused scan

    start_method = "spawn"

    def setup(self) -> None:
        import jax
        from repro.models import build_model
        from repro.runtime.serve_executor import FusedGenerator, Request
        self._model = build_model(self.cfg)
        self._decode = jax.jit(self._model.decode_step, donate_argnums=(1,))
        self._gen = FusedGenerator(self._model) if self.fused_decode else None
        self._reqs = {rid: Request(rid, prompt, max_new)
                      for rid, prompt, max_new in self.requests}

    def __call__(self, tasks: Sequence[int]) -> dict:
        from repro.runtime.serve_executor import decode_request_groups
        return decode_request_groups(
            self._model, self.params, self._decode,
            [self._reqs[t] for t in tasks], batch_decode=self.batch_decode,
            generator=self._gen)
