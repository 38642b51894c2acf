"""Worker backends for the unified self-scheduling engine.

The engine (repro.core.engine) owns the master-worker loop — request,
liveness, barrier polling, hang surfacing, metrics.  A backend only
defines what a chunk of tasks IS:

  * :class:`FnBackend`      — run a Python callable per task (parity tests,
                              run_to_completion-style draining of real work);
  * :class:`ChunkBackend`   — run a whole chunk as ONE call (the paper's
                              loops: one kernel launch per chunk);
  * :class:`TrainBackend`   — grad-accumulation microbatches with
                              exactly-once-by-task-id reduction;
  * :class:`ServeBackend`   — inference requests, decoded per-request or as
                              padded jitted batches, first-completion-wins.

Backends never talk to the queue; ``commit`` receives the task ids its
report newly finished, so a duplicate's payload is applied only for tasks
it won.  ``commit`` runs under the engine's commit lock in threaded mode.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace as trc
from repro.core.engine import WorkerBackend
from repro.core.rdlb import Chunk


class FnBackend(WorkerBackend):
    """Execute ``task_fn(task_id)`` per task; optional nominal costs.

    With ``task_times`` the scheduling timeline is identical to the
    simulator backend over the same costs — the sim/exec parity seam.
    """

    def __init__(self, task_fn: Optional[Callable[[int], Any]] = None,
                 task_times: Optional[Sequence[float]] = None) -> None:
        self.task_fn = task_fn
        self._ctime = (None if task_times is None else
                       np.cumsum(np.concatenate([[0.0], task_times])))
        self.results: dict[int, Any] = {}     # exactly-once, by task id

    def execute(self, chunk: Chunk, wid: int) -> Any:
        if self.task_fn is None:
            return None
        return {t: self.task_fn(t) for t in chunk.tasks()}

    def cost(self, chunk: Chunk, wid: int) -> float:
        if self._ctime is None:
            return float(chunk.size)
        return float(self._ctime[chunk.stop] - self._ctime[chunk.start])

    def commit(self, chunk: Chunk, wid: int, payload: Any,
               newly: list[int]) -> None:
        if payload is None:
            return
        for t in newly:
            self.results[t] = payload[t]


def chunk_to_host(dispatch: Callable[[], Any]) -> np.ndarray:
    """A chunk entry's device program, and its result brought to the
    host, as two program spans (``core/trace.py`` names them):
    ``chunk.dispatch`` is ``dispatch()`` (the input lookups, the inputs
    sent to the device, the jitted call's return), ``chunk.to_host`` the
    ``np.asarray`` of its result, which asks for the copy, waits for the
    device and copies in one blocking call."""
    with trc.span("chunk.dispatch"):
        out = dispatch()
    with trc.span("chunk.to_host"):
        return np.asarray(out)


class ChunkBackend(WorkerBackend):
    """Execute a chunk as ONE call ``chunk_fn(start, stop)``, which
    returns an array with one row per task of ``[start, stop)`` — the
    paper's loops, whose task bodies are one kernel launch per chunk.

    ``commit`` writes the rows a report newly finished into ``results``
    (``n_tasks`` rows), exactly once by task id: a duplicate's rows land
    only for tasks it won."""

    def __init__(self, chunk_fn: Callable[[int, int], Any],
                 n_tasks: int) -> None:
        self.chunk_fn = chunk_fn
        self.n_tasks = n_tasks
        self.results: Optional[np.ndarray] = None

    def execute(self, chunk: Chunk, wid: int) -> Any:
        return np.asarray(self.chunk_fn(chunk.start, chunk.stop))

    def commit(self, chunk: Chunk, wid: int, payload: Any,
               newly: list[int]) -> None:
        if self.results is None:
            self.results = np.zeros((self.n_tasks,) + payload.shape[1:],
                                    payload.dtype)
        rows = np.asarray(newly, dtype=np.int64)
        self.results[rows] = payload[rows - chunk.start]


class TrainBackend(WorkerBackend):
    """Grad-accum microbatches; exactly-once gradient reduction.

    ``grad_fn(task_id) -> (loss, grads)`` computes one microbatch.  A
    duplicate executes (wasted work, as in the paper) but ``commit`` only
    accumulates tasks its report won, so k fail-stop workers change
    nothing about the computed update.

    exact_accumulation: store per-task grads and reduce in task order at
    the end — bit-identical results regardless of schedule.  Otherwise
    accumulate in report-arrival order (cheaper; order is deterministic
    in virtual-time mode, racy in threaded mode).
    """

    def __init__(self, grad_fn: Callable[[int], tuple], *,
                 exact_accumulation: bool = False) -> None:
        self.grad_fn = grad_fn
        self.exact = exact_accumulation
        self.per_task: dict[int, Any] = {}
        self.grad_acc = None
        self.loss_sum = 0.0
        self.n_done = 0

    def execute(self, chunk: Chunk, wid: int) -> Any:
        return {t: self.grad_fn(t) for t in chunk.tasks()}

    def commit(self, chunk: Chunk, wid: int, payload: Any,
               newly: list[int]) -> None:
        for t in newly:
            loss, grads = payload[t]
            self.loss_sum += float(loss)
            self.n_done += 1
            if self.exact:
                self.per_task[t] = grads
            elif self.grad_acc is None:
                self.grad_acc = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
            else:
                self.grad_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32),
                    self.grad_acc, grads)

    def reduced(self) -> Any:
        """Final accumulated gradients (fixed task order when exact)."""
        if not self.exact:
            return self.grad_acc
        acc = None
        for t in sorted(self.per_task):
            g = self.per_task[t]
            if acc is None:
                acc = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), g)
            else:
                acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
        return acc


class ServeBackend(WorkerBackend):
    """Inference requests; first-completion-wins output commit.

    ``generate_fn(requests) -> {rid: tokens}`` decodes a chunk's requests
    (per-request loop or one padded batch — the engine doesn't care).
    Greedy decode is deterministic, so duplicates are interchangeable and
    whichever report lands first fixes the output.
    """

    def __init__(self, requests: Sequence,
                 generate_fn: Callable[[list], dict]) -> None:
        self.requests = requests
        self.generate_fn = generate_fn

    def execute(self, chunk: Chunk, wid: int) -> Any:
        return self.generate_fn([self.requests[r] for r in chunk.tasks()])

    def commit(self, chunk: Chunk, wid: int, payload: Any,
               newly: list[int]) -> None:
        for rid in newly:
            req = self.requests[rid]
            req.output = payload[rid]
            req.completed_by = wid
            req.duplicated = chunk.duplicate
