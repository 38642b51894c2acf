"""rDLB training executor: the paper's technique as a JAX runtime feature.

One global training step = N independent TASKS (grad-accumulation
microbatches, each a fixed-shape jitted computation over a slice of the
global batch).  Tasks are self-scheduled to WORKERS (data-parallel worker
groups; simulated in-process on CPU) through the SAME unified engine
(repro.core.engine) the discrete-event simulator drives — this executor
only supplies a ``TrainBackend`` (microbatch gradients, exactly-once
reduction):

  * a free worker requests work; the DLS technique sizes its chunk of tasks;
  * with rDLB, once every task is assigned, idle workers receive DUPLICATES
    of in-flight tasks (oldest first) — no failure detection anywhere;
  * gradient accumulation is EXACTLY-ONCE BY TASK ID: a duplicate's result
    is discarded if the original already landed (and vice versa).  Because
    the data pipeline is content-addressed (repro.data), a re-executed task
    computes bit-identical gradients, so which copy wins is irrelevant;
  * fail-stop workers simply never report; their in-flight tasks are
    re-issued to survivors.  Up to W-1 worker losses are tolerated within
    a step (the paper's P-1 claim, at chunk granularity);
  * without rDLB, a failure turns the step into the paper's Fig. 1b hang —
    surfaced as ``StepResult.hung`` instead of an infinite wait.

Configuration is a declarative :class:`repro.api.RunSpec`
(``RDLBTrainExecutor(model, spec=spec)``, built with ``api.train_spec``).
Worker perturbations (stragglers via ``speed``, count-based fail-stops
via ``fail_after_tasks``, ...) are declared on ``spec.cluster``, the ONE
vocabulary and the only constructor of ``EngineWorker`` lists.

After a step with losses, ``runtime.elastic`` shrinks the worker set (and,
on hardware, re-meshes + re-shards via the checkpoint substrate).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from repro import api
from repro.data import chunk_batch
from repro.optim import apply_updates, clip_by_global_norm, make_optimizer
from repro.runtime.backends import TrainBackend


@dataclasses.dataclass
class WorkerState:
    wid: int
    alive: bool = True
    speed: float = 1.0                    # <1.0 = straggler
    fail_after_tasks: Optional[int] = None  # fail-stop after N task execs
    tasks_done: int = 0                   # executed (incl. wasted)
    credit: float = 0.0
    # The spec-declared WorkerSpec this state was materialized from —
    # carries perturbations the live fields above don't track
    # (fail_time, msg_latency, sleep_per_task) back into each step's
    # ClusterSpec.  None = nominal.
    profile: Optional[api.WorkerSpec] = None


@dataclasses.dataclass
class StepResult:
    params: Any
    opt_state: Any
    loss: float
    hung: bool
    n_tasks: int
    n_duplicates: int
    wasted_tasks: int
    tasks_by_worker: dict
    survivors: list


class RDLBTrainExecutor:
    """Drives model training with DLS + rDLB task scheduling.

    Parameters
    ----------
    model:       any repro.models model (has .loss(params, batch)).
    spec:        a :class:`repro.api.RunSpec` — scheduling technique,
                 rDLB knobs, cluster (worker count + perturbations),
                 execution mode (``"threaded"`` = real OS threads whose
                 duplicates race in wall-clock time), adaptive policy.
                 ``spec.n_tasks`` is the grad-accum microbatches per
                 global step.
    optimizer/lr/grad_clip/loss_fn: training-side knobs (not scheduling
                 — deliberately outside the spec).
    exact_accumulation: store per-task grads and reduce in task order —
                 bit-identical results regardless of schedule (used by the
                 equality tests); False accumulates in arrival order.
    adaptive:    optional live adaptive policy object
                 (repro.adaptive.AdaptiveController), overriding
                 ``spec.adaptive``.
    """

    def __init__(self, model, *, spec: api.RunSpec,
                 optimizer: str = "adamw", lr: float = 1e-3,
                 grad_clip: float = 1.0, exact_accumulation: bool = False,
                 loss_fn: Optional[Callable] = None,
                 adaptive: Optional[Any] = None):
        if spec.n_tasks is None:
            raise ValueError("training needs spec.n_tasks (microbatches "
                             "per global step)")
        self.spec = spec
        self.n_workers = spec.cluster.n_workers
        self.n_tasks = spec.n_tasks
        self.model = model
        self.exact_accumulation = exact_accumulation
        self.adaptive = adaptive
        self.opt = make_optimizer(optimizer, lr=lr)
        self.grad_clip = grad_clip
        self._custom_loss = loss_fn is not None
        base_loss = loss_fn or (lambda p, b: model.loss(p, b)[0])
        self._grad_fn = jax.jit(jax.value_and_grad(base_loss))
        self.reset_workers()

    # ------------------------------------------------------------- helpers
    def reset_workers(self) -> None:
        """(Re)materialize live worker state from the spec's cluster."""
        self.workers = [
            WorkerState(wid, alive=w.alive, speed=w.speed,
                        fail_after_tasks=w.fail_after_tasks, profile=w)
            for wid, w in enumerate(self.spec.cluster.worker_specs())]

    @property
    def alive_workers(self) -> list[WorkerState]:
        return [w for w in self.workers if w.alive]

    def _task_batch(self, batch: dict, task_id: int) -> dict:
        B = batch["tokens"].shape[0]
        rows = B // self.n_tasks
        return chunk_batch(batch, task_id * rows, rows)

    # ---------------------------------------------------------------- step
    def train_step(self, params, opt_state, batch: dict, *,
                   max_rounds: Optional[int] = None) -> StepResult:
        B = batch["tokens"].shape[0]
        assert B % self.n_tasks == 0, (B, self.n_tasks)
        # The step's cluster is the LIVE worker state (liveness and
        # speeds learned so far), through the one vocabulary.
        cluster = api.ClusterSpec.from_worker_states(
            self.workers, name=self.spec.cluster.name or "train")
        spec = self.spec.replace(cluster=cluster, n_tasks=self.n_tasks)
        if max_rounds is not None:
            spec = spec.override("execution.horizon", float(max_rounds))
        backend = TrainBackend(
            lambda t: self._grad_fn(params, self._task_batch(batch, t)),
            exact_accumulation=self.exact_accumulation)
        factory = None
        if spec.execution.mode == "process":
            # workers as real OS processes: the jitted closure cannot
            # cross the boundary, so ship the RECIPE (config + numpy
            # params/batch) and let the child rebuild grad_fn; grads
            # come back as numpy and accumulate exactly-once as usual.
            # NOTE: every step spawns fresh interpreters that re-import
            # JAX and re-jit (seconds per worker) — process mode is the
            # fault-tolerance testbed, not a fast multi-step training
            # path; a persistent worker pool is future work
            from repro.cluster import TrainTaskRunner  # lazy import
            cfg = getattr(self.model, "cfg", None)
            if cfg is None or self._custom_loss:
                raise ValueError(
                    "process mode needs a model with .cfg (rebuildable "
                    "via models.build_model) and the default loss path")
            import numpy as np
            factory = TrainTaskRunner(
                cfg, jax.tree_util.tree_map(np.asarray, params),
                jax.tree_util.tree_map(np.asarray, batch), self.n_tasks)
        eng = api.build(spec, backend, n_tasks=self.n_tasks,
                        adaptive=self.adaptive, factory=factory)
        for ew, w in zip(eng.workers, self.workers):
            ew.tasks_done = w.tasks_done     # count-based fail-stop state
        stats = api.run(spec, eng)
        for w, ew in zip(self.workers, eng.workers):  # liveness flows back
            w.alive, w.tasks_done = ew.alive, ew.tasks_done

        queue = eng.queue
        grad_acc = backend.reduced()
        if stats.hung or grad_acc is None:
            return StepResult(params, opt_state, float("nan"), True,
                              self.n_tasks, queue.n_duplicates,
                              queue.wasted_tasks, dict(stats.by_worker),
                              [w.wid for w in self.alive_workers])

        grads = jax.tree_util.tree_map(lambda g: g / self.n_tasks, grad_acc)
        grads, _ = clip_by_global_norm(grads, self.grad_clip)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return StepResult(params, opt_state,
                          backend.loss_sum / max(1, backend.n_done),
                          False, self.n_tasks, queue.n_duplicates,
                          queue.wasted_tasks, dict(stats.by_worker),
                          [w.wid for w in self.alive_workers])
