"""Discrete-event simulation of master-worker DLS execution (+- rDLB):
the timing-only backend and the result record.

Reproduces the *timing* behaviour of the paper's MPI DLS4LB experiments
on one CPU: P PEs self-schedule N tasks from the central RobustQueue; the
master (PE 0, which also computes, as in DLS4LB) serializes scheduling
transactions with overhead ``h``; failures drop in-flight chunks;
perturbations slow PEs or delay their messages.

A simulation is the unified engine (repro.core.engine) over
:class:`SimBackend`, which executes nothing — only nominal task costs
matter — while the engine's virtual-time event loop provides exact
causality (an rDLB duplicate is only issued if, at that instant, the
original chunk is still unfinished).  The SAME engine loop drives the
real JAX executors (repro.runtime), so simulated and executed schedules
cannot diverge: same (technique, cluster, seed) -> same assignment log.
The entry point is ``repro.api.simulate(spec, task_times)``.

Without rDLB and with a failure/hang, the execution never terminates —
reported as ``t_par = inf`` (the paper's "would wait indefinitely").
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core import engine, rdlb


@dataclasses.dataclass
class SimResult:
    t_par: float                 # parallel loop execution time (inf = hang)
    n_finished: int
    n_tasks: int
    n_assignments: int
    n_duplicates: int
    wasted_tasks: int            # task executions whose result was discarded
    pe_busy: np.ndarray          # per-PE total compute seconds
    pe_idle: np.ndarray          # per-PE idle-before-termination seconds
    technique: str
    scenario: str
    rdlb: bool
    adaptive_decisions: list = dataclasses.field(default_factory=list)
                                 # DecisionRecords when an adaptive policy
                                 # watched the run (spec.adaptive.enabled)
    t_wall: float = 0.0          # wall-clock seconds (== t_par only in
                                 # threaded/process modes, where time IS
                                 # wall time)
    chaos_events: list = dataclasses.field(default_factory=list)
                                 # real OS actions (process mode)
    trace: object = None         # core.trace.Trace when the spec enabled
                                 # the flight recorder; None otherwise
    metrics: object = None       # MetricsHub.snapshot() dict when the spec
                                 # enabled live telemetry; None otherwise

    @property
    def hang(self) -> bool:
        return math.isinf(self.t_par)

    @property
    def wasted_fraction(self) -> float:
        return self.wasted_tasks / max(1, self.n_tasks)

    def to_dict(self, *, include_trace: bool = True) -> dict:
        """JSON-serializable run record (``python -m repro run
        --emit-json``)."""

        def _rec(x):
            f = getattr(x, "to_dict", None)
            return f() if callable(f) else (
                dataclasses.asdict(x) if dataclasses.is_dataclass(x)
                and not isinstance(x, type) else repr(x))

        d = dict(
            t_par=None if math.isinf(self.t_par) else float(self.t_par),
            hang=self.hang,
            n_finished=int(self.n_finished),
            n_tasks=int(self.n_tasks),
            n_assignments=int(self.n_assignments),
            n_duplicates=int(self.n_duplicates),
            wasted_tasks=int(self.wasted_tasks),
            pe_busy=np.asarray(self.pe_busy).tolist(),
            pe_idle=np.asarray(self.pe_idle).tolist(),
            technique=self.technique,
            scenario=self.scenario,
            rdlb=bool(self.rdlb),
            t_wall=float(self.t_wall),
            adaptive_decisions=[_rec(x) for x in self.adaptive_decisions],
            chaos_events=[_rec(x) for x in self.chaos_events],
        )
        if include_trace and self.trace is not None:
            d["trace"] = self.trace.to_dict()
        if self.metrics is not None:
            d["metrics"] = self.metrics
        return d


class SimBackend(engine.WorkerBackend):
    """Timing-only backend: execution is a no-op; cost is the chunk's
    nominal task time (prefix sums over ``task_times``).

    ``ctime`` (the prefix-sum array, public) is the vectorized cost
    interface: the engine's fast-forward (repro.core.fastpath) reads
    chunk costs for whole rounds straight from it instead of calling
    ``cost`` per chunk.
    """

    def __init__(self, task_times: np.ndarray) -> None:
        self.ctime = np.cumsum(np.concatenate([[0.0], task_times]))

    def cost(self, chunk: rdlb.Chunk, wid: int) -> float:
        return float(self.ctime[chunk.stop] - self.ctime[chunk.start])
