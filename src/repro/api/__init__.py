"""One spec to run them all: the declarative RunSpec API.

    from repro import api

    spec = api.RunSpec(
        scheduling=api.SchedulingSpec(technique="FAC"),
        robustness=api.RobustnessSpec(max_duplicates=2),
        cluster=api.scenarios.pe_perturbation(256),   # Table 1
        execution=api.ExecutionSpec(mode="virtual", h=1e-4))
    result = api.simulate(spec, task_times)       # one call
    spec.save("scenario.json")                    # scenarios are data

Same spec, every driver: ``api.simulate(spec, task_times)``,
``RDLBTrainExecutor(model, spec=...)``, ``RDLBServeExecutor(model,
params, spec=...)``, the adaptive portfolio sweep, the benchmarks, and
``python -m repro run --spec file.json``.  Worker perturbations are
stated one way: a :class:`WorkerSpec` per worker in the
:class:`ClusterSpec`.
"""

from repro.api import scenarios  # noqa: F401
from repro.api.facade import (  # noqa: F401
    build, execute, make_scheduler, run, serve_spec, simulate, train_spec,
)
from repro.api.spec import (  # noqa: F401
    DEFAULT_PORTFOLIO, DEVICE_PORTFOLIO, SPEC_VERSION, AdaptiveSpec,
    Candidate, ClusterSpec,
    ExecutionSpec, RobustnessSpec, RunSpec, SchedulingSpec, WorkerSpec,
    spec_override,
)
