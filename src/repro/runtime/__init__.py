from repro.runtime.backends import (  # noqa: F401
    ChunkBackend, FnBackend, ServeBackend, TrainBackend,
)
from repro.runtime.executor import (  # noqa: F401
    RDLBTrainExecutor, StepResult, WorkerState,
)
from repro.runtime.serve_executor import (  # noqa: F401
    RDLBServeExecutor, Request, ServeStats,
)
