from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .service import (  # noqa: F401
    AsyncSolverService,
    Cancelled,
    QueueFull,
    SolveCancelled,
    SolveFuture,
    default_class_overrides,
)
from .solver_engine import (  # noqa: F401
    SolveOutcome,
    SolveRequest,
    SolverEngine,
    band_dominance,
    matrix_fingerprint,
)
