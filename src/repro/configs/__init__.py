"""Solver workload configurations.

``sap_solver`` is the paper's own workload (banded linear solve): the
``SolverConfig`` presets (``full``, ``reduced``, ``exact``, ``service``,
``fleet``) and the dry-run ``SOLVER_SHAPES``.
"""

from . import sap_solver  # noqa: F401
