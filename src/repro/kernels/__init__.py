"""Pallas TPU kernels for SaP::TPU's compute hot-spots.

The paper hand-optimizes exactly these stages on the GPU (Sec. 3.1); here
they are TPU-native Pallas kernels:

  * ``btf``         -- block-tridiagonal factorization (the paper's banded
                       LU "window sliding", re-blocked for the MXU)
  * ``bts``         -- forward/backward block solves (preconditioner apply
                       + spike computation)
  * ``fused_spike`` -- factor + spike-corner extraction in one pass
  * ``bcr``         -- block cyclic reduction (log-depth SaP-E interface
                       chain factor/solve)

``ops`` holds the jit'd dispatch wrappers, ``ref`` the pure-jnp oracles.
"""

from . import ops, ref  # noqa: F401
from .ops import (  # noqa: F401
    block_tridiag_factor,
    block_tridiag_solve,
    default_impl,
)
