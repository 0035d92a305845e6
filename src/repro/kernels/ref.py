"""Pure-jnp oracles for the Pallas kernels in this package.

The oracles are the production reference implementations from
``repro.core.block_lu``, re-exported so kernel tests have a single import
point.
"""

from repro.core.block_lu import (  # noqa: F401  (re-exports)
    BTFactors,
    FusedSpikeFactors,
    btf_ref,
    btf_ul_ref,
    bts_ref,
    fused_factor_spike_padded_ref,
    fused_factor_spike_ref,
    gj_inverse,
)
