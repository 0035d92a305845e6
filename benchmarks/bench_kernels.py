"""Kernel microbenchmarks: jnp reference path vs Pallas interpret path
(correctness-weighted; true kernel perf numbers require TPU hardware)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import Report, timeit


def bench_btf(report: Report):
    rng = np.random.default_rng(0)
    for (p, m, k) in [(8, 16, 16), (16, 32, 32)]:
        d = jnp.asarray(rng.normal(size=(p, m, k, k)), jnp.float32) + 4 * jnp.eye(k)
        e = jnp.asarray(rng.normal(size=(p, m, k, k)) * 0.3, jnp.float32)
        f = jnp.asarray(rng.normal(size=(p, m, k, k)) * 0.3, jnp.float32)
        us_j = timeit(lambda: ops.block_tridiag_factor(d, e, f, impl="jnp").sinv)
        report.add(f"kernel/btf/jnp/P{p}xM{m}xK{k}", us_j,
                   f"flops~{p*m*8*k**3:.2e}")
        fac = ref.btf_ref(d, e, f)
        b = jnp.asarray(rng.normal(size=(p, m, k, 4)), jnp.float32)
        us_s = timeit(lambda: ops.block_tridiag_solve(fac, b, impl="jnp"))
        report.add(f"kernel/bts/jnp/P{p}xM{m}xK{k}", us_s, "")


def bench_bcr_chain(report: Report):
    """Sequential chain sweep vs log-depth cyclic reduction (the SaP-E
    reduced interface system).  The jnp chain sweep is an O(M) lax.scan;
    BCR is log2(M) levels of batched matmuls -- the depth gap is the
    point, and it widens with the chain length (= partition count)."""
    from repro.core.block_lu import btf_chain, bts_chain
    from repro.core.cyclic_reduction import bcr_factor, bcr_solve

    rng = np.random.default_rng(2)
    k = 16
    for m in (15, 63, 255, 1023):
        # shaped like the SaP-E reduced chain: identity diagonal blocks
        # plus spike-corner couplings well inside the unit disk
        d = jnp.asarray(rng.normal(size=(m, k, k)) * 0.1, jnp.float32) + jnp.eye(k)
        e = jnp.asarray(rng.normal(size=(m, k, k)) * 0.05, jnp.float32)
        f = jnp.asarray(rng.normal(size=(m, k, k)) * 0.05, jnp.float32)
        b = jnp.asarray(rng.normal(size=(m, k, 4)), jnp.float32)

        jf_seq = jax.jit(btf_chain)
        jf_bcr = jax.jit(bcr_factor)
        us_fs = timeit(lambda: jf_seq(d, e, f).sinv)
        us_fb = timeit(lambda: jf_bcr(d, e, f).root_inv)
        report.add(f"kernel/chain_factor/seq/M{m}xK{k}", us_fs, "lax.scan sweep")
        report.add(f"kernel/chain_factor/bcr/M{m}xK{k}", us_fb,
                   f"levels={max(m - 1, 0).bit_length()};"
                   f"speedup={us_fs / us_fb:.2f}x")

        fac_seq = jf_seq(d, e, f)
        fac_bcr = jf_bcr(d, e, f)
        js_seq = jax.jit(bts_chain)
        js_bcr = jax.jit(bcr_solve)
        x_seq = js_seq(fac_seq, b)
        x_bcr = js_bcr(fac_bcr, b)
        err = float(jnp.abs(x_seq - x_bcr).max())
        us_ss = timeit(lambda: js_seq(fac_seq, b))
        us_sb = timeit(lambda: js_bcr(fac_bcr, b))
        report.add(f"kernel/chain_solve/seq/M{m}xK{k}", us_ss, "")
        report.add(f"kernel/chain_solve/bcr/M{m}xK{k}", us_sb,
                   f"speedup={us_ss / us_sb:.2f}x;maxdiff={err:.1e}")


def run(report: Report):
    bench_btf(report)
    bench_bcr_chain(report)
