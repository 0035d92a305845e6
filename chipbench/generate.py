"""Seeded inputs of the benchmark: banded systems and right-hand sides.

Everything here comes from ``--seed`` alone.  Matrices and right-hand sides
are made on the device, many at a time, in one jitted call each, in the
float32 they are solved in.  The law is the paper's random banded family
(arXiv:1509.07919 Sec. 4.1): off-diagonal entries U(-1, 1), entries that
fall outside the matrix zero, and |a_ii| = d * sum_{j != i} |a_ij|.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# Sub-stream tags folded into the seed's key, one per kind of input.
BANDS, RHS, WARM = 1, 2, 3


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one above 2**32."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def stream(seed: int, tag: int) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), tag)


@partial(jax.jit, static_argnames=("count", "n", "k"))
def _bands(key, d, count: int, n: int, k: int):
    def one(kk):
        band = jax.random.uniform(
            kk, (n, 2 * k + 1), jnp.float32, minval=-1.0, maxval=1.0
        )
        col = jnp.arange(n)[:, None] - k + jnp.arange(2 * k + 1)[None, :]
        band = jnp.where((col >= 0) & (col < n), band, 0.0)
        off = jnp.sum(jnp.abs(band), axis=1) - jnp.abs(band[:, k])
        sign = jnp.where(band[:, k] >= 0, 1.0, -1.0)
        return band.at[:, k].set(sign * jnp.maximum(d * off, 1e-3))

    return jax.vmap(one)(jax.random.split(key, count))


def bands(key, count: int, n: int, k: int, d: float) -> jax.Array:
    """(count, n, 2k+1) float32 band-storage matrices on the device."""
    return _bands(key, jnp.float32(d), count, n, k)


@partial(jax.jit, static_argnames=("shape",))
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def normal(key, shape: tuple) -> jax.Array:
    """Standard normal float32 array on the device (right-hand sides)."""
    return _normal(key, tuple(shape))

