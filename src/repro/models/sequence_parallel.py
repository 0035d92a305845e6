"""Sequence parallelism for the SaP-scan (SSM/WKV recurrences).

Long-context *prefill* of the recurrent architectures shards the sequence
axis across devices.  This is the distributed version of the paper's
split: each device solves its local block of the (block-bidiagonal)
recurrence system, then the inter-device coupling -- the paper's reduced
system, exact for triangular systems -- is resolved by a chain of
``ppermute`` steps carrying (decayed) partial states:

    r_i <- r_{i-1} * D_{i-1} + s_{i-1}        (P-1 neighbor steps)

where s_j is shard j's local carry and D_j its total decay.  The chain is
exact (no truncation needed: triangular system), costs O(P) tiny
messages (one state tensor each), and the local work is the existing
chunked kernel -- so the communication structure is identical to the
SaP solver's preconditioner (DESIGN.md section 4).

The incoming state is folded in analytically (one extra elementwise +
one small einsum), so the local scan runs ONCE -- no second pass.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops


def _prefix_chain(s_loc, ltot_exp, axes):
    """Exact cross-shard prefix of recurrence states.

    s_loc:    local carry with leading (B, H, ...) dims
    ltot_exp: per-shard total decay, broadcastable to s_loc
    Returns r = sum_{j < i} (prod_{j < l < i} D_l) s_j   on shard i.
    """
    n = jax.lax.axis_size(axes)
    perm = [(i, i + 1) for i in range(n - 1)]  # send to next; first gets 0

    def step(_, r):
        payload = r * ltot_exp + s_loc
        return jax.lax.ppermute(payload, axes, perm)

    r0 = jnp.zeros_like(s_loc)
    if n == 1:
        return r0
    return jax.lax.fori_loop(0, n - 1, step, r0)


# ---------------------------------------------------------------------------
# Mamba-2 SSD (scalar per-head decay)
# ---------------------------------------------------------------------------


def sp_ssd_local(x, b, c, loga, axes, chunk: int = 64):
    """Per-shard body (call under shard_map; T is the sharded axis).

    x: (B, H, T_loc, P), b/c: (B, H, T_loc, N), loga: (B, H, T_loc).
    Returns (y, state_out) where state_out on the *last* shard is the
    global final state.
    """
    bsz, h, t_loc, pd = x.shape
    n_state = b.shape[-1]
    zeros = jnp.zeros((bsz, h, n_state, pd), jnp.float32)
    y0, s_loc = kops.ssd(x, b, c, loga, zeros, chunk=min(chunk, t_loc))

    ltot = loga.sum(axis=2)  # (B, H) total log-decay of this shard
    d_exp = jnp.exp(ltot)[..., None, None]  # broadcast to (B, H, N, P)
    r = _prefix_chain(s_loc, d_exp, axes)  # incoming state for this shard

    # fold the incoming state in analytically:
    # y_t += exp(Lcum_t) * (c_t @ r)
    lcum = jnp.cumsum(loga, axis=2)  # (B, H, T_loc)
    y_corr = jnp.exp(lcum)[..., None] * jnp.einsum(
        "bhtn,bhnp->bhtp", c.astype(jnp.float32), r
    )
    s_out = r * d_exp + s_loc
    # states differ per shard; stack them on a sharded leading axis --
    # the caller's global final state is stack[-1]
    return y0 + y_corr, s_out[None]


def sp_ssd(mesh, seq_axes=("data",)):
    """shard_map-wrapped sequence-parallel SSD.

    Inputs are globally-shaped with T sharded over ``seq_axes``; heads may
    additionally be sharded over 'model' by the caller's in_specs.
    Returns (y, states) with states: (n_shards, B, H, N, P); the global
    final state is ``states[-1]``.
    """
    ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    spec_t = P(None, None, ax, None)
    spec_l = P(None, None, ax)
    spec_s = P(ax, None, None, None, None)  # per-shard states, stacked
    fn = partial(sp_ssd_local, axes=seq_axes)
    return jax.shard_map(
        lambda x, b, c, la: fn(x, b, c, la),
        mesh=mesh,
        in_specs=(spec_t, spec_t, spec_t, spec_l),
        out_specs=(spec_t, spec_s),
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# RWKV6 WKV (per-channel decay; state is (Dk, Dv) per head)
# ---------------------------------------------------------------------------


def sp_wkv6_local(r, k, v, logw, u, axes, chunk: int = 64):
    """Per-shard WKV6.  r/k/v/logw: (B, H, T_loc, D); u: (H, D).

    The current-token bonus u is shard-local (applies to position t only),
    so only the running state crosses shards.
    """
    bsz, h, t_loc, d = r.shape
    zeros = jnp.zeros((bsz, h, d, d), jnp.float32)
    o0, s_loc = kops.wkv6(r, k, v, logw, u, zeros, chunk=min(chunk, t_loc))

    ltot = logw.sum(axis=2)  # (B, H, D) per-channel total decay
    d_exp = jnp.exp(ltot)[..., None]  # (B, H, Dk, 1) acts on the k-dim
    rin = _prefix_chain(s_loc, d_exp, axes)

    # fold incoming state: o_t += (r_t * exp(Lprev_t)) @ r_in
    lcum = jnp.cumsum(logw, axis=2)
    lprev = jnp.concatenate(
        [jnp.zeros_like(lcum[:, :, :1]), lcum[:, :, :-1]], axis=2
    )
    o_corr = jnp.einsum(
        "bhtd,bhde->bhte", (r * jnp.exp(lprev)).astype(jnp.float32), rin
    )
    s_out = rin * d_exp + s_loc
    return o0 + o_corr, s_out[None]


def sp_wkv6(mesh, seq_axes=("data",)):
    """Returns (o, states) with states: (n_shards, B, H, Dk, Dv); the
    global final state is ``states[-1]``."""
    ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    spec_t = P(None, None, ax, None)
    spec_u = P(None, None)
    spec_s = P(ax, None, None, None, None)
    fn = partial(sp_wkv6_local, axes=seq_axes)
    return jax.shard_map(
        lambda r, k, v, lw, u: fn(r, k, v, lw, u),
        mesh=mesh,
        in_specs=(spec_t, spec_t, spec_t, spec_t, spec_u),
        out_specs=(spec_t, spec_s),
        check_vma=False,
    )
