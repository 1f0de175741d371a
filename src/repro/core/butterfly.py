"""ButterflyClip numerics (paper Alg. 2/5) + the O(n^2)-scalar verification
tables (Alg. 6): pure-jnp, shape (n_peers, d) -> robust average (d,).

Two call modes share this math:
  * simulated — stacked peer axis on one device (tests, controlled §4.1 runs);
  * distributed — launch/train.py wraps the same per-partition CenteredClip
    in a shard_map all_to_all/all_gather over the mesh peer axes.

Partitioning pads d to a multiple of n (the paper's SPLIT uses uneven parts;
padding with zeros is numerically identical for aggregation and keeps XLA
shapes static — recorded in DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.centered_clip import (
    centered_clip_adaptive_stacked,
    centered_clip_stacked,
    clip_residuals,
)


def pad_to_parts(d: int, n: int) -> int:
    return -(-d // n) * n


def split_parts(grads, n_parts):
    """(n, d) -> (n, n_parts, part) with zero padding."""
    n, d = grads.shape
    dp = pad_to_parts(d, n_parts)
    if dp != d:
        grads = jnp.pad(grads, ((0, 0), (0, dp - d)))
    return grads.reshape(n, n_parts, dp // n_parts)


def merge_parts(agg, d):
    """(n_parts, part) -> (d,)."""
    return agg.reshape(-1)[:d]


def butterfly_clip(
    grads, tau, n_iters: int = 50, weights=None, use_pallas=False, v0=None
):
    """Robust butterfly all-reduce: partition j is CenteredClip-aggregated
    across peers (by peer j in the real topology). Returns (agg_parts, parts).

    grads: (n, d). agg_parts: (n_parts, part). parts: (n, n_parts, part).
    use_pallas: run the aggregation through the fused all-partition TPU
    kernel (kernels/centered_clip.butterfly_clip_pallas).
    v0: optional (n_parts, part) warm start — the previous step's aggregate
    (cuts the iteration budget; see kernels/DESIGN.md warm-start section).
    """
    n = grads.shape[0]
    parts = split_parts(grads, n)

    if use_pallas:
        from repro.kernels.ops import butterfly_clip_op

        agg = butterfly_clip_op(
            jnp.swapaxes(parts, 0, 1), tau, weights, n_iters=n_iters, v0=v0
        )
        return agg, parts

    stacked = jnp.swapaxes(parts, 0, 1)  # (n_parts, n, part)
    agg = centered_clip_stacked(
        stacked, tau, n_iters=n_iters, weights=weights, v0=v0
    )
    return agg, parts


def butterfly_clip_adaptive(
    grads, tau, tol, max_iters: int, weights=None, use_pallas=False, v0=None
):
    """Adaptive-budget ButterflyClip aggregation: each partition's
    CenteredClip runs until ``||v_{l+1}-v_l|| <= tol`` (static ``max_iters``
    cap) under a ``lax.while_loop`` — the fixed point is unchanged, only the
    iteration budget adapts (warm starts via ``v0`` compound the saving).

    Returns (agg_parts (n_parts, part), parts (n, n_parts, part),
    iters (n_parts,) i32). use_pallas routes through the early-exit
    one-pass-per-iteration kernel driver (kernels/ops).
    """
    n = grads.shape[0]
    parts = split_parts(grads, n)
    stacked = jnp.swapaxes(parts, 0, 1)

    if use_pallas:
        from repro.kernels.ops import butterfly_clip_adaptive_op

        agg, iters = butterfly_clip_adaptive_op(
            stacked, tau, tol, weights, v0=v0, max_iters=max_iters
        )
        return agg, parts, iters

    agg, iters = centered_clip_adaptive_stacked(
        stacked, tau, tol, max_iters, weights=weights, v0=v0
    )
    return agg, parts, iters


def butterfly_clip_verified_adaptive(
    grads, tau, z, tol, max_iters: int, weights=None, use_pallas=False,
    v0=None,
):
    """Adaptive aggregation PLUS the Alg. 6 broadcast tables.

    The tables are a deterministic function of (parts, agg, z): however many
    iterations the early exit took, the verification epilogue runs EXACTLY
    once against the final iterate, so every peer recomputing the tables
    from the broadcast aggregate gets identical values (the accusation
    semantics never see the iteration count — kernels/DESIGN.md).

    Returns (agg_parts, parts, s (n, n_parts), norms (n, n_parts),
    iters (n_parts,) i32).
    """
    if use_pallas:
        from repro.kernels.ops import butterfly_clip_fused_adaptive_op

        n = grads.shape[0]
        parts = split_parts(grads, n)
        agg, s, norms, iters = butterfly_clip_fused_adaptive_op(
            jnp.swapaxes(parts, 0, 1), tau, z, tol, weights, v0=v0,
            max_iters=max_iters,
        )
        return agg, parts, s, norms, iters
    agg, parts, iters = butterfly_clip_adaptive(
        grads, tau, tol, max_iters, weights=weights, v0=v0
    )
    s, norms = verification_tables(parts, agg, z, tau)
    return agg, parts, s, norms, iters


def _clip_verified_fixed(
    grads, tau, z, n_iters: int = 50, weights=None, use_pallas=False, v0=None
):
    """Fixed-budget ButterflyClip aggregation AND the Alg. 6 broadcast
    tables together (the :func:`clip_aggregate` fixed/verified branch).

    grads: (n, d); z: (n_parts, part) unit directions (from the MPRNG seed).
    Returns (agg_parts (n_parts, part), parts (n, n_parts, part),
    s (n, n_parts), norms (n, n_parts)).

    use_pallas routes through the fused one-pass-per-iteration kernel
    (kernels/centered_clip.butterfly_clip_fused_pallas): the whole robust
    aggregation plus tables costs n_iters + 2 HBM passes of the stacked
    partitions instead of 2*n_iters + 1 (see kernels/DESIGN.md).
    v0: optional (n_parts, part) warm start (previous aggregate).
    """
    n = grads.shape[0]
    parts = split_parts(grads, n)
    stacked = jnp.swapaxes(parts, 0, 1)  # (n_parts, n, part)

    if use_pallas:
        from repro.kernels.ops import butterfly_clip_fused_op

        agg, s, norms = butterfly_clip_fused_op(
            stacked, tau, z, weights, n_iters=n_iters, v0=v0
        )
        return agg, parts, s, norms

    agg = centered_clip_stacked(
        stacked, tau, n_iters=n_iters, weights=weights, v0=v0
    )
    s, norms = verification_tables(parts, agg, z, tau)
    return agg, parts, s, norms


def clip_aggregate(
    grads, tau, n_iters: int, *, z=None, adaptive_tol=None, weights=None,
    use_pallas=False, v0=None,
):
    """Unified ButterflyClip driver — the single entry the AggregatorSpec
    registry resolves to (``core.aggregators``): fixed (``adaptive_tol is
    None``) or adaptive early-exit budget, with (``z`` given) or without the
    Alg. 6 verification tables.

    Returns (agg (n_parts, part), parts (n, n_parts, part), s, norms,
    iters () i32); s/norms are None when z is None; iters is the max
    CenteredClip budget any partition ran (== n_iters on the fixed path).
    """
    if z is None:
        if adaptive_tol is not None:
            agg, parts, it = butterfly_clip_adaptive(
                grads, tau, adaptive_tol, n_iters, weights=weights,
                use_pallas=use_pallas, v0=v0,
            )
            return agg, parts, None, None, it.max().astype(jnp.int32)
        agg, parts = butterfly_clip(
            grads, tau=tau, n_iters=n_iters, weights=weights,
            use_pallas=use_pallas, v0=v0,
        )
        return agg, parts, None, None, jnp.asarray(n_iters, jnp.int32)
    if adaptive_tol is not None:
        agg, parts, s, norms, it = butterfly_clip_verified_adaptive(
            grads, tau, z, adaptive_tol, n_iters, weights=weights,
            use_pallas=use_pallas, v0=v0,
        )
        return agg, parts, s, norms, it.max().astype(jnp.int32)
    agg, parts, s, norms = _clip_verified_fixed(
        grads, tau, z, n_iters=n_iters, weights=weights,
        use_pallas=use_pallas, v0=v0,
    )
    return agg, parts, s, norms, jnp.asarray(n_iters, jnp.int32)


def butterfly_clip_verified(
    grads, tau, z, n_iters: int = 50, weights=None, use_pallas=False, v0=None
):
    """DEPRECATED shim — resolve an :class:`~repro.core.aggregators.
    AggregatorSpec` instead (``verified_aggregate``); kept so pre-spec call
    sites keep working. Same contract as :func:`_clip_verified_fixed`."""
    import warnings

    warnings.warn(
        "butterfly_clip_verified is deprecated; select the aggregation via "
        "an AggregatorSpec (repro.core.aggregators.verified_aggregate / "
        "EngineConfig.aggregator) instead",
        DeprecationWarning, stacklevel=2,
    )
    from repro.core.aggregators import AggregatorSpec, verified_aggregate

    spec = AggregatorSpec(
        "butterfly_clip",
        (("adaptive_tol", None), ("n_iters", int(n_iters)),
         ("tau", float(tau)), ("warm_start", v0 is not None)),
    )
    agg, parts, s, norms, _iters = verified_aggregate(
        spec, grads, z, weights=weights, v0=v0, use_pallas=use_pallas
    )
    return agg, parts, s, norms


def get_random_directions(seed, n_parts: int, part: int):
    """z[j] — unit vector per partition from the MPRNG seed (Alg. 1 L5).

    Every peer derives the same z from the shared scalar seed, AFTER all
    aggregation hashes are committed.
    """
    key = jax.random.key(seed) if jnp.ndim(seed) == 0 else seed
    z = jax.random.normal(key, (n_parts, part), jnp.float32)
    return z / jnp.maximum(jnp.linalg.norm(z, axis=1, keepdims=True), 1e-30)


def verification_tables(parts, agg, z, tau, use_pallas=False):
    """Broadcast tables of Alg. 6: s[i, j] = <z[j], Delta_i^j>, norm[i, j].

    parts: (n, n_parts, part); agg: (n_parts, part); z: (n_parts, part).
    use_pallas: single-HBM-pass batched kernel instead of the vmapped jnp
    path (used standalone when agg changed after the fused aggregation,
    e.g. recomputing tables against a corrupted aggregate).
    """
    if use_pallas:
        from repro.kernels.ops import verify_tables_all_op

        return verify_tables_all_op(jnp.swapaxes(parts, 0, 1), agg, z, tau)

    def per_part(xs_j, v_j, z_j):
        deltas = clip_residuals(xs_j, v_j, tau)  # (n, part)
        s_j = deltas.astype(jnp.float32) @ z_j.astype(jnp.float32)
        norms_j = jnp.linalg.norm((xs_j - v_j[None]).astype(jnp.float32), axis=1)
        return s_j, norms_j

    s, norms = jax.vmap(per_part, in_axes=(1, 0, 0), out_axes=1)(parts, agg, z)
    return s, norms  # both (n, n_parts)


def checksum_violations(s, weights, tol):
    """Verification 2 checksum: |sum_i s_i^j| per partition (Alg. 1 L14).

    Returns (sums (n_parts,), violated (n_parts,) bool).
    """
    w = s if weights is None else s * weights[:, None]
    sums = w.sum(0)
    return sums, jnp.abs(sums) > tol


def delta_max_votes(norms, weights, delta_max):
    """Verification 3: fraction of active peers whose partition residual
    exceeds Delta_max; a majority vote triggers CHECKAVERAGING(j)."""
    active = norms.shape[0] if weights is None else jnp.maximum(weights.sum(), 1.0)
    check = norms > delta_max  # (n, n_parts)
    if weights is not None:
        check = check & (weights[:, None] > 0)
    votes = check.sum(0)
    return votes, votes > active / 2.0


@functools.partial(jax.profiler.annotate_function,
                   name="btard.host.checksum")
def checksum_offender_peers(checksums, rel: float = 1e-2):
    """Map violated Verification-2 checksums to aggregator peer ids.

    Partition j is aggregated by peer j in the butterfly topology (Alg. 2),
    so |sum_i s_i^j| above tolerance implicates peer j. The tolerance scales
    with the mean checksum magnitude (the fixed point is solved to finite
    precision). Returns a np.ndarray of offending peer indices.
    """
    cs = np.abs(np.asarray(checksums, np.float32))
    return np.nonzero(cs > rel * (1.0 + cs.mean()))[0]


def checksum_tolerance(agg, parts, rel=1e-3):
    """Numerical tolerance for the zero checksum: the fixed point is solved
    to finite precision, so scale by the residual magnitude."""
    scale = jnp.linalg.norm(parts.astype(jnp.float32), axis=-1).mean()
    return rel * jnp.maximum(scale, 1e-6)
