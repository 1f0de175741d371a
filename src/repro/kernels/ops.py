"""Jit'd public wrappers around the Pallas kernels.

Each kernel compiles natively when the program is lowered for a TPU and runs
in the Pallas interpreter when it is lowered for the CPU; any other platform
is an error (``centered_clip._pallas_call``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import centered_clip as _k


@functools.partial(jax.jit, static_argnames=("n_iters", "block"))
def centered_clip_op(
    xs, tau, weights=None, v0=None, *, n_iters: int = 20, block: int = _k.DEFAULT_BLOCK
):
    """Kernel-backed CenteredClip: xs (n, d), scalar tau -> (d,) f32.
    v0: optional (d,) warm start (previous aggregate)."""
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_iters,))
    return _k.centered_clip_pallas(
        xs, taus, weights, v0, block=block,
    )


@functools.partial(jax.jit, static_argnames=("block",))
def verify_tables_op(xs, v, z, tau, *, block: int = _k.DEFAULT_BLOCK):
    """Kernel-backed fused verification tables."""
    return _k.verify_tables_pallas(xs, v, z, tau, block=block)


@functools.partial(jax.jit, static_argnames=("n_iters", "block"))
def butterfly_clip_op(
    parts, tau, weights=None, v0=None, *, n_iters: int = 20, block: int = _k.DEFAULT_BLOCK
):
    """Kernel-backed all-partition ButterflyClip aggregation:
    parts (n_parts, n_peers, part) -> (n_parts, part).
    v0: optional (n_parts, part) warm start (previous aggregate)."""
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_iters,))
    return _k.butterfly_clip_pallas(
        parts, taus, weights, v0, block=block,
    )


# ---------------------------------------------------------------------------
# Fused one-pass-per-iteration family: aggregation + verification tables in
# n_iters + 2 HBM passes of x (vs 2*n_iters + 1 for the two-call pipeline).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_iters", "block"))
def centered_clip_fused_op(
    xs, tau, z, weights=None, tau_v=None, v0=None, *,
    n_iters: int = 20, block: int = _k.DEFAULT_BLOCK
):
    """Fused CenteredClip + Alg. 6 tables: xs (n, d), z (d,) ->
    (agg (d,), s (n,), norms (n,)). v0: optional (d,) warm start."""
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_iters,))
    return _k.centered_clip_fused_pallas(
        xs, taus, z, tau_v=tau_v, weights=weights, v0=v0,
        block=block,
    )


@functools.partial(jax.jit, static_argnames=("n_iters", "block"))
def butterfly_clip_fused_op(
    parts, tau, z, weights=None, tau_v=None, v0=None, *,
    n_iters: int = 20, block: int = _k.DEFAULT_BLOCK
):
    """Fused all-partition ButterflyClip aggregation + broadcast tables:
    parts (n_parts, n_peers, part), z (n_parts, part) ->
    (agg (n_parts, part), s (n_peers, n_parts), norms (n_peers, n_parts)).

    s/norms come back transposed to the (peer, partition) layout of
    core.butterfly.verification_tables. v0: optional warm start."""
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_iters,))
    agg, s, norms = _k.butterfly_clip_fused_pallas(
        parts, taus, z, tau_v=tau_v, weights=weights, v0=v0,
        block=block,
    )
    return agg, s.T, norms.T


@functools.partial(jax.jit, static_argnames=("n_iters", "block"))
def butterfly_clip_fused_dequant_op(
    qs, scales, tau, z, weights=None, tau_v=None, v0=None, *,
    n_iters: int = 20, block: int = _k.DEFAULT_BLOCK
):
    """Fused dequantize + ButterflyClip + broadcast tables over WIRE
    payloads (compressed:butterfly_clip — core.compression): qs
    (n_parts, n_peers, part) int8/bf16 stays in its wire dtype for all
    n_iters + 2 HBM passes, dequantized in-register against the
    (n_parts, n_peers) f32 sidecar scales. Returns (agg (n_parts, part),
    s (n_peers, n_parts), norms (n_peers, n_parts)) — the layout of
    butterfly_clip_fused_op."""
    taus = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (n_iters,))
    agg, s, norms = _k.butterfly_clip_fused_dequant_pallas(
        qs, scales, taus, z, tau_v=tau_v, weights=weights, v0=v0,
        block=block,
    )
    return agg, s.T, norms.T


# ---------------------------------------------------------------------------
# Adaptive early-exit family: one-pass-per-iteration step kernel under a
# lax.while_loop, stopping at ||v_{l+1}-v_l|| <= tol with a static max_iters
# cap; the verification-table epilogue runs exactly ONCE against the final
# iterate. iters_run + 2 HBM passes of the stack vs n_iters + 2 fixed.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("max_iters", "block"))
def butterfly_clip_adaptive_op(
    parts, tau, tol, weights=None, v0=None, *,
    max_iters: int = 60, block: int = _k.DEFAULT_BLOCK
):
    """Kernel-backed adaptive all-partition ButterflyClip aggregation:
    parts (n_parts, n_peers, part) -> (agg (n_parts, part),
    iters (n_parts,) i32). v0: optional warm start (previous aggregate)."""
    return _k.butterfly_clip_adaptive_pallas(
        parts, tau, tol, max_iters, weights, v0,
        block=block,
    )


@functools.partial(jax.jit, static_argnames=("max_iters", "block"))
def butterfly_clip_fused_adaptive_op(
    parts, tau, z, tol, weights=None, v0=None, *,
    max_iters: int = 60, block: int = _k.DEFAULT_BLOCK
):
    """Adaptive aggregation + Alg. 6 broadcast tables: the early-exit
    iteration driver followed by ONE verification-table pass against the
    final aggregate (deterministic however many iterations ran).

    Returns (agg (n_parts, part), s (n_peers, n_parts),
    norms (n_peers, n_parts), iters (n_parts,) i32) — s/norms in the
    (peer, partition) layout of core.butterfly.verification_tables."""
    agg, iters = _k.butterfly_clip_adaptive_pallas(
        parts, tau, tol, max_iters, weights, v0,
        block=block,
    )
    s, norms = _k.verify_tables_batched_pallas(
        parts, agg, z, tau, block=block,
    )
    return agg, s.T, norms.T, iters


@functools.partial(jax.jit, static_argnames=("block",))
def verify_tables_all_op(parts, agg, z, tau, *, block: int = _k.DEFAULT_BLOCK):
    """Kernel-backed all-partition verification tables (one pass of parts):
    -> (s (n_peers, n_parts), norms (n_peers, n_parts))."""
    s, norms = _k.verify_tables_batched_pallas(
        parts, agg, z, tau, block=block,
    )
    return s.T, norms.T


# ---------------------------------------------------------------------------
# Generalized verification-wrapper digests (core.verification): per-peer
# contribution digests s_i = <z, x_i - v>, ||x_i - v|| — no clip weight,
# because the wrapped coordinatewise aggregators carry no tau.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block",))
def digest_tables_all_op(parts, agg, z, *, block: int = _k.DEFAULT_BLOCK):
    """Kernel-backed all-partition contribution digests (one pass of parts):
    -> (s (n_peers, n_parts), norms (n_peers, n_parts)) — the standalone
    digest pass for verified:* specs whose aggregation runs in jnp."""
    s, norms = _k.digest_tables_batched_pallas(
        parts, agg, z, block=block,
    )
    return s.T, norms.T


@functools.partial(jax.jit, static_argnames=("block",))
def digest_tables_rows_op(parts, agg, z, rows, tau=0.0, *,
                          block: int = _k.DEFAULT_BLOCK):
    """Kernel-backed SAMPLED-column digests (sampled-digest audit mode):
    parts (n_parts, n_peers, part), rows (k,) i32 sampled partition ids ->
    (s (n_peers, k), norms (n_peers, k)) — transposed to the
    (peer, column) layout of core.verification.digest_tables, column p of
    the output = partition rows[p]. tau > 0 applies the ButterflyClip clip
    weight; tau == 0 emits the plain verified:* digests. One HBM pass of
    the k sampled partitions only (scalar-prefetched row ids)."""
    s, norms = _k.digest_tables_rows_pallas(
        parts, agg, z, rows, tau, block=block,
    )
    return s.T, norms.T


@functools.partial(jax.jit, static_argnames=("block",))
def mean_digest_fused_op(parts, z, weights=None, *, block: int = _k.DEFAULT_BLOCK):
    """verified:mean's fused aggregation + digest epilogue in ONE
    pallas_call (1 HBM pass of the stacked partitions, zero materialized
    temporaries): parts (n_parts, n_peers, part), z (n_parts, part) ->
    (agg (n_parts, part), s (n_peers, n_parts), norms (n_peers, n_parts)).

    s/norms come back transposed to the (peer, partition) layout of
    core.verification.digest_tables."""
    agg, s, norms = _k.mean_digest_fused_pallas(
        parts, z, weights, block=block,
    )
    return agg, s.T, norms.T


@functools.partial(jax.jit, static_argnames=("block",))
def mean_digest_fused_dequant_op(
    qs, scales, z, weights=None, *, block: int = _k.DEFAULT_BLOCK
):
    """compressed:verified:mean's fused dequantize + aggregation + digest
    epilogue: qs (n_parts, n_peers, part) int8/bf16 wire payloads stay in
    their wire dtype for both HBM passes, dequantized in-register against
    the (n_parts, n_peers) f32 sidecar scales. Returns (agg, s, norms) in
    the mean_digest_fused_op layout."""
    agg, s, norms = _k.mean_digest_fused_dequant_pallas(
        qs, scales, z, weights, block=block,
    )
    return agg, s.T, norms.T
