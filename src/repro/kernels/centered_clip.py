"""Pallas TPU kernels for BTARD's aggregation hot spots.

The CenteredClip fixed point is a bandwidth-bound reduction over the stacked
peer partitions (n_peers x part). The naive jnp version materializes
``diff``, ``norms`` and the weighted sum as separate HBM temporaries every
iteration (~4 passes). The fused kernel family streams x through VMEM ONE
time per clip iteration — see DESIGN.md for the full derivation:

* ``_fused_body`` (via ``centered_clip_fused_pallas`` and the batched
  ``butterfly_clip_fused_pallas``) — grid (n_iters + 2, n_blocks):
  pass 0 is a norm prologue (||x_i - v_0||^2 into a VMEM scratch), passes
  1..n_iters update v while accumulating the NEXT iteration's per-peer
  squared norms incrementally (||x_i - v_{l+1}||^2 = sum_b ||diff_b -
  upd_b||^2 — diff and upd are already in registers, so the separate norm
  phase of the legacy kernel disappears), and pass n_iters+1 is a fused
  verification epilogue producing the Alg. 6 broadcast tables
  s_i = min(1, tau/||x_i - v||) <z, x_i - v> and ||x_i - v|| for free
  (the final squared norms are still sitting in the scratch).
  Total: n_iters + 2 HBM passes of x vs 2*n_iters + 1 for the legacy
  two-phase kernel + separate table kernel.

* ``centered_clip_kernel`` (legacy, kept as a cross-check) — grid
  (n_iters, 2, n_blocks); phase 0 accumulates per-peer squared norms,
  phase 1 converts them to clip weights and updates v in place. 2 HBM
  passes of x per iteration.

* ``verify_tables_kernel`` — ONE pass of x producing both Verification-1/2
  tables standalone (used when the aggregate was corrupted after the fused
  call and the tables must be recomputed against the corrupted v).

* ``_dg_batched_kernel`` / ``digest_tables_batched_pallas`` — the
  GENERALIZED verification wrapper's contribution digests
  s_i = <z, x_i - v>, ||x_i - v|| (no clip weight — wrapped coordinatewise
  aggregators have no tau) in one pass of the stacked partitions; the
  standalone table pass for verified:* specs whose aggregation is a jnp
  sort (trimmed mean, coordinate median — nothing to fuse into).

* ``_md_kernel`` / ``mean_digest_fused_pallas`` — verified:mean's fused
  aggregation + digests: the weighted per-partition mean decomposes over
  lanes, so each block's aggregate is final as soon as it is computed and
  the digest dot / squared norm accumulate against it in the same grid
  step (1 HBM pass of x, zero materialized temporaries).

* dequant variants (``butterfly_clip_fused_dequant_pallas``,
  ``mean_digest_fused_dequant_pallas``) — the same fused bodies over WIRE
  payloads (core.compression): xs stays int8/bf16 in HBM for every pass
  and is dequantized in-register against a per-(partition, peer) f32
  sidecar scale, so ``compressed:*`` specs keep the n_iters + 2 (resp. 1)
  pass structure over 1-2 byte data — ≈4× (int8) fewer HBM bytes per pass.
  All arithmetic runs on the dequantized f32 values (the same bits the jnp
  path computes), which is what keeps compressed verification exact.

Block geometry: peers stay un-tiled (n <= ~64 on the peer axis), the
partition dim is tiled by ``block`` (lane-aligned multiples of 128). Inputs
are zero-padded to a block multiple — zero columns where x == v == z == 0
contribute nothing to norms, dots, or updates, so padding is exact.

Multi-pass kernels carry the iterate v through HBM: v is an ``pl.ANY``
output aliased to the v0 input, and each grid step copies its (1, blk)
window in and, after an update, back out (``_hbm_window``). A pipelined
VMEM output block cannot carry it, because the TPU pipeline only ever
writes an output block back and never re-reads it: a block revisited in a
later pass would start from whatever the VMEM buffer last held.

Every kernel compiles natively on a TPU and runs in the Pallas interpreter
on the CPU (``_pallas_call``), where it is validated against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 512


def _pallas_call(kernel, *, name, interpret=None, **kwargs):
    """``pl.pallas_call`` that runs natively on a TPU and in the Pallas
    interpreter on the CPU. The platform is the one the call is lowered
    for (``lax.platform_dependent``), read then and not at import; lowering
    for any other platform raises. ``interpret=True``/``False`` forces one
    mode (the native-lowering tests compile ``False`` for a described TPU).
    ``name`` is the kernel's stable name: the TPU custom call's
    ``kernel_name`` and, in the interpreter, a scope of its ops, so that a
    trace finds the kernel's device time by it.
    """
    kwargs["name"] = name
    if interpret is not None:
        return pl.pallas_call(kernel, interpret=interpret, **kwargs)
    native = pl.pallas_call(kernel, interpret=False, **kwargs)
    interp = pl.pallas_call(kernel, interpret=True, **kwargs)
    return lambda *args: jax.lax.platform_dependent(
        *args, cpu=interp, tpu=native
    )


def _hbm_window(ref, blk, width, part=None):
    """The (1, width) lane window ``blk`` of the HBM iterate ``ref`` ((1, dp),
    or (n_parts, 1, dp) with ``part``), for ``pltpu.sync_copy``."""
    lanes = pl.ds(pl.multiple_of(blk * width, width), width)
    return ref.at[:, lanes] if part is None else ref.at[part, :, lanes]


_ANY = pl.BlockSpec(memory_space=pl.ANY)  # an HBM ref the kernel copies by hand


# ===========================================================================
# CenteredClip fixed-point kernel
# ===========================================================================
def _cc_kernel(taus_ref, w_ref, xs_ref, v0_ref, out_ref, sq_ref, cw_ref,
               v_ref):
    """Grid (n_iters, 2, n_blocks).

    taus: (n_iters, 1) in SMEM (whole schedule, indexed by the pass id —
    a (1, 1) VMEM block would violate the TPU (8, 128) tile minimum);
    w: (n, 1) peer weights; xs: (n, blk) tile; out: the (1, dp) HBM
    iterate, aliased to v0 (so it starts as v0); scratch sq/cw: (n, 1) f32,
    v: this step's (1, blk) window of the iterate.
    """
    it = pl.program_id(0)
    phase = pl.program_id(1)
    blk = pl.program_id(2)
    v_hbm = _hbm_window(out_ref, blk, v_ref.shape[-1])
    pltpu.sync_copy(v_hbm, v_ref)

    @pl.when(phase == 0)
    def _phase_norms():
        @pl.when(blk == 0)
        def _reset():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        diff = xs_ref[...].astype(jnp.float32) - v_ref[...]
        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(phase == 1)
    def _phase_update():
        @pl.when(blk == 0)
        def _weights():
            tau = taus_ref[it, 0]
            norms = jnp.sqrt(jnp.maximum(sq_ref[...], 1e-30))
            cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
            cw = jnp.where(jnp.isinf(tau), 1.0, cw)
            cw_ref[...] = cw * w_ref[...].astype(jnp.float32)

        wsum = jnp.maximum(jnp.sum(w_ref[...].astype(jnp.float32)), 1e-30)
        diff = xs_ref[...].astype(jnp.float32) - v_ref[...]
        upd = jnp.sum(cw_ref[...] * diff, axis=0, keepdims=True) / wsum
        v_ref[...] = v_ref[...] + upd
        pltpu.sync_copy(v_ref, v_hbm)


def centered_clip_pallas(
    xs, taus, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """CenteredClip via the Pallas kernel. xs: (n, d) -> v: (d,) f32.

    v0: optional (d,) warm start — flows straight into the kernel's v ref
    (the iteration state), zero extra HBM traffic.
    """
    n, d = xs.shape
    n_iters = int(taus.shape[0])
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        xs = jnp.pad(xs, ((0, 0), (0, dp - d)))
        if v0 is not None:
            v0 = jnp.pad(v0, (0, dp - d))
    n_blocks = dp // blk

    taus2 = taus.reshape(n_iters, 1).astype(jnp.float32)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    v0 = (
        jnp.zeros((1, dp), jnp.float32)
        if v0 is None
        else v0.reshape(1, dp).astype(jnp.float32)
    )

    out = _pallas_call(
        _cc_kernel,
        name="centered_clip",
        grid=(n_iters, 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda i, p, b: (0, 0)),
            pl.BlockSpec((n, blk), lambda i, p, b: (0, b)),
            _ANY,
        ],
        out_specs=_ANY,
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((1, blk), jnp.float32),
        ],
        input_output_aliases={3: 0},
        interpret=interpret,
    )(taus2, w2, xs, v0)
    return out[0, :d]


# ===========================================================================
# Batched multi-partition CenteredClip (the full ButterflyClip aggregation
# in ONE pallas_call: grid (n_parts, n_iters, 2, n_blocks); the partition
# index is outermost so the per-peer scratch naturally re-initializes at
# each partition's first grid step)
# ===========================================================================
def _bcc_kernel(taus_ref, w_ref, xs_ref, v0_ref, out_ref, sq_ref, cw_ref,
                v_ref):
    """Like _cc_kernel with a leading partition grid axis. The iterate
    carries a singleton sublane dim — (n_parts, 1, dp) — so each step's
    window is a (1, blk) row like the unbatched kernel's."""
    it = pl.program_id(1)
    phase = pl.program_id(2)
    blk = pl.program_id(3)
    v_hbm = _hbm_window(out_ref, blk, v_ref.shape[-1], pl.program_id(0))
    pltpu.sync_copy(v_hbm, v_ref)

    @pl.when(phase == 0)
    def _phase_norms():
        @pl.when(blk == 0)
        def _reset():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        diff = xs_ref[0].astype(jnp.float32) - v_ref[...]
        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(phase == 1)
    def _phase_update():
        @pl.when(blk == 0)
        def _weights():
            tau = taus_ref[it, 0]
            norms = jnp.sqrt(jnp.maximum(sq_ref[...], 1e-30))
            cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
            cw = jnp.where(jnp.isinf(tau), 1.0, cw)
            cw_ref[...] = cw * w_ref[...].astype(jnp.float32)

        wsum = jnp.maximum(jnp.sum(w_ref[...].astype(jnp.float32)), 1e-30)
        diff = xs_ref[0].astype(jnp.float32) - v_ref[...]
        upd = jnp.sum(cw_ref[...] * diff, axis=0, keepdims=True) / wsum
        v_ref[...] = v_ref[...] + upd
        pltpu.sync_copy(v_ref, v_hbm)


def butterfly_clip_pallas(
    parts, taus, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """All-partition CenteredClip: parts (n_parts, n_peers, part) -> the
    robust aggregate (n_parts, part) f32 — i.e. ButterflyClip's aggregation
    stage as a single fused kernel. v0: optional (n_parts, part) warm start."""
    n_parts, n, d = parts.shape
    n_iters = int(taus.shape[0])
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        if v0 is not None:
            v0 = jnp.pad(v0, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    taus2 = taus.reshape(n_iters, 1).astype(jnp.float32)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    v0 = (
        jnp.zeros((n_parts, 1, dp), jnp.float32)
        if v0 is None
        else v0.astype(jnp.float32).reshape(n_parts, 1, dp)
    )

    out = _pallas_call(
        _bcc_kernel,
        name="butterfly_clip",
        grid=(n_parts, n_iters, 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda p, i, ph, b: (0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, i, ph, b: (p, 0, b)),
            _ANY,
        ],
        out_specs=_ANY,
        out_shape=jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((1, blk), jnp.float32),
        ],
        input_output_aliases={3: 0},
        interpret=interpret,
    )(taus2, w2, parts, v0)
    return out[:, 0, :d]


# ===========================================================================
# Fused one-pass-per-iteration CenteredClip with incremental norms and a
# verification epilogue. Grid (n_iters + 2, n_blocks) (a leading n_parts
# axis in the batched variant):
#
#   pass 0            prologue: sq_i := ||x_i - v0||^2 (the HBM iterate
#                     starts as v0: it is aliased to the v0 input)
#   pass 1..n_iters   at blk 0 convert sq -> clip weights, zero sq; then per
#                     block: upd = sum_i cw_i (x_i - v) / wsum, v += upd, and
#                     sq_i += ||diff_i - upd||^2 — the NEXT iteration's
#                     squared norms, accumulated from values already in
#                     registers (no second read of x).
#   pass n_iters+1    epilogue: dot_i = <z, x_i - v>; on the last block emit
#                     s_i = min(1, tau_v/||x_i - v||) dot_i and ||x_i - v||
#                     (sq still holds the final squared norms).
#
# n_iters + 2 HBM passes of x total, vs 2*n_iters + 1 for the legacy
# two-phase kernel plus the standalone table kernel.
# ===========================================================================
def _fused_body(
    batched, taus_ref, tauv_ref, w_ref, xs_ref, v0_ref, z_ref,
    out_ref, s_ref, norm_ref, sq_ref, cw_ref, dot_ref, v_ref, *,
    scales_ref=None,
):
    """taus/tauv live in SMEM (whole schedule, indexed by the pass id); in
    the batched variant z/out/s/norm carry a singleton sublane dim (see
    _bcc_kernel) so every VMEM block satisfies the TPU tiling rules. out is
    the HBM iterate (aliased to v0); v_ref holds this step's window of it.

    scales_ref (dequant variant): per-peer f32 sidecar scales — xs arrives
    in its WIRE dtype (int8 / bf16) and is dequantized in-register
    (``xs.astype(f32) * scale``, the exact formula of
    core.compression.dequantize), so every clip iteration and the digest
    epilogue stream 1-2 byte data through HBM while all arithmetic sees the
    same f32 wire values as the jnp path — bit-identical digests."""
    off = 1 if batched else 0
    it = pl.program_id(off + 0)
    blk = pl.program_id(off + 1)
    n_upd = pl.num_programs(off + 0) - 2
    nb = pl.num_programs(off + 1)
    xs = (xs_ref[0] if batched else xs_ref[...]).astype(jnp.float32)
    if scales_ref is not None:  # in-register dequantize of the wire payload
        xs = xs * (scales_ref[0] if batched else scales_ref[...])
    z = (z_ref[0] if batched else z_ref[...]).astype(jnp.float32)
    v_hbm = _hbm_window(out_ref, blk, v_ref.shape[-1],
                        pl.program_id(0) if batched else None)
    pltpu.sync_copy(v_hbm, v_ref)

    @pl.when(it == 0)
    def _prologue():
        @pl.when(blk == 0)
        def _reset():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        diff = xs - v_ref[...]
        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(jnp.logical_and(it >= 1, it <= n_upd))
    def _update():
        @pl.when(blk == 0)
        def _weights():
            tau = taus_ref[it, 0]
            norms = jnp.sqrt(jnp.maximum(sq_ref[...], 1e-30))
            cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
            cw = jnp.where(jnp.isinf(tau), 1.0, cw)
            cw_ref[...] = cw * w_ref[...].astype(jnp.float32)
            sq_ref[...] = jnp.zeros_like(sq_ref)  # accumulates iter l+1 norms

        wsum = jnp.maximum(jnp.sum(w_ref[...].astype(jnp.float32)), 1e-30)
        diff = xs - v_ref[...]
        upd = jnp.sum(cw_ref[...] * diff, axis=0, keepdims=True) / wsum
        v_ref[...] = v_ref[...] + upd
        pltpu.sync_copy(v_ref, v_hbm)
        nd = diff - upd  # x_i - v_{l+1} restricted to this block
        sq_ref[...] += jnp.sum(nd * nd, axis=1, keepdims=True)

    @pl.when(it == n_upd + 1)
    def _epilogue():
        @pl.when(blk == 0)
        def _reset_dot():
            dot_ref[...] = jnp.zeros_like(dot_ref)

        diff = xs - v_ref[...]
        dot_ref[...] += jnp.sum(diff * z, axis=1, keepdims=True)

        @pl.when(blk == nb - 1)
        def _tables():
            tau_v = tauv_ref[0, 0]
            norms = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0))
            cwv = jnp.minimum(1.0, tau_v / jnp.maximum(norms, 1e-30))
            cwv = jnp.where(jnp.isinf(tau_v), 1.0, cwv)
            s = cwv * dot_ref[...]  # (n, 1)
            if batched:
                s_ref[0] = s.reshape(s_ref.shape[1:])
                norm_ref[0] = norms.reshape(norm_ref.shape[1:])
            else:
                s_ref[...] = s.reshape(s_ref.shape)
                norm_ref[...] = norms.reshape(norm_ref.shape)


def _fused_dequant_body(
    batched, taus_ref, tauv_ref, w_ref, scales_ref, xs_ref, v0_ref, z_ref,
    out_ref, s_ref, norm_ref, sq_ref, cw_ref, dot_ref, v_ref,
):
    """Positional-ref adapter for the dequant variant: the sidecar scales
    ride as one extra VMEM operand between w and the wire-dtype xs."""
    _fused_body(
        batched, taus_ref, tauv_ref, w_ref, xs_ref, v0_ref, z_ref,
        out_ref, s_ref, norm_ref, sq_ref, cw_ref, dot_ref, v_ref,
        scales_ref=scales_ref,
    )


def _pad_taus(taus, n_iters):
    """(n_iters,) -> (n_iters + 2, 1) so the grid's pass index maps straight
    into the schedule (rows 0 / n_iters+1 are never read)."""
    t = taus.astype(jnp.float32).reshape(n_iters, 1)
    return jnp.concatenate([t[:1], t, t[-1:]], axis=0)


def centered_clip_fused_pallas(
    xs, taus, z, tau_v=None, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """Fused CenteredClip + verification tables in n_iters + 2 passes of x.

    xs: (n, d); taus: (n_iters,); z: (d,) unit direction for the epilogue.
    tau_v defaults to taus[-1] (the protocol uses a constant schedule).
    v0: optional (d,) warm start (previous aggregate).
    Returns (v (d,), s (n,), norms (n,)) f32.
    """
    n, d = xs.shape
    n_iters = int(taus.shape[0])
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if tau_v is None:
        tau_v = taus[-1]
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        xs = jnp.pad(xs, ((0, 0), (0, dp - d)))
        z = jnp.pad(z, (0, dp - d))
        if v0 is not None:
            v0 = jnp.pad(v0, (0, dp - d))
    n_blocks = dp // blk

    tauv2 = jnp.asarray(tau_v, jnp.float32).reshape(1, 1)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    v0 = (
        jnp.zeros((1, dp), jnp.float32)
        if v0 is None
        else v0.reshape(1, dp).astype(jnp.float32)
    )

    out, s, norms = _pallas_call(
        functools.partial(_fused_body, False),
        name="cc_fused",
        grid=(n_iters + 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda i, b: (0, 0)),
            pl.BlockSpec((n, blk), lambda i, b: (0, b)),
            _ANY,
            pl.BlockSpec((1, blk), lambda i, b: (0, b)),
        ],
        out_specs=[
            _ANY,
            pl.BlockSpec((n, 1), lambda i, b: (0, 0)),
            pl.BlockSpec((n, 1), lambda i, b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((1, blk), jnp.float32),
        ],
        input_output_aliases={4: 0},
        interpret=interpret,
    )(_pad_taus(taus, n_iters), tauv2, w2, xs, v0, z.reshape(1, dp))
    return out[0, :d], s[:, 0], norms[:, 0]


def butterfly_clip_fused_pallas(
    parts, taus, z, tau_v=None, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """All-partition fused ButterflyClip: the whole robust aggregation AND
    the Alg. 6 broadcast tables in ONE pallas_call of n_iters + 2 passes.

    parts: (n_parts, n_peers, part); z: (n_parts, part).
    v0: optional (n_parts, part) warm start (previous aggregate).
    Returns (agg (n_parts, part), s (n_parts, n), norms (n_parts, n)) f32.
    """
    n_parts, n, d = parts.shape
    n_iters = int(taus.shape[0])
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if tau_v is None:
        tau_v = taus[-1]
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
        if v0 is not None:
            v0 = jnp.pad(v0, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    tauv2 = jnp.asarray(tau_v, jnp.float32).reshape(1, 1)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    v0 = (
        jnp.zeros((n_parts, 1, dp), jnp.float32)
        if v0 is None
        else v0.astype(jnp.float32).reshape(n_parts, 1, dp)
    )

    out, s, norms = _pallas_call(
        functools.partial(_fused_body, True),
        name="butterfly_clip_fused",
        grid=(n_parts, n_iters + 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda p, i, b: (0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, i, b: (p, 0, b)),
            _ANY,
            pl.BlockSpec((1, 1, blk), lambda p, i, b: (p, 0, b)),
        ],
        out_specs=[
            _ANY,
            pl.BlockSpec((1, 1, n), lambda p, i, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, i, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((1, blk), jnp.float32),
        ],
        input_output_aliases={4: 0},
        interpret=interpret,
    )(_pad_taus(taus, n_iters), tauv2, w2, parts, v0,
      z.reshape(n_parts, 1, dp))
    return out[:, 0, :d], s[:, 0], norms[:, 0]


def butterfly_clip_fused_dequant_pallas(
    qs, scales, taus, z, tau_v=None, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """The fused ButterflyClip aggregation + tables over WIRE payloads: qs
    stays int8/bf16 in HBM for all n_iters + 2 passes and is dequantized
    in-register against the per-(partition, peer) sidecar scales — the
    ``compressed:butterfly_clip`` hot path (≈4× fewer HBM bytes per pass
    for int8).

    qs: (n_parts, n_peers, part) wire dtype; scales: (n_parts, n_peers)
    f32 (ship 1s for bf16); z: (n_parts, part); v0: optional (n_parts,
    part) f32 warm start (a broadcast value, not a wire payload).
    Returns (agg (n_parts, part), s (n_parts, n), norms (n_parts, n)) f32.

    Tiling: the qs block (1, n, blk) keeps the full peer axis, so the
    sublane dim equals the array dim and the wire dtype's tighter native
    tile minima are satisfied; scales use the (n_parts, n, 1) singleton-
    lane layout of the adaptive step kernel's sq operand (DESIGN.md).
    """
    n_parts, n, d = qs.shape
    n_iters = int(taus.shape[0])
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if tau_v is None:
        tau_v = taus[-1]
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        qs = jnp.pad(qs, ((0, 0), (0, 0), (0, dp - d)))  # wire zeros: exact
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
        if v0 is not None:
            v0 = jnp.pad(v0, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    tauv2 = jnp.asarray(tau_v, jnp.float32).reshape(1, 1)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    sc3 = scales.reshape(n_parts, n, 1).astype(jnp.float32)
    v0 = (
        jnp.zeros((n_parts, 1, dp), jnp.float32)
        if v0 is None
        else v0.astype(jnp.float32).reshape(n_parts, 1, dp)
    )

    out, s, norms = _pallas_call(
        functools.partial(_fused_dequant_body, True),
        name="butterfly_clip_fused_dequant",
        grid=(n_parts, n_iters + 2, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda p, i, b: (0, 0)),
            pl.BlockSpec((1, n, 1), lambda p, i, b: (p, 0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, i, b: (p, 0, b)),
            _ANY,
            pl.BlockSpec((1, 1, blk), lambda p, i, b: (p, 0, b)),
        ],
        out_specs=[
            _ANY,
            pl.BlockSpec((1, 1, n), lambda p, i, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, i, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((1, blk), jnp.float32),
        ],
        input_output_aliases={5: 0},
        interpret=interpret,
    )(_pad_taus(taus, n_iters), tauv2, w2, sc3, qs, v0,
      z.reshape(n_parts, 1, dp))
    return out[:, 0, :d], s[:, 0], norms[:, 0]


# ===========================================================================
# Adaptive early-exit driver: ONE clip iteration per kernel invocation, the
# incremental-norm recurrence carried BETWEEN invocations, a host-level (but
# fully jitted) lax.while_loop deciding whether the next iteration runs.
#
#   prologue (jnp)     sq_i := ||x_i - v_0||^2 per partition  (1 pass of x)
#   while ||dv|| > tol _adaptive_step_kernel: cw from sq, v += upd,
#     and it < cap       sq := sum_b ||diff_b - upd_b||^2     (1 pass of x)
#   epilogue           verify_tables_batched_pallas against the FINAL v,
#                      exactly once                           (1 pass of x)
#
# Total: iters_run + 2 HBM passes of the stacked partitions — the fused
# fixed-budget kernel's pass structure, but the iteration count now adapts
# to the data (warm starts routinely land it at 1-3 instead of the
# protocol-default 60). Converged partitions are frozen via select, exactly
# the vmap(while_loop) batching rule, so results match per-partition
# independent adaptive loops (and, at tol=0, the fixed-budget kernel).
# ===========================================================================
def _adaptive_step_kernel(
    tau_ref, w_ref, xs_ref, vin_ref, sqin_ref, vout_ref, sqout_ref,
    sq_ref, cw_ref,
):
    """Grid (n_parts, n_blocks): one CenteredClip iteration for every
    partition. sqin holds ||x_i - v_in||^2 (the recurrence state from the
    previous invocation); emits v_out = v_in + upd and the NEXT iteration's
    squared norms. v carries a singleton sublane dim, sq a singleton lane
    dim ((n_parts, n, 1) with (1, n, 1) blocks — the (n, 1) layout of the
    w operand, legal native tiles per DESIGN.md)."""
    blk = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(blk == 0)
    def _weights():
        tau = tau_ref[0, 0]
        norms = jnp.sqrt(jnp.maximum(sqin_ref[0], 1e-30))
        cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
        cw = jnp.where(jnp.isinf(tau), 1.0, cw)
        cw_ref[...] = cw * w_ref[...].astype(jnp.float32)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    wsum = jnp.maximum(jnp.sum(w_ref[...].astype(jnp.float32)), 1e-30)
    diff = xs_ref[0].astype(jnp.float32) - vin_ref[0].astype(jnp.float32)
    upd = jnp.sum(cw_ref[...] * diff, axis=0, keepdims=True) / wsum
    vout_ref[0] = vin_ref[0].astype(jnp.float32) + upd
    nd = diff - upd  # x_i - v_{l+1} restricted to this block
    sq_ref[...] += jnp.sum(nd * nd, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _emit():
        sqout_ref[0] = sq_ref[...].reshape(sqout_ref.shape[1:])


def adaptive_clip_step_pallas(
    parts, v, sq, tau, weights=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """One all-partition CenteredClip iteration (single HBM pass of parts).

    parts: (n_parts, n, part) (pre-padded to a block multiple);
    v: (n_parts, 1, part); sq: (n_parts, n, 1) = ||x_i - v||^2.
    Returns (v_new, sq_new) in the same layouts.
    """
    n_parts, n, dp = parts.shape
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, dp))
    if dp % blk:
        raise ValueError(
            f"adaptive step kernel needs part dim {dp} pre-padded to a "
            f"multiple of block {blk} (the while driver pads before looping)"
        )
    n_blocks = dp // blk

    tau2 = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    w2 = weights.reshape(n, 1).astype(jnp.float32)
    return _pallas_call(
        _adaptive_step_kernel,
        name="cc_adaptive_step",
        grid=(n_parts, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, 1), lambda p, b: (0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, n, 1), lambda p, b: (p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, n, 1), lambda p, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tau2, w2, parts, v, sq)


def butterfly_clip_adaptive_pallas(
    parts, tau, tol, max_iters: int, weights=None, v0=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """Early-exit all-partition CenteredClip: iterate the one-pass step
    kernel under ``lax.while_loop`` until every partition's update norm is
    <= tol (or ``max_iters``). Converged partitions freeze (select), so
    per-partition results equal independent adaptive loops.

    parts: (n_parts, n_peers, part). Returns (agg (n_parts, part) f32,
    iters (n_parts,) i32). The verification-table epilogue is NOT included
    — callers (kernels/ops.butterfly_clip_fused_adaptive_op) run it exactly
    once against the returned aggregate.
    """
    n_parts, n, d = parts.shape
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        if v0 is not None:
            v0 = jnp.pad(v0, ((0, 0), (0, dp - d)))
    parts = parts.astype(jnp.float32)

    v = (
        jnp.zeros((n_parts, 1, dp), jnp.float32)
        if v0 is None
        else v0.astype(jnp.float32).reshape(n_parts, 1, dp)
    )
    # prologue: the recurrence state for the starting iterate (1 pass of x)
    sq = jnp.sum((parts - v) ** 2, axis=-1, keepdims=True)  # (n_parts, n, 1)
    tol2 = jnp.float32(tol) ** 2

    def cond(carry):
        _, _, d2, it, _ = carry
        return jnp.logical_and((d2 > tol2).any(), it < max_iters)

    def body(carry):
        v, sq, d2, it, iters = carry
        v_new, sq_new = adaptive_clip_step_pallas(
            parts, v, sq, tau, weights, block=blk, interpret=interpret
        )
        active = d2 > tol2  # (n_parts,) — frozen partitions keep their carry
        upd2 = ((v_new - v) ** 2).sum(axis=(1, 2))
        v = jnp.where(active[:, None, None], v_new, v)
        sq = jnp.where(active[:, None, None], sq_new, sq)
        d2 = jnp.where(active, upd2, d2)
        return v, sq, d2, it + 1, iters + active.astype(jnp.int32)

    v, _, _, _, iters = jax.lax.while_loop(
        cond,
        body,
        (v, sq, jnp.full((n_parts,), jnp.inf, jnp.float32), jnp.int32(0),
         jnp.zeros((n_parts,), jnp.int32)),
    )
    return v[:, 0, :d], iters


# ===========================================================================
# Fused verification-tables kernel (single HBM pass)
# ===========================================================================
def _vt_kernel(tau_ref, xs_ref, v_ref, z_ref, s_ref, norm_ref, dot_ref, sq_ref):
    """Grid (n_blocks,). Accumulate per-peer dot & sqnorm; epilogue on last."""
    blk = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(blk == 0)
    def _reset():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = xs_ref[...].astype(jnp.float32) - v_ref[...].astype(jnp.float32)
    zb = z_ref[...].astype(jnp.float32)
    dot_ref[...] += jnp.sum(diff * zb, axis=1, keepdims=True)
    sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _epilogue():
        tau = tau_ref[0, 0]
        norms = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0))
        cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
        s_ref[...] = cw * dot_ref[...]
        norm_ref[...] = norms


def verify_tables_pallas(
    xs, v, z, tau, *, block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
):
    """Fused s_i = <z, clip(x_i - v)>, norm_i = ||x_i - v|| in one pass.

    xs: (n, d); v, z: (d,). Returns (s (n,), norms (n,)).
    """
    n, d = xs.shape
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        xs = jnp.pad(xs, ((0, 0), (0, dp - d)))
        v = jnp.pad(v, (0, dp - d))
        z = jnp.pad(z, (0, dp - d))
    n_blocks = dp // blk

    tau2 = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    s, norms = _pallas_call(
        _vt_kernel,
        name="verify_tables",
        grid=(n_blocks,),
        in_specs=[
            # scalar: whole (1, 1) array in SMEM — a (1, 1) VMEM block is
            # an illegal sub-tile on real TPUs (the PR 2 bug class)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n, blk), lambda b: (0, b)),
            pl.BlockSpec((1, blk), lambda b: (0, b)),
            pl.BlockSpec((1, blk), lambda b: (0, b)),
        ],
        out_specs=[
            pl.BlockSpec((n, 1), lambda b: (0, 0)),
            pl.BlockSpec((n, 1), lambda b: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tau2, xs, v.reshape(1, dp), z.reshape(1, dp))
    return s[:, 0], norms[:, 0]


def _vt_batched_kernel(
    tau_ref, xs_ref, v_ref, z_ref, s_ref, norm_ref, dot_ref, sq_ref
):
    """Grid (n_parts, n_blocks) — verify_tables for every partition in one
    pallas_call (the recompute path when the aggregate changed after the
    fused kernel ran, e.g. a corrupted aggregator). v/z/s/norm carry a
    singleton sublane dim for legal native TPU tiles (see _bcc_kernel)."""
    blk = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(blk == 0)
    def _reset():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = xs_ref[0].astype(jnp.float32) - v_ref[0].astype(jnp.float32)
    zb = z_ref[0].astype(jnp.float32)
    dot_ref[...] += jnp.sum(diff * zb, axis=1, keepdims=True)
    sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _epilogue():
        tau = tau_ref[0, 0]
        norms = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0))
        cw = jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30))
        s_ref[0] = (cw * dot_ref[...]).reshape(s_ref.shape[1:])
        norm_ref[0] = norms.reshape(norm_ref.shape[1:])


def _dg_batched_kernel(xs_ref, v_ref, z_ref, s_ref, norm_ref, dot_ref, sq_ref):
    """Grid (n_parts, n_blocks) — generalized contribution digests for every
    partition in one pallas_call: s_i = <z, x_i - v>, norm_i = ||x_i - v||.
    Like _vt_batched_kernel minus the clip weight (wrapped coordinatewise
    aggregators carry no tau). v/z/s/norm carry a singleton sublane dim for
    legal native TPU tiles (see _bcc_kernel)."""
    blk = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(blk == 0)
    def _reset():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = xs_ref[0].astype(jnp.float32) - v_ref[0].astype(jnp.float32)
    zb = z_ref[0].astype(jnp.float32)
    dot_ref[...] += jnp.sum(diff * zb, axis=1, keepdims=True)
    sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _epilogue():
        s_ref[0] = dot_ref[...].reshape(s_ref.shape[1:])
        norm_ref[0] = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0)).reshape(
            norm_ref.shape[1:]
        )


def digest_tables_batched_pallas(
    parts, agg, z, *, block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
):
    """All-partition generalized digests in one pass of the stacked parts.

    parts: (n_parts, n, part); agg, z: (n_parts, part).
    Returns (s (n_parts, n), norms (n_parts, n)).
    """
    n_parts, n, d = parts.shape
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        agg = jnp.pad(agg, ((0, 0), (0, dp - d)))
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    s, norms = _pallas_call(
        _dg_batched_kernel,
        name="digest_tables_batched",
        grid=(n_parts, n_blocks),
        in_specs=[
            pl.BlockSpec((1, n, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(parts, agg.reshape(n_parts, 1, dp), z.reshape(n_parts, 1, dp))
    return s[:, 0], norms[:, 0]


def _rows_digest_kernel(rows_ref, tau_ref, xs_ref, v_ref, z_ref, s_ref,
                        norm_ref, dot_ref, sq_ref):
    """Grid (k, n_blocks) — digests for the SAMPLED partitions rows[p] only
    (sampled-digest audit mode: k = m_validators * audit_k columns per step
    instead of all n_parts). The row ids ride the scalar-prefetch channel
    and were consumed by the BlockSpec index_maps — the body never touches
    them. tau_ref[0] > 0 applies the ButterflyClip clip weight (the sampled
    sibling of _vt_batched_kernel); 0 emits the plain contribution digests
    (_dg_batched_kernel), so one kernel serves every verifiable spec."""
    del rows_ref  # consumed by the index_maps
    blk = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(blk == 0)
    def _reset():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = xs_ref[0].astype(jnp.float32) - v_ref[0].astype(jnp.float32)
    zb = z_ref[0].astype(jnp.float32)
    dot_ref[...] += jnp.sum(diff * zb, axis=1, keepdims=True)
    sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _epilogue():
        tau = tau_ref[0]
        norms = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0))
        cw = jnp.where(
            tau > 0.0, jnp.minimum(1.0, tau / jnp.maximum(norms, 1e-30)), 1.0
        )
        s_ref[0] = (cw * dot_ref[...]).reshape(s_ref.shape[1:])
        norm_ref[0] = norms.reshape(norm_ref.shape[1:])


def digest_tables_rows_pallas(
    parts, agg, z, rows, tau=0.0, *, block: int = DEFAULT_BLOCK,
    interpret: bool | None = None
):
    """Sampled-column digest tables in one pass of the SAMPLED partitions.

    parts: (n_parts, n, part); agg, z: (n_parts, part); rows: (k,) i32
    sampled partition ids; tau: scalar — > 0 applies the ButterflyClip clip
    weight min(1, tau/||diff||), 0 emits the plain verified:* digests.
    Returns (s (k, n), norms (k, n)), column p of the output = partition
    rows[p].

    The row ids are a scalar-prefetch operand (SMEM), so every BlockSpec
    index_map picks its partition block dynamically — HBM traffic is
    O(k * n * part), not O(n_parts * n * part): the kernel-side half of the
    sampled-digest cost model.
    """
    n_parts, n, d = parts.shape
    k = rows.shape[0]
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        agg = jnp.pad(agg, ((0, 0), (0, dp - d)))
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k, n_blocks),
        in_specs=[
            pl.BlockSpec((1, n, blk), lambda p, b, rows, tau: (rows[p], 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b, rows, tau: (rows[p], 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b, rows, tau: (rows[p], 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n), lambda p, b, rows, tau: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, b, rows, tau: (p, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
    )
    s, norms = _pallas_call(
        _rows_digest_kernel,
        name="digest_tables_rows",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((k, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((k, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(rows, jnp.int32),
        jnp.asarray(tau, jnp.float32).reshape(1),
        parts,
        agg.reshape(n_parts, 1, dp),
        z.reshape(n_parts, 1, dp),
    )
    return s[:, 0], norms[:, 0]


def _md_kernel(w_ref, xs_ref, z_ref, out_ref, s_ref, norm_ref, dot_ref,
               sq_ref, *, scales_ref=None):
    """Grid (n_parts, n_blocks) — fused weighted mean + digest tables.

    The per-partition weighted mean decomposes over lanes, so each block's
    aggregate is final as soon as it is computed; the per-peer digest dot
    and squared norm accumulate against it in the same step, and both
    tables are emitted on the last block. 1 HBM pass of x, zero
    materialized (n, d) temporaries.

    scales_ref (dequant variant): xs arrives in its wire dtype (int8/bf16)
    and both phases see ``xs.astype(f32) * scale`` — the exact formula of
    core.compression.dequantize, so aggregate and digests are computed over
    the dequantized-from-wire values (compressed:verified:mean)."""
    blk = pl.program_id(1)
    nb = pl.num_programs(1)
    xs = xs_ref[0].astype(jnp.float32)
    if scales_ref is not None:  # in-register dequantize of the wire payload
        xs = xs * scales_ref[0]
    w = w_ref[...].astype(jnp.float32)
    wsum = jnp.maximum(jnp.sum(w), 1e-30)
    agg = jnp.sum(w * xs, axis=0, keepdims=True) / wsum
    out_ref[0] = agg

    @pl.when(blk == 0)
    def _reset():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = xs - agg
    dot_ref[...] += jnp.sum(
        diff * z_ref[0].astype(jnp.float32), axis=1, keepdims=True
    )
    sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(blk == nb - 1)
    def _epilogue():
        s_ref[0] = dot_ref[...].reshape(s_ref.shape[1:])
        norm_ref[0] = jnp.sqrt(jnp.maximum(sq_ref[...], 0.0)).reshape(
            norm_ref.shape[1:]
        )


def mean_digest_fused_pallas(
    parts, z, weights=None, *, block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
):
    """verified:mean's fused aggregation + digest tables in one pallas_call.

    parts: (n_parts, n, part); z: (n_parts, part); weights: (n,).
    Returns (agg (n_parts, part), s (n_parts, n), norms (n_parts, n)).
    """
    n_parts, n, d = parts.shape
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    w2 = weights.reshape(n, 1).astype(jnp.float32)
    agg, s, norms = _pallas_call(
        _md_kernel,
        name="mean_digest_fused",
        grid=(n_parts, n_blocks),
        in_specs=[
            pl.BlockSpec((n, 1), lambda p, b: (0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w2, parts, z.reshape(n_parts, 1, dp))
    return agg[:, 0, :d], s[:, 0], norms[:, 0]


def _md_dequant_kernel(
    w_ref, scales_ref, xs_ref, z_ref, out_ref, s_ref, norm_ref, dot_ref,
    sq_ref,
):
    """Positional-ref adapter: sidecar scales between w and the wire xs."""
    _md_kernel(
        w_ref, xs_ref, z_ref, out_ref, s_ref, norm_ref, dot_ref, sq_ref,
        scales_ref=scales_ref,
    )


def mean_digest_fused_dequant_pallas(
    qs, scales, z, weights=None, *,
    block: int = DEFAULT_BLOCK, interpret: bool | None = None,
):
    """compressed:verified:mean's fused aggregation + digests over WIRE
    payloads: qs stays int8/bf16 in HBM for its one pass, dequantized
    in-register against the sidecar scales (see
    butterfly_clip_fused_dequant_pallas for the tiling argument).

    qs: (n_parts, n, part) wire dtype; scales: (n_parts, n) f32 (1s for
    bf16); z: (n_parts, part).
    Returns (agg (n_parts, part), s (n_parts, n), norms (n_parts, n)).
    """
    n_parts, n, d = qs.shape
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        qs = jnp.pad(qs, ((0, 0), (0, 0), (0, dp - d)))  # wire zeros: exact
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    w2 = weights.reshape(n, 1).astype(jnp.float32)
    sc3 = scales.reshape(n_parts, n, 1).astype(jnp.float32)
    agg, s, norms = _pallas_call(
        _md_dequant_kernel,
        name="mean_digest_fused_dequant",
        grid=(n_parts, n_blocks),
        in_specs=[
            pl.BlockSpec((n, 1), lambda p, b: (0, 0)),
            pl.BlockSpec((1, n, 1), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, n, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, dp), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w2, sc3, qs, z.reshape(n_parts, 1, dp))
    return agg[:, 0, :d], s[:, 0], norms[:, 0]


def verify_tables_batched_pallas(
    parts, agg, z, tau, *, block: int = DEFAULT_BLOCK,
    interpret: bool | None = None,
):
    """All-partition verification tables in one pass of the stacked parts.

    parts: (n_parts, n, part); agg, z: (n_parts, part).
    Returns (s (n_parts, n), norms (n_parts, n)).
    """
    n_parts, n, d = parts.shape
    blk = min(block, max(128, d))
    dp = -(-d // blk) * blk
    if dp != d:
        parts = jnp.pad(parts, ((0, 0), (0, 0), (0, dp - d)))
        agg = jnp.pad(agg, ((0, 0), (0, dp - d)))
        z = jnp.pad(z, ((0, 0), (0, dp - d)))
    n_blocks = dp // blk

    tau2 = jnp.asarray(tau, jnp.float32).reshape(1, 1)
    s, norms = _pallas_call(
        _vt_batched_kernel,
        name="verify_tables_batched",
        grid=(n_parts, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
            pl.BlockSpec((1, 1, blk), lambda p, b: (p, 0, b)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda p, b: (p, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
            jax.ShapeDtypeStruct((n_parts, 1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(tau2, parts, agg.reshape(n_parts, 1, dp), z.reshape(n_parts, 1, dp))
    return s[:, 0], norms[:, 0]
