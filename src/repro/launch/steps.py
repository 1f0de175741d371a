"""Distributed step builders for the production mesh.

Three step kinds per architecture:

* baseline train   — auto-GSPMD FSDP('data') x TP('model') AR-SGD (the
                     paper's All-Reduce comparison; also the 33-pair roofline
                     baseline).
* BTARD train      — the paper's technique as a first-class distributed step:
                     stage 1 computes per-peer gradients (shard_map manual
                     over the peer axes = pod x data, auto over 'model');
                     stage 2 is the AggregatorSpec-dispatched robust
                     all-reduce (fully-manual shard_map). Verifiable specs
                     run the butterfly: all_to_all gradient partitions,
                     per-partition aggregation by the owner (CenteredClip
                     for the flagship, the base coordinatewise fn for
                     verified:* wrapped specs; optionally Pallas kernels),
                     the O(n^2)-scalar verification tables / contribution
                     digests, all_gather back. Non-verifiable specs (mean,
                     krum, ...) all_gather the stack and apply the registry
                     fn (trusted-PS model, zero tables).
* serve (prefill / decode) — auto-GSPMD with KV-cache shardings
                     (sequence-sharded for long_500k).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.aggregators import resolve_spec
from repro.core.centered_clip import (
    centered_clip,
    centered_clip_adaptive,
    clip_residuals,
)
from repro.launch import input_specs as ispecs
from repro.models import Model
from repro.optim.optimizers import apply_updates
from repro.sharding import param_specs, set_mesh


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def opt_state_specs(opt_state_abs, pspecs):
    """Optimizer state mirrors the param tree per moment buffer."""

    def per_bucket(bucket):
        return pspecs

    return {k: pspecs for k in opt_state_abs} if isinstance(opt_state_abs, dict) else opt_state_abs


# ===========================================================================
# Baseline AR-SGD train step (auto GSPMD, FSDP x TP)
# ===========================================================================
def make_baseline_train_step(model: Model, optimizer, mesh, shape):
    set_mesh(mesh)
    params_abs = model.abstract_params()
    pspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(param_specs(params_abs), mesh), params_abs, mesh
    )
    bspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.batch_specs(model.cfg, shape, "train"), mesh),
        ispecs.abstract_batch(model.cfg, shape, "train"),
        mesh,
    )
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    ospecs = {k: pspecs for k in opt_abs}

    def train_step(params, opt_state, batch, step):
        (loss, metrics), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
            params, batch
        )
        updates, opt_state = optimizer.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    jitted = jax.jit(
        train_step,
        in_shardings=(
            _named(mesh, pspecs),
            _named(mesh, ospecs),
            _named(mesh, bspecs),
            None,
        ),
        out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs), None),
    )
    abstract_args = (
        params_abs,
        opt_abs,
        ispecs.abstract_batch(model.cfg, shape, "train"),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    return jitted, abstract_args


# ===========================================================================
# BTARD butterfly stage (fully-manual shard_map over every mesh axis)
# ===========================================================================
def _flatten_local(leaves, dtype=jnp.float32):
    return jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])


def _unflatten_local(vec, leaves):
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(vec[off : off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return out


def _collapse_peer_mesh(mesh):
    """Collapse multi-axis peer meshes (pod x data) into ONE manual axis.

    jaxlib 0.4.37's SPMD partitioner RET_CHECKs ("Incompatible manual
    sharding ... aligned.has_value()") on partial-manual shard_map regions
    whose manual set spans MULTIPLE mesh axes next to an auto 'model' axis;
    a single manual axis is the well-trodden code path. Device order under
    P(('pod', 'data')) equals P('peers') on the reshaped mesh (pod-major),
    so caller-side shardings built on the original mesh stay compatible.
    Returns (mesh, peer_axes)."""
    peer_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if len(peer_axes) <= 1:
        return mesh, peer_axes
    from jax.sharding import AxisType, Mesh

    other = tuple(a for a in mesh.axis_names if a not in peer_axes)
    perm = [mesh.axis_names.index(a) for a in peer_axes + other]
    devs = np.transpose(mesh.devices, perm)
    devs = devs.reshape((-1,) + devs.shape[len(peer_axes):])
    names = ("peers",) + other
    mesh = Mesh(devs, names, axis_types=(AxisType.Auto,) * len(names))
    return mesh, ("peers",)


def aggregation_stage(
    g_vec, peer_axes, n_peers, spec, weights, seed, use_pallas=False,
    delta_max=None, v0_full=None, gather_axes=(), groups=None,
    audit_k=None, agg_attack_scale=None, byz_mask=None, audit_grad=None,
):
    """Fully-manual-region robust all-reduce of one local gradient vector,
    dispatched by :class:`~repro.core.aggregators.AggregatorSpec`. Returns
    (aggregated vector, verification dict).

    Verifiable specs run the paper's butterfly topology: the local
    (model-shard) gradient vector is split into n_peers partitions;
    partition j is robustly aggregated by peer j (all_to_all), exactly
    Alg. 2 with partitions laid out over the TPU peer axis. For the
    ButterflyClip flagship the CenteredClip params (tau / n_iters /
    adaptive_tol) come from the spec and the tables are the tau-clipped
    residuals; for ``verified:<base>`` wrapped coordinatewise specs
    (core.verification) the partition owner applies the BASE fn to its
    all_to_all'd stack and broadcasts the generalized contribution digests
    s_i = <z, x_i - v>, ||x_i - v|| instead — same O(n^2)-scalar table
    traffic, same O(d)-per-peer gradient traffic as the flagship, where the
    unwrapped baselines pay the O(n*d) PS all_gather below. The V2
    checksum is emitted only for specs with the linear zero-sum identity
    (butterfly_clip, verified:mean); nonlinear wrapped specs report 0 and
    rely on validator recomputation (the host protocol's audit arm).

    ``compressed:<verifiable>`` specs (core.compression) quantize each
    (peer -> owner) payload before the exchange: the gradient all_to_all
    carries int8/bf16 wire words (≈4x / 2x fewer bytes than f32) plus one
    f32 sidecar scale per payload in a second scalar all_to_all. All
    aggregation and every digest then run over the dequantized-from-wire
    values — dispatch continues with the INNER spec — so sender, owner and
    validator agree bit-for-bit and honest peers are never accused over
    rounding. On the Pallas paths the received wire stack feeds the fused
    dequantize kernels directly (HBM reads stay 1-2 bytes/coordinate).

    Non-verifiable specs (mean, median, Krum, ...) have no partition
    ownership to verify: every peer all_gathers the full stack and applies
    the registry fn (the trusted-PS communication model, O(n·d) per peer
    instead of the butterfly's O(d)); the verification tables come back as
    zeros and the launch-side ban policy never fires. ``gather_axes`` names
    the NON-peer manual mesh axes (model shards): coordinatewise specs
    apply per shard (exact — they decompose over coordinates), while
    norm/distance-based specs (Krum, geometric median, CenteredClip) first
    join the shards along those axes so the full-vector geometry — and
    e.g. Krum's single global argmin — is preserved; the joined layout is
    a fixed coordinate permutation of the parameter vector, irrelevant to
    permutation-invariant fns, and each device slices its own shard back.

    v0_full: optional (d,) previous aggregated vector (replicated — every
    peer holds it after last step's all_gather); warm-startable specs seed
    their iteration from it, cutting the budget (DESIGN.md). Adaptive
    specs' per-device while_loops with data-dependent trip counts are fine
    in the manual region because the loop body contains no collectives;
    the verification tables are computed exactly once against the final
    iterate, so the broadcast protocol is budget-oblivious.

    Flat-cost verification axes (core.hierarchy — verifiable specs only):

    ``groups=g`` runs the butterfly-of-butterflies: the peer axis splits
    into g groups of gs = n/g via ``axis_index_groups`` (one manual mesh
    axis, two collective scopes). Level 1 is the ordinary butterfly WITHIN
    each group — gs partitions of size d/gs, owner = member index, digests
    against the group aggregate — so per-peer table traffic is O(gs^2)
    instead of O(n^2). Level 2 combines the g group aggregates by
    active-weight mean with a grouped psum at fixed member index (linear —
    the zero-sum checksum identity holds exactly for ANY base), and each
    group reconstructs the same full vector from its own level-1 gather.

    ``audit_k=k`` is sampled-digest mode: only the k owner columns in this
    step's rotating window (start = seed mod n) broadcast digests; every
    other owner ships zeros. Because checksum and votes are computed FROM
    the zeroed digests, the ban policy is silent at unsampled columns by
    construction (the zero-scatter invariant) — table bytes drop to
    O(n*k) while the rotating window bounds every column's audit staleness
    by n/k full cycles. Composes with ``groups``.

    ``agg_attack_scale`` + ``byz_mask`` simulate the LYING OWNER: a
    Byzantine partition owner corrupts its aggregate after aggregating and
    reports digests recomputed against the corrupted value — perfectly
    self-consistent tables, undetectable by the V1 mismatch rule. The
    validator audit arm (always on for verifiable specs) is what catches
    it: the shared seed elects one owner column per step, every validator
    recomputes that partition's aggregation from the same payloads, and
    the max deviation from the broadcast value is reported per peer in
    ``audit_agg_mismatch`` (exact zero for honest owners). ``audit_grad``
    threads the analogous gradient-recompute deviation from the caller
    (the payload audit — see _build_btard_step); both feed the host ban
    policy, closing the loop for nonlinear verified:* specs whose digests
    carry no checksum.
    """
    spec = resolve_spec(spec)
    d = g_vec.shape[0]
    if not spec.verifiable:
        join = tuple(gather_axes) if not spec.coordinatewise else ()
        with jax.named_scope("exchange"):
            stack = jax.lax.all_gather(g_vec, peer_axes)  # (n_peers, d) each
            v0 = None
            if v0_full is not None and spec.warm_startable:
                v0 = v0_full.astype(jnp.float32)
            if join:
                stack = jax.lax.all_gather(stack, join, axis=1, tiled=True)
                if v0 is not None:
                    v0 = jax.lax.all_gather(v0, join, axis=0, tiled=True)
            # pin the gathered transport dtype before the f32 upcast below —
            # same hoist hazard as the butterfly barrier at the all_to_all
            stack = jax.lax.optimization_barrier(stack)
        with jax.named_scope("clip"):
            agg_fn = spec.build(n_peers, stack.shape[1], use_pallas=use_pallas)
            flat, info = agg_fn(
                stack.astype(jnp.float32),
                weights if spec.weighted else None,
                v0,
                jax.random.key(seed),
            )
        if join:  # slice this device's model shard back out
            with jax.named_scope("gather"):
                my = jnp.zeros((), jnp.int32)
                for a in join:  # row-major over the joined axes == gather order
                    my = my * jax.lax.psum(1, a) + jax.lax.axis_index(a)
                flat = jax.lax.dynamic_slice_in_dim(flat, my * d, d)
        verif = {
            "checksum": jnp.zeros((1,), jnp.float32),
            "votes": jnp.zeros((1,), jnp.float32),
            "clip_iters": jnp.asarray(info.iters, jnp.int32)[None],
            "s_table": jnp.zeros((n_peers, n_peers), jnp.float32),
            "norm_table": jnp.zeros((n_peers, n_peers), jnp.float32),
            # the trusted-PS model has no audit protocol — zeros keep the
            # verif tree uniform across specs
            "audit_target": jnp.zeros((1,), jnp.int32),
            "audit_grad_mismatch": jnp.zeros((1,), jnp.float32),
            "audit_agg_mismatch": jnp.zeros((1,), jnp.float32),
        }
        return flat.astype(jnp.float32), verif

    from repro.core import compression as comp_mod
    from repro.core import verification as verif_mod

    my_idx = jax.lax.axis_index(peer_axes)
    hier = groups is not None and groups > 1
    if hier:
        from repro.core.hierarchy import group_shape

        n_groups, gs = group_shape(n_peers, groups)
        lvl1_groups = [[a * gs + c for c in range(gs)] for a in range(n_groups)]
        lvl2_groups = [[a * gs + c for a in range(n_groups)] for c in range(gs)]
        my_group = my_idx // gs
        fold_idx = my_idx % gs  # member index == level-1 partition owner
        n_loc = gs
        # the owner aggregates its GROUP's payloads with the group's weights
        weights = jnp.take(weights.reshape(n_groups, gs), my_group, axis=0)
    else:
        lvl1_groups = lvl2_groups = None
        fold_idx = my_idx
        n_loc = n_peers

    part = -(-d // n_loc)
    pad = part * n_loc - d
    with jax.named_scope("exchange"):
        if pad:
            g_vec = jnp.concatenate([g_vec, jnp.zeros((pad,), g_vec.dtype)])
        x = g_vec.reshape(n_loc, part)
        # each peer receives everyone's copy of ITS partition. The barrier pins
        # the transport dtype: without it XLA hoists the downstream f32 upcast
        # ahead of the collective, silently undoing bf16 transport (§Perf H3)
        # — or, for compressed specs, the wire codec itself.
        comp_wire = None
        if comp_mod.is_wrapped(spec):
            # compressed:* — quantize each (peer -> owner) payload BEFORE the
            # exchange: the gradient all_to_all ships 1-2 byte wire words, plus
            # ONE f32 sidecar scalar per payload in a second tiny all_to_all
            # (n_peers floats vs part*n_peers wire words). Every digest below
            # runs over the DEQUANTIZED wire values (core.compression), so the
            # owner's tables match any validator's recompute bit-for-bit and
            # rounding can never trip an accusation.
            codec = comp_mod.codec_of(spec)
            wire, scales = comp_mod.quantize(x, codec)  # (n, part), (n,) f32
            recv_w = jax.lax.all_to_all(
                wire, peer_axes, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=lvl1_groups,
            )
            recv_s = jax.lax.all_to_all(
                scales, peer_axes, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=lvl1_groups,
            )
            recv_w, recv_s = jax.lax.optimization_barrier((recv_w, recv_s))
            comp_wire = (recv_w, recv_s)
            recv = comp_mod.dequantize(recv_w, recv_s)  # the f32 wire values
            spec = comp_mod.inner_spec(spec)  # dispatch below is by inner spec
        else:
            recv = jax.lax.all_to_all(
                x, peer_axes, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=lvl1_groups,
            )
            recv = jax.lax.optimization_barrier(recv)

    # --- z for the verification tables (Alg. 6): derived from the shared
    # MPRNG seed, folded by partition owner index; commitments are host-side
    # (protocol). Known before the aggregation runs, so the fused kernel can
    # emit the tables from its epilogue pass. Hierarchical mode folds by
    # MEMBER index: z is shared across groups (core.hierarchy's z1).
    with jax.named_scope("verify"):
        z = jax.random.normal(
            jax.random.fold_in(jax.random.key(seed), fold_idx), (part,))
        z = z / jnp.maximum(jnp.linalg.norm(z), 1e-30)

    if verif_mod.is_wrapped(spec):
        # wrapped coordinatewise spec: the partition owner runs the BASE fn
        # over its all_to_all'd stack (exact — coordinatewise fns decompose
        # over the partition split) and broadcasts the generalized digests;
        # the fused-vs-standalone kernel dispatch lives in owner_aggregate,
        # so its digests count under ``clip`` with the aggregation.
        with jax.named_scope("clip"):
            agg, s_local, norms_local, iters_used = verif_mod.owner_aggregate(
                spec, recv, z, weights, use_pallas=use_pallas,
                key=jax.random.key(seed), wire=comp_wire,
            )
        tau_v = 0.0
        with_checksum = verif_mod.has_zero_checksum(spec)
        return _verify_audit_tail(
            g_vec, d, pad, recv, agg, s_local, norms_local, iters_used,
            weights, peer_axes, delta_max, z, seed, n_peers, n_loc, fold_idx,
            my_idx, tau_v, with_checksum, lvl1_groups, lvl2_groups, audit_k,
            agg_attack_scale, byz_mask, audit_grad,
        )

    p = spec.param_dict()
    tau, clip_iters = p["tau"], p["n_iters"]
    adaptive_tol = p["adaptive_tol"]

    # ``clip`` is the aggregation proper; the fused Pallas kernels emit the
    # digest tables from their epilogue pass, so on those paths the table
    # work counts under ``clip`` too, and ``verify`` holds only the rest
    with jax.named_scope("clip"):
        v0 = None
        if v0_full is not None:
            if pad:
                v0_full = jnp.concatenate(
                    [v0_full, jnp.zeros((pad,), v0_full.dtype)]
                )
            v0 = v0_full.reshape(n_loc, part)[fold_idx].astype(jnp.float32)

    iters_used = jnp.asarray(clip_iters, jnp.int32)
    if adaptive_tol is not None and use_pallas:
        from repro.kernels.ops import butterfly_clip_adaptive_op, verify_tables_op

        # early-exit one-pass-per-iteration driver (single-partition batch),
        # then ONE verification-table pass against the final iterate
        with jax.named_scope("clip"):
            agg_b, iters = butterfly_clip_adaptive_op(
                recv[None], tau, adaptive_tol, weights,
                v0=None if v0 is None else v0[None], max_iters=clip_iters,
            )
            agg, iters_used = agg_b[0], iters[0]
        with jax.named_scope("verify"):
            s_local, norms_local = verify_tables_op(
                recv, agg, z.astype(jnp.float32), tau
            )
    elif use_pallas and comp_wire is not None:
        from repro.kernels.ops import butterfly_clip_fused_dequant_op

        # the wire payloads stay int8/bf16 in HBM: the fused dequantize+
        # clip+digest kernel makes its n_iters + 2 passes over 1-2 byte
        # data, dequantizing in-register against the sidecar scales
        qs, qscales = comp_wire
        with jax.named_scope("clip"):
            agg_b, s_b, n_b = butterfly_clip_fused_dequant_op(
                qs[None], qscales[None], tau, z.astype(jnp.float32)[None],
                weights, v0=None if v0 is None else v0[None],
                n_iters=clip_iters,
            )
            agg, s_local, norms_local = agg_b[0], s_b[:, 0], n_b[:, 0]
    elif use_pallas:
        from repro.kernels.ops import centered_clip_fused_op

        # fused one-pass-per-iteration kernel: aggregate + s_i = <z, Delta_i>
        # + ||x_i - v|| in n_iters + 2 HBM passes of the peer stack
        with jax.named_scope("clip"):
            agg, s_local, norms_local = centered_clip_fused_op(
                recv, tau, z.astype(jnp.float32), weights, v0=v0,
                n_iters=clip_iters,
            )
    else:
        with jax.named_scope("clip"):
            if adaptive_tol is not None:
                agg, iters_used = centered_clip_adaptive(
                    recv, tau, adaptive_tol, clip_iters, weights=weights,
                    v0=v0,
                )
            else:
                agg = centered_clip(
                    recv, tau=tau, n_iters=clip_iters, weights=weights, v0=v0
                )
            agg = agg.astype(jnp.float32)
        with jax.named_scope("verify"):
            deltas = clip_residuals(recv.astype(jnp.float32), agg, tau)
            s_local = deltas @ z  # (n_peers,) — s_i^{my partition}
            norms_local = jnp.linalg.norm(
                recv.astype(jnp.float32) - agg[None], axis=1)

    return _verify_audit_tail(
        g_vec, d, pad, recv, agg, s_local, norms_local, iters_used, weights,
        peer_axes, delta_max, z, seed, n_peers, n_loc, fold_idx, my_idx,
        float(tau), True, lvl1_groups, lvl2_groups, audit_k,
        agg_attack_scale, byz_mask, audit_grad,
    )


def _verify_audit_tail(
    g_vec, d, pad, recv, agg, s_local, norms_local, iters_used, weights,
    peer_axes, delta_max, z, seed, n_peers, n_loc, fold_idx, my_idx, tau_v,
    with_checksum, lvl1_groups, lvl2_groups, audit_k, agg_attack_scale,
    byz_mask, audit_grad,
):
    """Shared post-aggregation tail of the verifiable butterfly paths:
    lying-owner simulation, validator audit, sampled-column masking, then
    the table broadcast (:func:`_emit_tables`)."""
    with jax.named_scope("verify"):
        # --- aggregator-shift attack (the lying owner): the Byzantine owner
        # corrupts its partition aggregate AFTER aggregating and recomputes its
        # digests against the corrupted value — self-consistent tables, so the
        # V1 mismatch rule never fires; detection falls to the V2 checksum
        # (linear specs) or the validator audit below (any spec).
        agg_honest = agg
        if agg_attack_scale is not None and byz_mask is not None:
            is_byz = byz_mask[my_idx] > 0
            rms = jnp.linalg.norm(agg) / jnp.sqrt(jnp.float32(agg.shape[0]))
            agg = jnp.where(is_byz, agg + agg_attack_scale * (rms + 1e-8), agg)
            diff = recv.astype(jnp.float32) - agg[None]
            n_att = jnp.linalg.norm(diff, axis=1)
            dots = diff @ z.astype(jnp.float32)
            if tau_v > 0:
                s_att = jnp.minimum(1.0, tau_v / jnp.maximum(n_att, 1e-30)) * dots
            else:
                s_att = dots
            s_local = jnp.where(is_byz, s_att, s_local)
            norms_local = jnp.where(is_byz, n_att, norms_local)

        # --- validator audit arm (launch-side CHOOSETARGET): the shared seed
        # elects one owner column per step; validators recompute that column's
        # aggregation from the same payloads (bit-identical here — agg_honest
        # IS that recompute) and report the max deviation of the value the
        # owner actually broadcast. Exact zero for honest owners.
        t_col = jnp.mod(jnp.asarray(seed, jnp.int32), n_loc)
        audit_agg = jnp.where(
            fold_idx == t_col,
            jnp.max(jnp.abs(agg.astype(jnp.float32)
                            - agg_honest.astype(jnp.float32))),
            0.0,
        )

        # --- sampled-digest masking: only the audit_k owner columns in this
        # step's rotating window broadcast digests; everyone else ships zeros.
        # checksum/votes below are computed FROM the zeroed digests, so the ban
        # policy is silent at unsampled columns by construction (the
        # zero-scatter invariant — core.hierarchy).
        if audit_k is not None:
            k_tot = min(int(audit_k), n_loc)
            sampled_me = jnp.mod(fold_idx - jnp.asarray(seed, jnp.int32), n_loc) < k_tot
            s_local = jnp.where(sampled_me, s_local, 0.0)
            norms_local = jnp.where(sampled_me, norms_local, 0.0)

        extra = {
            "audit_target": jnp.mod(jnp.asarray(seed, jnp.int32), n_peers)[None],
            "audit_grad_mismatch": (
                jnp.zeros((1,), jnp.float32) if audit_grad is None
                else jnp.asarray(audit_grad, jnp.float32)[None]
            ),
            "audit_agg_mismatch": jnp.asarray(audit_agg, jnp.float32)[None],
        }
    return _emit_tables(
        g_vec, d, pad, agg, s_local, norms_local, iters_used, weights,
        peer_axes, delta_max, with_checksum=with_checksum,
        lvl1_groups=lvl1_groups, lvl2_groups=lvl2_groups, extra_verif=extra,
    )


def _emit_tables(g_vec, d, pad, agg, s_local, norms_local, iters_used,
                 weights, peer_axes, delta_max, with_checksum=True,
                 lvl1_groups=None, lvl2_groups=None, extra_verif=None):
    """Shared table-broadcast tail of the verifiable butterfly paths:
    checksum/Delta_max votes from the owner's local tables, the O(n^2)
    scalar table all_gathers, and the aggregated-partition all_gather.
    ``with_checksum=False`` (nonlinear verified:* specs — no zero-sum
    identity) reports a zero checksum so the launch-side ban policy never
    fires on honest finite-precision residue.

    Hierarchical mode (``lvl1_groups``/``lvl2_groups`` set): the owner's
    digest row IS its table row — each peer emits its (gs,) digests under a
    peer-axis out spec, so global table traffic is n*gs scalars instead of
    n^2. The level-2 combine is the active-weight mean of the g group
    aggregates, evaluated by grouped psum at fixed member index (linear in
    the group aggregates, so the zero-sum checksum identity is exact for
    ANY base); each group then reconstructs the same full vector from its
    own level-1 all_gather."""
    with jax.named_scope("verify"):
        if with_checksum:
            checksum = jnp.abs((s_local * weights).sum())
        else:
            checksum = jnp.zeros(())
        votes = ((norms_local > delta_max) * weights).sum() if delta_max is not None else jnp.zeros(())
        if lvl1_groups is not None:
            # hierarchical: per-peer (gs,) table rows (n*gs scalars globally)
            s_table = s_local[None]
            norm_table = norms_local[None]
        else:
            # broadcast the scalar tables (O(n^2) data total — size-independent)
            s_table = jax.lax.all_gather(s_local, peer_axes)  # (n_parts, n_peers)
            norm_table = jax.lax.all_gather(norms_local, peer_axes)
    with jax.named_scope("gather"):
        if lvl1_groups is not None:
            w_grp = weights.sum()  # this group's active weight W_a
            num = jax.lax.psum(
                w_grp * agg.astype(jnp.float32), peer_axes,
                axis_index_groups=lvl2_groups,
            )
            den = jax.lax.psum(w_grp, peer_axes, axis_index_groups=lvl2_groups)
            v2 = num / jnp.maximum(den, 1e-30)
            full = jax.lax.all_gather(
                v2.astype(g_vec.dtype), peer_axes, tiled=True,
                axis_index_groups=lvl1_groups,
            )  # (gs*part,) == padded d, same in every group
        else:
            full = jax.lax.all_gather(
                agg.astype(g_vec.dtype), peer_axes, tiled=True
            )  # (n_peers*part,) — gather in transport dtype
        # barrier before the upcast: the gather must ship transport dtype
        full = jax.lax.optimization_barrier(full).astype(jnp.float32)
        if pad:
            full = full[:d]
    # checksum/votes are per-partition (expand-dims -> peer-axis out spec);
    # the gathered s/norm tables are the SAME on every peer (the broadcast)
    # so they leave the region as replicated (n_parts, n_peers) arrays —
    # except hierarchical mode, where each peer's row leaves under the peer
    # axis as a global (n_peers, gs) table.
    verif = {
        "checksum": checksum[None],
        "votes": jnp.asarray(votes)[None],
        "clip_iters": jnp.asarray(iters_used, jnp.int32)[None],
        "s_table": s_table,
        "norm_table": norm_table,
    }
    if extra_verif:
        verif.update(extra_verif)
    return full, verif


def butterfly_stage(
    g_vec, peer_axes, n_peers, tau, clip_iters, weights, seed, use_pallas=False,
    delta_max=None, v0_full=None, adaptive_tol=None,
):
    """DEPRECATED shim — resolves to :func:`aggregation_stage` with the
    equivalent ButterflyClip :class:`AggregatorSpec`."""
    import warnings

    warnings.warn(
        "butterfly_stage is deprecated; call aggregation_stage with an "
        "AggregatorSpec (repro.core.aggregators) instead",
        DeprecationWarning, stacklevel=2,
    )
    from repro.core.aggregators import AggregatorSpec

    spec = AggregatorSpec(
        "butterfly_clip",
        (("adaptive_tol", adaptive_tol), ("n_iters", int(clip_iters)),
         ("tau", float(tau)), ("warm_start", v0_full is not None)),
    )
    return aggregation_stage(
        g_vec, peer_axes, n_peers, spec, weights, seed,
        use_pallas=use_pallas, delta_max=delta_max, v0_full=v0_full,
    )


def device_attack(grads_vec, byz_mask, peer_axes, kind, key, lam=100.0):
    """Device-side Byzantine simulation on the local gradient vector."""
    my_idx = jax.lax.axis_index(peer_axes)
    is_byz = byz_mask[my_idx] > 0
    if kind == "none":
        return grads_vec
    if kind == "sign_flip":
        return jnp.where(is_byz, -lam * grads_vec, grads_vec)
    if kind == "random_direction":
        v = jax.random.normal(key, grads_vec.shape, grads_vec.dtype)
        v = v / jnp.maximum(jnp.linalg.norm(v), 1e-30)
        scale = lam * jnp.linalg.norm(grads_vec)
        return jnp.where(is_byz, scale * v, grads_vec)
    if kind == "ipm":
        n_honest = jnp.maximum((1.0 - byz_mask).sum(), 1.0)
        honest_sum = jax.lax.psum(
            jnp.where(is_byz, 0.0, 1.0) * grads_vec, peer_axes
        )
        mu = honest_sum / n_honest
        return jnp.where(is_byz, -0.6 * mu, grads_vec)
    raise ValueError(kind)


# ===========================================================================
# BTARD distributed train step
# ===========================================================================
def _build_btard_step(
    model: Model,
    optimizer,
    mesh,
    shape,
    tau: float = 1.0,
    clip_iters: int = 20,
    attack: str = "none",
    use_pallas: bool = False,
    delta_max: float | None = 1e9,
    zero1: bool = True,
    transport_dtype=jnp.float32,
    warm_start: bool = False,
    adaptive_tol: float | None = None,
    aggregator=None,
    groups: int | None = None,
    audit_k: int | None = None,
    agg_attack: float | None = None,
):
    """Shared construction for the single-step and scanned BTARD steps.

    ``aggregator`` is an :class:`AggregatorSpec` / ``"name[:k=v,...]"``
    string / None (-> flagship ButterflyClip); the legacy knobs (tau /
    clip_iters / adaptive_tol / warm_start) fill the spec's declared params
    as defaults. The shard_map carry/specs derive from the resolved spec's
    capability flags: only a warm-startable spec with ``warm_start`` set
    threads the previous-aggregate input into the aggregation region.

    ``groups`` / ``audit_k`` select the flat-cost verification axes
    (hierarchical butterfly-of-butterflies / sampled-digest mode — see
    :func:`aggregation_stage`); ``agg_attack`` turns on the lying-owner
    simulation at the given shift scale. All three apply to verifiable
    specs only.

    Returns (step_core, mesh, specs dict, abstract args) where
    step_core(params, opt_state, batch, step, seed, byz_mask, weights,
    v_prev) -> (params, opt_state, metrics, verif, v_agg); v_prev / v_agg
    is the flattened previous/current aggregate (the warm-start carry).
    """
    spec = resolve_spec(aggregator).with_defaults(
        tau=tau, n_iters=clip_iters, max_iters=clip_iters,
        adaptive_tol=adaptive_tol, warm_start=warm_start,
    )
    carry_v0 = spec.warm_startable and bool(spec.get("warm_start", False))
    mesh, peer_axes = _collapse_peer_mesh(mesh)
    hier = bool(groups and groups > 1 and spec.verifiable)
    # the non-peer manual axes (model shards) — non-coordinatewise specs
    # join these inside aggregation_stage to see full-vector geometry
    model_axes = tuple(a for a in mesh.axis_names if a not in peer_axes)
    set_mesh(mesh)
    cfg = model.cfg
    n_peers = int(np.prod([mesh.shape[a] for a in peer_axes]))
    if hier:
        from repro.core.hierarchy import group_shape

        group_shape(n_peers, groups)  # validates g | n and gs >= 2

    params_abs = model.abstract_params()
    # replicated over peers: param specs WITHOUT the fsdp axis
    pspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(param_specs(params_abs), mesh), params_abs, mesh
    )
    pspecs = jax.tree.map(
        lambda s: P(*[_drop_data(e) for e in s]), pspecs, is_leaf=lambda x: isinstance(x, P)
    )
    bspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.batch_specs(cfg, shape, "train"), mesh),
        ispecs.abstract_batch(cfg, shape, "train"),
        mesh,
    )
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    ospecs = {k: pspecs for k in opt_abs}

    # ---- stage 1: per-peer grads (manual peers, auto model) ----------------
    def peer_grads(params, batch):
        from repro.sharding.specs import set_manual_axes

        set_manual_axes(peer_axes)  # trace-time: shard() skips peer axes
        try:
            with jax.named_scope("btard.grads"):
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True
                )(params, batch)
        finally:
            set_manual_axes(())
        return loss[None], jax.tree.map(lambda g: g[None], grads)

    stage1 = jax.shard_map(
        peer_grads,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda s: P(), pspecs, is_leaf=_is_p), _peer_lead(bspecs, peer_axes)),
        out_specs=(P(peer_axes), jax.tree.map(lambda s: P(peer_axes), pspecs, is_leaf=_is_p)),
        axis_names=set(peer_axes),
        check_vma=False,
    )

    # ---- stage 2: butterfly robust all-reduce (fully manual) ---------------
    # each phase of the step runs under a named scope: the compiled
    # instructions stay as they are, and each carries its phase in its
    # ``op_name`` metadata, by which a device trace is read per phase
    @jax.named_scope("btard.aggregate")
    def butterfly_all(grads, seed, byz_mask, weights, key, *rest):
        with jax.named_scope("flatten"):
            leaves = jax.tree.leaves(grads)
            # beyond-paper: gradients can travel the butterfly in bf16 — halves
            # the all_to_all + all_gather volume; CenteredClip still iterates in
            # f32 (EXPERIMENTS.md §Perf H3)
            vec = _flatten_local([l[0] for l in leaves], transport_dtype)
            vec_honest = vec
            vec = device_attack(vec, byz_mask, peer_axes, attack, key)
            # per-peer public-seed spot-check residue: every peer's max
            # deviation between the payload it broadcast and the recompute from
            # the public batch (vec_honest IS that recompute here) — exact zero
            # for honest peers. The host membership layer consumes this for
            # PROBATION slots only (the Sybil gate of core.sybil: a joining
            # peer is spot-checked every step of its probation window), the
            # protocol-faithful subset of a per-peer observable.
            probe = jnp.max(jnp.abs(vec.astype(jnp.float32)
                                    - vec_honest.astype(jnp.float32)))
            if model_axes:
                probe = jax.lax.pmax(probe, model_axes)
            audit_grad = None
            if spec.verifiable:
                # gradient-recompute audit (CHOOSETARGET's payload arm): the
                # shared seed elects one peer; validators recompute its
                # gradient from the PUBLIC batch — bit-identical here, the
                # pre-attack vector IS that recompute — and report the max
                # deviation of the payload it actually sent. Exact zero for
                # honest peers, so the host ban policy can fire on any nonzero
                # regardless of the spec's digest linearity.
                t_peer = jnp.mod(jnp.asarray(seed, jnp.int32), n_peers)
                audit_grad = jnp.where(
                    jax.lax.axis_index(peer_axes) == t_peer,
                    jnp.max(jnp.abs(vec.astype(jnp.float32)
                                    - vec_honest.astype(jnp.float32))),
                    0.0,
                )
            v0_full = None
            if carry_v0:
                # previous aggregate, flattened in the SAME leaf order as vec
                v0_full = _flatten_local(jax.tree.leaves(rest[0]), jnp.float32)
        agg_vec, verif = aggregation_stage(
            vec, peer_axes, n_peers, spec, weights, seed,
            use_pallas=use_pallas, delta_max=delta_max, v0_full=v0_full,
            gather_axes=model_axes, groups=groups if hier else None,
            audit_k=audit_k if spec.verifiable else None,
            agg_attack_scale=agg_attack, byz_mask=byz_mask,
            audit_grad=audit_grad,
        )
        with jax.named_scope("gather"):
            agg_leaves = _unflatten_local(agg_vec, [l[0] for l in leaves])
        agg = jax.tree.unflatten(jax.tree.structure(grads), agg_leaves)
        verif["probe_mismatch"] = probe[None]
        return agg, verif

    manual_pspecs = jax.tree.map(
        lambda s: P(peer_axes, *s), pspecs, is_leaf=_is_p
    )
    agg_specs = pspecs  # the aggregate tree shards exactly like the params
    stage2 = jax.shard_map(
        butterfly_all,
        mesh=mesh,
        in_specs=(manual_pspecs, P(), P(), P(), P())
        + ((agg_specs,) if carry_v0 else ()),
        out_specs=(
            agg_specs,
            {
                "checksum": P(peer_axes),
                "votes": P(peer_axes),
                "clip_iters": P(peer_axes),
                # hierarchical tables leave per-peer ((n, gs) global rows);
                # flat tables are the replicated post-broadcast (n, n)
                "s_table": P(peer_axes, None) if hier else P(None, None),
                "norm_table": P(peer_axes, None) if hier else P(None, None),
                "audit_target": P(peer_axes),
                "audit_grad_mismatch": P(peer_axes),
                "audit_agg_mismatch": P(peer_axes),
                "probe_mismatch": P(peer_axes),
            },
        ),
        axis_names=set(mesh.axis_names),
        check_vma=False,
    )

    def step_core(params, opt_state, batch, step, seed, byz_mask, weights,
                  v_prev=None):
        loss, grads = stage1(params, batch)
        # attack key from the traced (seed, step) pair — a literal-seeded
        # key here would be randomness outside the protocol transcript
        # (btard-lint purity rule; the MPRNG chain covers all other keys)
        key = jax.random.fold_in(jax.random.key(seed), step)
        rest = (v_prev,) if carry_v0 else ()
        agg, verif = stage2(grads, seed, byz_mask, weights, key, *rest)
        with jax.named_scope("btard.optimizer"):
            updates, opt_state = optimizer.update(
                agg, opt_state, params, step)
            params = apply_updates(params, updates)
        metrics = {
            "loss": loss.mean(),
            "checksum_max": verif["checksum"].max(),
            "votes_max": verif["votes"].max(),
            "clip_iters_max": verif["clip_iters"].max(),
        }
        return params, opt_state, metrics, verif, agg

    if zero1:
        zaxis = peer_axes[0] if len(peer_axes) == 1 else "data"
        n_zshards = mesh.shape.get(zaxis, 1)
        ospecs = {
            k: jax.tree.map(
                lambda s, l: _with_data(s, l.shape, n_zshards, zaxis),
                pspecs,
                opt_abs[k],
                is_leaf=_is_p,
            )
            for k in opt_abs
        }

    specs = {
        "params": pspecs,
        "opt": ospecs,
        "batch": bspecs,
        "agg": agg_specs,
    }
    abstract_args = (
        params_abs,
        opt_abs,
        ispecs.abstract_batch(cfg, shape, "train"),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((n_peers,), jnp.float32),
        jax.ShapeDtypeStruct((n_peers,), jnp.float32),
    )
    return step_core, mesh, specs, abstract_args


def make_btard_train_step(
    model: Model,
    optimizer,
    mesh,
    shape,
    tau: float = 1.0,
    clip_iters: int = 20,
    attack: str = "none",
    use_pallas: bool = False,
    delta_max: float | None = 1e9,
    zero1: bool = True,
    transport_dtype=jnp.float32,
    adaptive_tol: float | None = None,
    aggregator=None,
    groups: int | None = None,
    audit_k: int | None = None,
    agg_attack: float | None = None,
):
    """Returns (jitted step, abstract args).

    step(params, opt_state, batch, step_idx, seed, byz_mask, weights)
      -> (params, opt_state, metrics, verif)
    Params are replicated over the peer axes (each peer = full replica,
    model-sharded over 'model'); optimizer state is ZeRO-1-sharded over the
    peer axis when zero1 (the butterfly partition owner updates its shard —
    exactly Alg. 7's per-partition ownership). ``aggregator`` selects the
    robust aggregation stage by AggregatorSpec (default ButterflyClip).

    The single-step API carries no previous aggregate between calls, so a
    spec's ``warm_start`` is forced off here — use
    :func:`make_btard_scan_train_step`, whose v_prev carry implements it.
    """
    spec = resolve_spec(aggregator)
    if "warm_start" in spec.definition.param_names:
        spec = spec.override(warm_start=False)
    step_core, mesh, specs, abstract_args = _build_btard_step(
        model, optimizer, mesh, shape, tau=tau, clip_iters=clip_iters,
        attack=attack, use_pallas=use_pallas, delta_max=delta_max,
        zero1=zero1, transport_dtype=transport_dtype, warm_start=False,
        adaptive_tol=adaptive_tol, aggregator=spec, groups=groups,
        audit_k=audit_k, agg_attack=agg_attack,
    )

    def train_step(params, opt_state, batch, step, seed, byz_mask, weights):
        params, opt_state, metrics, verif, _ = step_core(
            params, opt_state, batch, step, seed, byz_mask, weights
        )
        return params, opt_state, metrics, verif

    jitted = jax.jit(
        train_step,
        in_shardings=(
            _named(mesh, specs["params"]),
            _named(mesh, specs["opt"]),
            _named(mesh, specs["batch"]),
            None,
            None,
            None,
            None,
        ),
        out_shardings=(
            _named(mesh, specs["params"]), _named(mesh, specs["opt"]),
            None, None,
        ),
    )
    return jitted, abstract_args


def make_btard_scan_train_step(
    model: Model,
    optimizer,
    mesh,
    shape,
    n_scan_steps: int,
    tau: float = 1.0,
    clip_iters: int = 20,
    attack: str = "none",
    use_pallas: bool = False,
    delta_max: float | None = 1e9,
    zero1: bool = True,
    transport_dtype=jnp.float32,
    warm_start: bool = False,
    adaptive_tol: float | None = None,
    aggregator=None,
    pipeline=None,
    extras=None,
    groups: int | None = None,
    audit_k: int | None = None,
    agg_attack: float | None = None,
):
    """The BTARD train step under ``lax.scan``: ``n_scan_steps`` full rounds
    per dispatch, one compiled program, zero host sync between rounds.

    Host-batch mode (pipeline=None):
      step(params, opt_state, batches, steps, seeds, byz_mask, weights,
      v_prev) -> (params, opt_state, metrics, verif, v_last)
      batches: the single-step batch tree with a leading (n_scan_steps,) dim.

    Device-resident mode (pipeline = a ``repro.data.TokenPipeline``):
      step(params, opt_state, steps, seeds, byz_mask, weights, v_prev)
      Each round's batch is generated INSIDE the scan body from the public
      ``peer_key`` chain (``pipeline.device_batch``) and sharded to the
      batch specs — zero host->device batch bytes per step, and the bits
      match the host pipeline exactly (tests/test_device_data.py), so
      verification/accusation semantics are unchanged.

    steps / seeds: (n_scan_steps,) i32. v_prev / v_last: the aggregate tree
    (zeros_like(params) to start) — with ``warm_start`` each round's
    CenteredClip starts from the previous round's aggregate, which cuts the
    iteration budget (see kernels/DESIGN.md); without it the carry is
    threaded but unused. ``adaptive_tol`` makes that saving automatic: the
    clip loop early-exits at ||v_{l+1}-v_l|| <= tol (clip_iters = cap).
    metrics / verif gain a leading scan dim.
    Returns (jitted step, abstract args).
    """
    step_core, mesh, specs, abstract_args = _build_btard_step(
        model, optimizer, mesh, shape, tau=tau, clip_iters=clip_iters,
        attack=attack, use_pallas=use_pallas, delta_max=delta_max,
        zero1=zero1, transport_dtype=transport_dtype, warm_start=warm_start,
        adaptive_tol=adaptive_tol, aggregator=aggregator, groups=groups,
        audit_k=audit_k, agg_attack=agg_attack,
    )
    agg_shardings = _named(mesh, specs["agg"])
    # the in-scan generator is pinned REPLICATED: every peer generates the
    # full public batch and slices its share — the paper's public-data model
    # (any peer recomputes any batch), and the only sharding under which the
    # non-partitionable threefry PRNG emits the SAME bits as the host
    # pipeline (GSPMD partitioning of the generator changes random bits;
    # tested in tests/test_device_data.py). Generation cost is trivial next
    # to fwd+bwd; the peer-sharded consumer reshards with a local slice.
    replicated_batch = jax.tree.map(
        lambda s: NamedSharding(mesh, P()), specs["batch"], is_leaf=_is_p
    )

    def body_of(batch_for, byz_mask, weights):
        def body(carry, xs):
            params, opt_state, v_prev = carry
            step, seed = xs[-2], xs[-1]
            batch = batch_for(xs)
            params, opt_state, metrics, verif, agg = step_core(
                params, opt_state, batch, step, seed, byz_mask, weights,
                v_prev=v_prev,
            )
            return (params, opt_state, agg), (metrics, verif)

        return body

    if pipeline is not None:

        def scan_step(params, opt_state, steps, seeds, byz_mask, weights,
                      v_prev):
            def batch_for(xs):
                # the in-scan data phase: public-seed batch for this round,
                # generated on device (replicated — see replicated_batch)
                with jax.named_scope("btard.data"):
                    batch = pipeline.device_batch(xs[-2], extras=extras)
                    return jax.tree.map(
                        jax.lax.with_sharding_constraint, batch,
                        replicated_batch,
                    )

            (params, opt_state, v_last), (metrics, verif) = jax.lax.scan(
                body_of(batch_for, byz_mask, weights),
                (params, opt_state, v_prev), (steps, seeds),
            )
            return params, opt_state, metrics, verif, v_last

        in_shardings = (
            _named(mesh, specs["params"]), _named(mesh, specs["opt"]),
            None, None, None, None, agg_shardings,
        )
    else:

        def scan_step(params, opt_state, batches, steps, seeds, byz_mask,
                      weights, v_prev):
            (params, opt_state, v_last), (metrics, verif) = jax.lax.scan(
                body_of(lambda xs: xs[0], byz_mask, weights),
                (params, opt_state, v_prev), (batches, steps, seeds),
            )
            return params, opt_state, metrics, verif, v_last

        # stacked batches: leading scan dim replicated, per-step as before
        scan_bspecs = jax.tree.map(
            lambda s: P(None, *s), specs["batch"], is_leaf=_is_p
        )
        in_shardings = (
            _named(mesh, specs["params"]), _named(mesh, specs["opt"]),
            _named(mesh, scan_bspecs), None, None, None, None, agg_shardings,
        )

    jitted = jax.jit(
        scan_step,
        in_shardings=in_shardings,
        out_shardings=(
            _named(mesh, specs["params"]), _named(mesh, specs["opt"]),
            None, None, agg_shardings,
        ),
    )
    p_abs, o_abs, b_abs, step_abs, seed_abs, byz_abs, w_abs = abstract_args
    stack = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_scan_steps,) + l.shape, l.dtype), tree
    )
    steps_abs = jax.ShapeDtypeStruct((n_scan_steps,), jnp.int32)
    v_abs = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), p_abs
    )
    if pipeline is not None:
        scan_abstract = (p_abs, o_abs, steps_abs, steps_abs, byz_abs, w_abs,
                         v_abs)
    else:
        scan_abstract = (p_abs, o_abs, stack(b_abs), steps_abs, steps_abs,
                         byz_abs, w_abs, v_abs)
    return jitted, scan_abstract


def _is_p(x):
    return isinstance(x, P)


def _drop_data(entry):
    if entry in ("data", "pod", "peers"):
        return None
    if isinstance(entry, (tuple, list)):
        kept = tuple(a for a in entry if a not in ("data", "pod", "peers"))
        return kept or None
    return entry


def _with_data(spec, shape, n_shards, axis="data"):
    """ZeRO-1: shard the first shardable (unsharded & divisible) dim of the
    moment buffers on the peer axis — the butterfly partition owner updates
    its shard."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % n_shards == 0:
            entries[i] = axis
            return P(*entries)
    return P(*entries)


def _peer_lead(bspecs, peer_axes):
    def fix(s):
        return P(peer_axes, *list(s)[1:])

    return jax.tree.map(fix, bspecs, is_leaf=_is_p)


# ===========================================================================
# Serving steps
# ===========================================================================
def make_decode_step(model: Model, mesh, shape, fsdp_params: bool | None = None):
    set_mesh(mesh)
    params_abs = model.abstract_params()
    if fsdp_params is None:
        per_chip = model.param_count() * 2 / mesh.shape["model"]
        fsdp_params = per_chip > 10e9  # replicate unless it would not fit
    pspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(param_specs(params_abs), mesh), params_abs, mesh
    )
    if not fsdp_params:
        pspecs = jax.tree.map(
            lambda s: P(*[_drop_data(e) for e in s]), pspecs, is_leaf=_is_p
        )
    cspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.cache_specs(model, shape, mesh), mesh),
        ispecs.abstract_cache(model, shape),
        mesh,
    )
    bspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.batch_specs(model.cfg, shape, "decode"), mesh),
        ispecs.abstract_batch(model.cfg, shape, "decode"),
        mesh,
    )

    def decode(params, cache, batch):
        logits, new_cache = model.decode_step(params, batch, cache)
        return logits, new_cache

    jitted = jax.jit(
        decode,
        in_shardings=(
            _named(mesh, pspecs),
            _named(mesh, cspecs),
            _named(mesh, bspecs),
        ),
        out_shardings=(None, _named(mesh, cspecs)),
    )
    abstract_args = (
        params_abs,
        ispecs.abstract_cache(model, shape),
        ispecs.abstract_batch(model.cfg, shape, "decode"),
    )
    return jitted, abstract_args


def make_prefill_step(model: Model, mesh, shape, fsdp_params: bool = True):
    set_mesh(mesh)
    params_abs = model.abstract_params()
    pspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(param_specs(params_abs), mesh), params_abs, mesh
    )
    if not fsdp_params:
        pspecs = jax.tree.map(
            lambda s: P(*[_drop_data(e) for e in s]), pspecs, is_leaf=_is_p
        )
    cspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.cache_specs(model, shape, mesh), mesh),
        ispecs.abstract_cache(model, shape),
        mesh,
    )
    bspecs = ispecs.sanitize_specs(
        ispecs.resolve_spec_names(ispecs.batch_specs(model.cfg, shape, "prefill"), mesh),
        ispecs.abstract_batch(model.cfg, shape, "prefill"),
        mesh,
    )

    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache)

    jitted = jax.jit(
        prefill,
        in_shardings=(
            _named(mesh, pspecs),
            _named(mesh, bspecs),
            _named(mesh, cspecs),
        ),
        out_shardings=(None, _named(mesh, cspecs)),
    )
    abstract_args = (
        params_abs,
        ispecs.abstract_batch(model.cfg, shape, "prefill"),
        ispecs.abstract_cache(model, shape),
    )
    return jitted, abstract_args
