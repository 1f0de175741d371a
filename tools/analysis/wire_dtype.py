"""btard-lint layer 2: wire-dtype contracts of the launch aggregation stage.

The robust all-reduce ships gradients over collectives in a *declared* wire
dtype — bf16 transport for the plain butterfly, the codec dtype (int8/bf16)
for ``compressed:*`` specs — and every digest is f32 computed from the
post-exchange wire values. XLA is free to hoist a later f32 upcast across a
collective unless an ``optimization_barrier`` pins the boundary; when it
does, the wire silently carries f32 and the compression is undone (the PR 6
bug class). These rules catch that statically:

* **W1 — unpinned upcast of a collective result**: a widening
  ``convert_element_type`` whose operand dataflows (through layout-only
  ops) straight from a collective output, with no barrier in between.
* **W2 — widened operand feeding a collective**: the same hoist written by
  hand — upcasting *before* the exchange.
* **W3 — wire presence**: at least one collective actually carries the
  declared wire dtype (compression that never reaches the wire is a no-op).
* **W4 — collective dtype allow-list**: no collective ships anything
  outside {wire dtype, f32 scalars/tables, integers, bool}.
* **W5 — digests are f32**: the broadcast verification tables and checksum
  leave the stage as float32.

Tracing needs ZERO devices: an ``AbstractMesh`` + ``shard_map`` +
``jax.make_jaxpr`` stages the collectives abstractly.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax.sharding import AbstractMesh, PartitionSpec as P

from tools.analysis.common import (
    COLLECTIVE_PRIMS,
    CheckResult,
    Finding,
    as_jaxpr,
    callback_findings,
    constant_key_findings,
    is_widening,
    iter_jaxprs,
    producer_map,
    trace_back,
)

N_PEERS = 8
D = 512

# (label, spec string, aggregation_stage kwargs, declared wire dtype).
# Transport is always bf16 — the narrow dtype is what makes a hoisted
# upcast visible in the trace (an f32->f32 convert stages no eqn at all).
SPEC_MATRIX = (
    ("butterfly_clip", "butterfly_clip", {}, jnp.bfloat16),
    ("butterfly_warm", "butterfly_clip:warm_start=true", {"v0": True},
     jnp.bfloat16),
    ("butterfly_adaptive", "butterfly_clip:adaptive_tol=1e-4", {},
     jnp.bfloat16),
    ("verified_mean", "verified:mean", {}, jnp.bfloat16),
    ("verified_trimmed", "verified:trimmed_mean", {}, jnp.bfloat16),
    ("compressed_int8", "compressed:butterfly_clip:codec=int8", {},
     jnp.int8),
    ("compressed_bf16", "compressed:butterfly_clip:codec=bf16", {},
     jnp.bfloat16),
    ("compressed_verified", "compressed:verified:mean:codec=int8", {},
     jnp.int8),
    ("hier", "butterfly_clip", {"groups": 2}, jnp.bfloat16),
    ("hier_compressed", "compressed:butterfly_clip:codec=int8",
     {"groups": 2}, jnp.int8),
    ("sampled", "butterfly_clip", {"audit_k": 2}, jnp.bfloat16),
    ("lying_owner", "butterfly_clip", {"agg_attack": 2.0}, jnp.bfloat16),
    ("nonverifiable_mean", "mean", {}, jnp.bfloat16),
    ("nonverifiable_krum", "krum:n_byzantine=1", {}, jnp.bfloat16),
)

# The real-model gauntlet cell: the same stage traced at the flat gradient
# dim of the reduced zoo transformer (core.flatten boundary over
# abstract_params — no weights materialize), under the mixed-precision
# contract the gauntlet ships: bf16 payload on the wire, f32 digests over
# dequantized wire values. Synthetic-D green + real-D red would mean the
# contract breaks at scale (e.g. a dim-dependent rewrite hoists the upcast).
REAL_MODEL_SPEC = (
    "real_model_albert", "compressed:verified:mean:codec=bf16", jnp.bfloat16
)


def _real_model_dim() -> int:
    """Flat gradient dim of the gauntlet's reference arch, padded to the
    peer count (the same ravel boundary BTARDTrainer flattens at)."""
    from repro.configs import get_config, reduce_config
    from repro.core.flatten import FlatBoundary
    from repro.models.model import Model

    model = Model(reduce_config(get_config("albert-large")))
    d = FlatBoundary(model.abstract_params()).d
    return -(-d // N_PEERS) * N_PEERS

# dtypes that may legitimately cross a collective besides the wire dtype:
# f32 sidecar scales / digest tables / level-2 combines, index/mask ints
_ALWAYS_OK = frozenset({
    jnp.dtype(jnp.float32), jnp.dtype(jnp.int32), jnp.dtype(jnp.uint32),
    jnp.dtype(jnp.bool_),
})

_VERIF_KEYS = ("checksum", "votes", "clip_iters", "s_table", "norm_table",
               "audit_target", "audit_grad_mismatch", "audit_agg_mismatch")


def trace_aggregation_stage(spec: str, *, groups=None, audit_k=None,
                            agg_attack=None, v0=False, use_pallas=False,
                            d=D):
    """Trace one launch-side robust all-reduce on an abstract 8-peer mesh.

    Returns (closed_jaxpr, out_avals) for ``aggregation_stage`` wrapped in
    the same manual-region harness the real train step uses. ``d`` is the
    per-peer gradient dim (default the synthetic ``D``; the real-model cell
    passes the zoo arch's flat dim).
    """
    from repro.launch.steps import aggregation_stage

    mesh = AbstractMesh((N_PEERS,), ("peers",))
    hier = groups is not None and groups > 1

    def region(g_vec, weights, seed, byz_mask, v0_full):
        return aggregation_stage(
            g_vec, "peers", N_PEERS, spec, weights, seed,
            use_pallas=use_pallas, delta_max=10.0,
            v0_full=v0_full if v0 else None,
            groups=groups, audit_k=audit_k,
            agg_attack_scale=agg_attack,
            byz_mask=byz_mask if agg_attack is not None else None,
        )

    verif_specs = {k: P("peers") for k in _VERIF_KEYS}
    verif_specs["s_table"] = P("peers", None) if hier else P(None, None)
    verif_specs["norm_table"] = verif_specs["s_table"]
    f = jax.shard_map(
        region, mesh=mesh,
        in_specs=(P("peers"), P(), P(), P(), P()),
        out_specs=(P(), verif_specs),
        check_vma=False,
    )
    args = (
        jax.ShapeDtypeStruct((N_PEERS * d,), jnp.bfloat16),
        jax.ShapeDtypeStruct((N_PEERS,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((N_PEERS,), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32),
    )
    closed = jax.make_jaxpr(f)(*args)
    out = jax.eval_shape(f, *args)
    return closed, out


def wire_findings(closed, where: str, wire_dtype,
                  transport_dtype=jnp.bfloat16):
    """Rules W1-W4 over one traced stage. ``wire_dtype`` is the payload-
    exchange dtype (the codec dtype for compressed specs); the aggregate
    redistribution still travels in ``transport_dtype``, so both are
    sanctioned on the wire — anything else (beyond f32 scalars/tables and
    integers) is a leak."""
    findings = []
    wire = jnp.dtype(wire_dtype)
    ok = _ALWAYS_OK | {wire, jnp.dtype(transport_dtype)}
    saw_wire = False
    for j in iter_jaxprs(as_jaxpr(closed)):
        prod = producer_map(j)
        for e in j.eqns:
            if is_widening(e) and isinstance(e.invars[0], jcore.Var):
                src = trace_back(e.invars[0], prod)
                if src is not None and src.primitive.name in COLLECTIVE_PRIMS:
                    findings.append(Finding(
                        "wire_dtype", where,
                        f"f-widening convert ({e.invars[0].aval.dtype} -> "
                        f"{e.params['new_dtype']}) consumes the result of "
                        f"'{src.primitive.name}' with no optimization_barrier"
                        " between them: XLA may hoist the upcast across the "
                        "collective and ship the wide dtype on the wire",
                    ))
            if e.primitive.name in COLLECTIVE_PRIMS:
                for v in e.invars:
                    if not isinstance(v, jcore.Var):
                        continue
                    if jnp.dtype(v.aval.dtype) == wire:
                        saw_wire = True
                    elif jnp.dtype(v.aval.dtype) not in ok:
                        findings.append(Finding(
                            "wire_dtype", where,
                            f"'{e.primitive.name}' ships dtype "
                            f"{v.aval.dtype}; sanctioned wire dtypes are "
                            f"{wire} (payload) / "
                            f"{jnp.dtype(transport_dtype)} (transport) "
                            "plus f32 scalars/tables",
                        ))
                    src = trace_back(v, prod)
                    if src is not None and is_widening(src):
                        findings.append(Finding(
                            "wire_dtype", where,
                            f"operand of '{e.primitive.name}' was widened "
                            f"to {src.params['new_dtype']} before the "
                            "exchange: upcast after the collective (behind "
                            "a barrier), not before it",
                        ))
    if not saw_wire:
        findings.append(Finding(
            "wire_dtype", where,
            f"no collective carries the declared wire dtype {wire}: the "
            "narrow transport/codec never reaches the wire",
        ))
    return findings


def digest_findings(out, where: str):
    """Rule W5: tables/checksum leave the stage as f32 (digests are f32
    computed from wire values — the dtype every validator recomputes in)."""
    findings = []
    _, verif = out
    for k in ("checksum", "s_table", "norm_table"):
        if jnp.dtype(verif[k].dtype) != jnp.dtype(jnp.float32):
            findings.append(Finding(
                "wire_dtype", where,
                f"digest output '{k}' has dtype {verif[k].dtype}, "
                "expected float32",
            ))
    return findings


def check_wire_dtype() -> CheckResult:
    t0 = time.time()
    res = CheckResult("wire_dtype")
    for label, spec, kw, wire in SPEC_MATRIX:
        where = f"aggregation_stage[{label}]"
        closed, out = trace_aggregation_stage(spec, **kw)
        res.findings += wire_findings(closed, where, wire)
        res.findings += digest_findings(out, where)
        # the stage is protocol-critical launch code: purity applies too
        res.findings += callback_findings(closed, where)
        res.findings += constant_key_findings(closed, where)
        res.traced += 1
    # real-model cell: same rules at the gauntlet arch's flat dim
    label, spec, wire = REAL_MODEL_SPEC
    where = f"aggregation_stage[{label}]"
    closed, out = trace_aggregation_stage(spec, d=_real_model_dim())
    res.findings += wire_findings(closed, where, wire)
    res.findings += digest_findings(out, where)
    res.findings += callback_findings(closed, where)
    res.findings += constant_key_findings(closed, where)
    res.traced += 1
    res.seconds = time.time() - t0
    return res
