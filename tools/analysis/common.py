"""Shared jaxpr-walking machinery for btard-lint (``tools.analysis``).

Every check in this package reduces to the same move: trace real repo code
with abstract inputs (``jax.make_jaxpr`` — no FLOPs, no devices), then walk
the jaxpr — including every sub-jaxpr hiding in ``scan`` / ``while`` /
``cond`` / ``pjit`` / ``shard_map`` / ``pallas_call`` params — and assert
protocol invariants on the primitives found there. This module owns the
walking; the per-layer rule sets live in ``jaxpr_checks`` / ``wire_dtype``
/ ``contracts`` / ``kernels_check``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.extend import core as jcore

# Primitives that reach outside the traced program. Any of these inside a
# protocol phase breaks bitwise recomputability: a validator re-running the
# step cannot reproduce what a host callback did.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
})

# Cross-peer collectives — the wire. Operand dtype at these IS the wire
# dtype; everything the digests commit to crosses one of these.
COLLECTIVE_PRIMS = frozenset({
    "all_to_all", "all_gather", "psum", "reduce_scatter", "psum_scatter",
    "ppermute", "pmax", "pmin",
})

# PRNG key creation. Keys must be created from *traced inputs* (the
# MPRNG chain: state.key / the shared seed); a key minted from a literal
# is randomness the protocol transcript does not cover.
KEY_CREATION_PRIMS = frozenset({"random_seed", "threefry_seed"})

# Shape/layout-only ops the dataflow walks look through when connecting a
# ``convert_element_type`` to the collective that produced (or consumes)
# its operand. ``optimization_barrier`` is deliberately NOT here — the
# barrier is the sanctioned way to pin a dtype boundary, so hitting one
# ends the walk.
TRANSPARENT_PRIMS = frozenset({
    "reshape", "transpose", "squeeze", "broadcast_in_dim", "slice",
    "dynamic_slice", "rev", "copy", "concatenate", "pad", "expand_dims",
})


@dataclass
class Finding:
    """One invariant violation. ``check`` names the rule that fired,
    ``where`` the traced target (function / spec / kernel), ``message``
    the violation itself."""

    check: str
    where: str
    message: str

    def to_dict(self) -> dict:
        return {"check": self.check, "where": self.where,
                "message": self.message}

    def __str__(self) -> str:  # CLI text rendering
        return f"[{self.check}] {self.where}: {self.message}"


@dataclass
class CheckResult:
    """Outcome of one named check: pass/fail + findings + trace count."""

    name: str
    findings: list = field(default_factory=list)
    traced: int = 0
    seconds: float = 0.0
    error: str | None = None

    @property
    def ok(self) -> bool:
        return not self.findings and self.error is None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "status": "pass" if self.ok else "fail",
            "traced": self.traced,
            "seconds": round(self.seconds, 2),
            "error": self.error,
            "findings": [f.to_dict() for f in self.findings],
        }


def _param_jaxprs(eqn):
    """Every Jaxpr/ClosedJaxpr nested in an eqn's params (scan/while/cond
    bodies, pjit/shard_map/pallas_call callees, custom_* rules)."""
    out = []
    for v in eqn.params.values():
        for item in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(item, jcore.ClosedJaxpr):
                out.append(item.jaxpr)
            elif isinstance(item, jcore.Jaxpr):
                out.append(item)
    return out


def iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable from it."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        yield j
        for e in j.eqns:
            stack.extend(_param_jaxprs(e))


def iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested sub-jaxprs."""
    for j in iter_jaxprs(jaxpr):
        yield from j.eqns


def as_jaxpr(closed_or_open):
    return (closed_or_open.jaxpr
            if isinstance(closed_or_open, jcore.ClosedJaxpr)
            else closed_or_open)


def producer_map(jaxpr):
    """var -> producing eqn, for ONE jaxpr level (vars are jaxpr-scoped)."""
    prod = {}
    for e in jaxpr.eqns:
        for v in e.outvars:
            prod[v] = e
    return prod


def trace_back(var, prod):
    """Walk ``var`` backwards through layout-only (TRANSPARENT) eqns and
    return the first structural producer eqn, or None for jaxpr inputs/
    consts. Multi-input transparent ops (concatenate, pad) stop the walk —
    a merged value has no single producer."""
    seen = 0
    while True:
        e = prod.get(var)
        if e is None:
            return None
        if e.primitive.name not in TRANSPARENT_PRIMS:
            return e
        data_in = [v for v in e.invars if isinstance(v, jcore.Var)]
        if len(data_in) != 1:
            return e  # merged value: treat the transparent op as structural
        var = data_in[0]
        seen += 1
        if seen > 1000:  # defensive: malformed jaxpr
            return e


def is_widening(eqn) -> bool:
    """True for a ``convert_element_type`` that grows the element size —
    the upcast direction XLA is allowed to hoist across a collective,
    which is exactly what undoes wire compression (PR 6)."""
    if eqn.primitive.name != "convert_element_type":
        return False
    src = eqn.invars[0].aval.dtype
    dst = eqn.params["new_dtype"]
    try:
        return jax.numpy.dtype(dst).itemsize > jax.numpy.dtype(src).itemsize
    except TypeError:
        return False


def _is_key_like(aval) -> bool:
    """PRNG key material: a typed key array, or the raw uint32[2] pair."""
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    if jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key):
        return True
    shape = getattr(aval, "shape", ())
    return dtype == jax.numpy.uint32 and tuple(shape[-1:]) == (2,)


def constant_key_findings(closed, where: str, check: str = "purity"):
    """Findings for PRNG key material baked into the program as constants
    or minted from literals — randomness outside the MPRNG fold-in chain.

    Two ways a hidden key enters a traced phase: (a) ``jax.random.key(0)``
    / ``PRNGKey(0)`` traced with a literal seed (a ``random_seed`` /
    ``threefry_seed`` eqn whose operand is a Literal), (b) a key built
    eagerly on the host and closed over (a key-dtype / uint32[2] constvar).
    Honest recomputation still matches — the bits are deterministic — but
    the randomness is pinned across runs and invisible to the transcript,
    so the lint bans both forms outright.
    """
    findings = []
    jaxpr = as_jaxpr(closed)
    for cv in jaxpr.constvars:
        if _is_key_like(cv.aval):
            findings.append(Finding(
                check, where,
                f"constant PRNG key baked into the trace ({cv.aval}); "
                "derive keys from the state key / shared seed inputs",
            ))
    for e in iter_eqns(jaxpr):
        if e.primitive.name in KEY_CREATION_PRIMS:
            seed_in = e.invars[0]
            if isinstance(seed_in, jcore.Literal):
                findings.append(Finding(
                    check, where,
                    f"{e.primitive.name} from literal seed "
                    f"{seed_in.val!r}: off-chain PRNG (key material must "
                    "derive from traced inputs — the MPRNG chain)",
                ))
    return findings


def callback_findings(closed, where: str, check: str = "purity"):
    """Findings for host callbacks / io primitives / ordered effects."""
    findings = []
    jaxpr = as_jaxpr(closed)
    effects = getattr(closed, "effects", None) or jaxpr.effects
    if effects:
        findings.append(Finding(
            check, where,
            f"trace carries effects {sorted(str(x) for x in effects)}; "
            "protocol phases must be effect-free (bitwise recomputable)",
        ))
    for e in iter_eqns(jaxpr):
        if e.primitive.name in CALLBACK_PRIMS:
            findings.append(Finding(
                check, where,
                f"host-callback primitive '{e.primitive.name}' inside the "
                "traced program: validators cannot recompute host effects",
            ))
    return findings
