"""btard-lint layer 3: AggregatorSpec registry contracts.

The engine, the launch stages and the CLI all dispatch on a spec's
*capability flags* — ``verifiable`` decides whether the verification
pipeline runs, ``warm_startable`` whether the previous aggregate is carried
into the region, ``coordinatewise`` whether model shards may be aggregated
independently. A flag that disagrees with what the maker actually does is a
protocol bug waiting for the first config that trusts it. This layer checks
every registered spec (bases + ``verified:``/``compressed:`` wrappers)
against its *traced or executed* behavior:

* **C1 — name round-trip**: ``parse -> canonical -> parse`` is the
  identity, for the bare name and with every declared param set to a
  non-default value.
* **C2 — verifiable <=> tables**: under the engine's aggregation phase,
  verifiable specs produce (n, n) f32 digest tables; non-verifiable specs
  produce none (and :func:`verified_aggregate` rejects them).
* **C3 — warm_startable <=> v0 read**: built with ``warm_start=true``, a
  warm-startable spec's fn consumes the v0 input in its jaxpr; a
  non-warm-startable spec's fn ignores it.
* **C4 — weighted <=> weights read**: same, for the weights input.
* **C5 — coordinatewise is bitwise**: a flagged spec applied to two
  coordinate slices concatenates to the full-vector result *bitwise*
  (the exact property the launch path uses to skip the model-shard join).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from tools.analysis.common import CheckResult, Finding

# non-default value for every declared param name in the registry —
# exercises parse/canonical over every param's type (float/int/bool/str)
ALT_PARAMS = {
    "trim_ratio": 0.25,
    "eps": 1e-5,
    "max_iters": 7,
    "n_byzantine": 1,
    "tau": 0.5,
    "n_iters": 7,
    "adaptive_tol": 1e-3,
    "warm_start": True,
    "codec": "bf16",
}

_N, _D = 4, 16  # tiny concrete sizes for the bitwise probe


def _build_args(n, d):
    return (
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )


def _consumed_inputs(fn, n, d):
    """Which of (xs, weights, v0, key) the built fn's jaxpr actually reads.

    Returns a 4-tuple of bools. An input is 'read' if its top-level invar
    appears in any equation (values threaded into sub-jaxprs surface in the
    carrying eqn's invars, so one level is enough)."""
    def wrapped(xs, weights, v0, key):
        out, _info = fn(xs, weights, v0,
                        jax.random.wrap_key_data(key))
        return out

    closed = jax.make_jaxpr(wrapped)(*_build_args(n, d))
    invars = closed.jaxpr.invars
    used = set()
    for e in closed.jaxpr.eqns:
        for v in e.invars:
            if isinstance(v, jcore.Var):
                used.add(v)
    return tuple(v in used for v in invars)


def check_registry_roundtrip() -> CheckResult:
    """C1 over every registered name, bare and fully parameterized."""
    from repro.core import aggregators as agg_mod

    t0 = time.time()
    res = CheckResult("registry_roundtrip")
    for name in agg_mod.registered_aggregators():
        defn = agg_mod.REGISTRY[name]
        texts = [name]
        if defn.defaults:
            alt = {k: ALT_PARAMS[k] for k, _ in defn.defaults}
            spec = agg_mod.AggregatorSpec(name, tuple(sorted(alt.items())))
            texts.append(spec.canonical())
        for text in texts:
            res.traced += 1
            try:
                spec = agg_mod.AggregatorSpec.parse(text)
            except Exception as e:  # noqa: BLE001 — report, don't crash
                res.findings.append(Finding(
                    "registry_roundtrip", name,
                    f"parse({text!r}) raised {e!r}"))
                continue
            canon = spec.canonical()
            again = agg_mod.AggregatorSpec.parse(canon)
            if again != spec or again.canonical() != canon:
                res.findings.append(Finding(
                    "registry_roundtrip", name,
                    f"{text!r} -> {canon!r} -> {again.canonical()!r} "
                    "is not a fixed point",
                ))
    res.seconds = time.time() - t0
    return res


def check_capability_flags() -> CheckResult:
    """C2-C4: flags vs traced behavior, every registered spec."""
    from repro.core import aggregators as agg_mod
    from repro.core import engine

    t0 = time.time()
    res = CheckResult("capability_flags")
    for name in agg_mod.registered_aggregators():
        defn = agg_mod.REGISTRY[name]
        spec = agg_mod.AggregatorSpec(name).with_defaults(
            warm_start=True, n_byzantine=1)
        res.traced += 1

        # C2: tables under the engine aggregation phase
        cfg = engine.EngineConfig(n=8, d=64, aggregator=spec.canonical())
        state = engine.abstract_state(cfg)
        out = jax.eval_shape(
            lambda s, G, w, sd: engine.phase_aggregation(cfg, s, G, w, sd),
            state,
            jax.ShapeDtypeStruct((8, 64), jnp.float32),
            jax.ShapeDtypeStruct((8,), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        _agg, _parts, _z, s_tbl, norm_tbl, _it = out
        if defn.verifiable and (s_tbl is None or norm_tbl is None):
            res.findings.append(Finding(
                "capability_flags", name,
                "flagged verifiable but the aggregation phase emits no "
                "digest tables",
            ))
        elif defn.verifiable:
            if (tuple(s_tbl.shape) != (8, 8)
                    or s_tbl.dtype != jnp.float32
                    or norm_tbl.dtype != jnp.float32):
                res.findings.append(Finding(
                    "capability_flags", name,
                    f"digest tables are {s_tbl.shape}/{s_tbl.dtype}, "
                    "expected (n, n) float32",
                ))
        elif s_tbl is not None:
            res.findings.append(Finding(
                "capability_flags", name,
                "flagged non-verifiable but the aggregation phase emits "
                "digest tables",
            ))

        # C3/C4: does the built fn read v0 / weights?
        fn = spec.build(8, 64)
        _xs_used, w_used, v0_used, _k = _consumed_inputs(fn, 8, 64)
        if defn.warm_startable and not v0_used:
            res.findings.append(Finding(
                "capability_flags", name,
                "flagged warm_startable (built with warm_start=true) but "
                "the fn never reads v0: the launch carry would be wasted",
            ))
        if not defn.warm_startable and v0_used:
            res.findings.append(Finding(
                "capability_flags", name,
                "not flagged warm_startable but the fn reads v0: the "
                "launch path would never thread the carry it needs",
            ))
        if defn.weighted and not w_used:
            res.findings.append(Finding(
                "capability_flags", name,
                "flagged weighted but the fn never reads weights: "
                "banned peers would keep their votes",
            ))
    res.seconds = time.time() - t0
    return res


def check_coordinatewise() -> CheckResult:
    """C5: the bitwise split/concat probe for every flagged spec.

    The launch path trusts ``coordinatewise`` to aggregate model shards
    independently; digests are then recomputed per shard, so anything
    short of BITWISE equality lets honest peers accuse each other."""
    from repro.core import aggregators as agg_mod

    t0 = time.time()
    res = CheckResult("coordinatewise")
    key = jax.random.PRNGKey(7)
    xs = jax.random.normal(key, (_N, _D), jnp.float32)
    w = jnp.ones((_N,), jnp.float32)
    h = _D // 2
    for name in agg_mod.registered_aggregators():
        defn = agg_mod.REGISTRY[name]
        if not defn.coordinatewise:
            continue
        res.traced += 1
        spec = agg_mod.AggregatorSpec(name)
        full, _ = spec.build(_N, _D)(xs, w, None, None)
        left, _ = spec.build(_N, h)(xs[:, :h], w, None, None)
        right, _ = spec.build(_N, h)(xs[:, h:], w, None, None)
        stitched = jnp.concatenate([left, right])
        if bool(jnp.any(full != stitched)):
            mx = float(jnp.max(jnp.abs(
                full.astype(jnp.float32) - stitched.astype(jnp.float32))))
            res.findings.append(Finding(
                "coordinatewise", name,
                "flagged coordinatewise but split/concat is not bitwise "
                f"(max |diff| {mx:.3e}): per-shard aggregation would "
                "diverge from the full-vector recompute",
            ))
    res.seconds = time.time() - t0
    return res
