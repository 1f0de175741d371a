"""Compressed robust all-reduce (core.compression): quantized butterfly
payloads with EXACT verification.

* registry / combinator / CLI-parse contract for the compressed: wrappers
  (auto-lift through verified:, codec param binding, canonical round trip);
* hypothesis property tests for the wire codecs over ragged shapes, extreme
  magnitudes (denormal territory), and all-zero partitions: determinism
  (same bits in -> same wire bits out, the exact-verification foundation),
  the int8 half-step error bound, bf16 cast equality, and digest equality —
  the tables any validator recomputes from the wire values match the
  owner's bit-for-bit;
* the fused dequantize kernels == kernels/ref.py oracles per partition;
* the adversarial attack x codec engine grid: compressed ButterflyClip and
  compressed verified:mean ban every Byzantine peer within 5 steps under
  every attack, honest runs produce ZERO accusations over 50 steps, and
  the scanned engine matches the stepwise engine exactly;
* one-coordinate cheaters are banned under BOTH codecs, while a
  perturbation BELOW the int8 quantization step is invisible: same wire
  row, same aggregate, no accusation — the wire representation IS the
  protocol-visible contribution.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import example, given, settings, strategies as st

from repro.core import butterfly as bf
from repro.core import compression as comp
from repro.core import engine as eng
from repro.core import verification as verif
from repro.core.aggregators import AggregatorSpec, registered_aggregators
from repro.core.protocol import AttackConfig

N, D = 8, 48
BYZ = (6, 7)
BAN_WITHIN = 5
GRID_STEPS = 8
HONEST_STEPS = 50

ATTACKS = {
    "sign_flip": dict(kind="sign_flip", lam=1.0),
    "scaled": dict(kind="sign_flip", lam=1000.0),
    "random": dict(kind="random_direction", lam=100.0),
    "colluding": dict(kind="ipm_06"),
}


def _spec(name, codec):
    return AggregatorSpec(name, (("codec", codec),))


def _grid_specs(codec):
    return [
        _spec("compressed:butterfly_clip", codec),
        _spec("compressed:verified:mean", codec),
    ]


def _grads_fn(n=N, d=D):
    w_true = jax.random.normal(jax.random.key(9), (d,))

    def peer_grad(peer, step, params):
        k = jax.random.key((peer * 7919 + step) % (2**31 - 1))
        X = jax.random.normal(k, (4, d))
        return 2 * X.T @ (X @ params - X @ w_true) / 4

    def grads_fn(params, t, flips):
        G = jax.vmap(lambda i: peer_grad(i, t, params))(jnp.arange(n))
        return G, G

    return grads_fn


def _cfg(spec, attack_kw, m_validators=3):
    # clip_iters=200 runs CenteredClip to its fixed point so the V2
    # checksum is honest-clean (as in tests/test_verification_grid.py);
    # the wrapped mean declares no n_iters and ignores it.
    return eng.config_from_attack(
        N, D, AttackConfig(start_step=0, **attack_kw),
        tau=1.0, clip_iters=200, m_validators=m_validators, aggregator=spec,
    )


def _run_stepwise(cfg, byz_mask, steps, grads_fn=None):
    grads_fn = grads_fn or _grads_fn()
    step_fn = eng.jit_protocol_step(cfg)
    state = eng.init_state(cfg, seed=0)
    flips = jnp.zeros((N,), bool)
    params = jnp.zeros(D, jnp.float32)
    outs = []
    for _ in range(steps):
        G, H = grads_fn(params, state.step, flips)
        state, out = step_fn(state, byz_mask, G, H)
        outs.append(out)
    return state, outs


def _run_scan(cfg, byz_mask, steps, grads_fn=None):
    grads_fn = grads_fn or _grads_fn()
    return jax.jit(
        lambda s, b, p: eng.scan_protocol(cfg, s, b, p, grads_fn, steps)
    )(eng.init_state(cfg, seed=0), byz_mask, jnp.zeros(D, jnp.float32))


# ---------------------------------------------------------------------------
# Registry / combinator / parse contract
# ---------------------------------------------------------------------------
def test_compressed_combinator_and_registry():
    names = set(registered_aggregators())
    assert {"compressed:butterfly_clip", "compressed:verified:mean",
            "compressed:verified:trimmed_mean",
            "compressed:verified:coordinate_median"} <= names
    # every compressed wrapper stays verifiable and declares a codec
    for name in names:
        if name.startswith("compressed:"):
            spec = AggregatorSpec(name)
            assert spec.verifiable
            assert comp.codec_of(spec) == comp.DEFAULT_CODEC

    # combinator: verifiable specs wrap directly, params preserved
    w = comp.compressed(
        AggregatorSpec("butterfly_clip", (("n_iters", 7),)), codec="bf16"
    )
    assert w.name == "compressed:butterfly_clip"
    assert w.get("n_iters") == 7 and comp.codec_of(w) == "bf16"
    assert comp.inner_spec(w) == AggregatorSpec(
        "butterfly_clip", (("n_iters", 7),)
    )
    # non-verifiable coordinatewise specs lift through verified: first
    assert comp.compressed("mean").name == "compressed:verified:mean"
    # already-compressed: unchanged unless the codec is overridden
    assert comp.compressed(w) == w
    assert comp.codec_of(comp.compressed(w, codec="int8")) == "int8"
    # full-vector specs rejected, like verified:
    for name in ("krum", "geometric_median", "centered_clip"):
        with pytest.raises(ValueError, match="not coordinatewise"):
            comp.compressed(name)
    with pytest.raises(ValueError, match="unknown wire codec"):
        comp.compressed("butterfly_clip", codec="fp4")
    with pytest.raises(ValueError, match="unknown wire codec"):
        comp.codec_of(_spec("compressed:butterfly_clip", "fp4"))

    # CLI parse: codec binds to the wrapper, other params to the inner spec
    s = AggregatorSpec.parse("compressed:butterfly_clip:n_iters=20,codec=bf16")
    assert s.name == "compressed:butterfly_clip"
    assert s.get("n_iters") == 20 and comp.codec_of(s) == "bf16"
    assert AggregatorSpec.parse(s.canonical()) == s
    s2 = AggregatorSpec.parse("compressed:verified:mean")
    assert s2.name == "compressed:verified:mean"
    s3 = AggregatorSpec.parse("compressed:mean")  # auto-lift
    assert s3.name == "compressed:verified:mean"
    s4 = AggregatorSpec.parse(
        "compressed:verified:trimmed_mean:trim_ratio=0.3"
    )
    assert s4.get("trim_ratio") == 0.3


# ---------------------------------------------------------------------------
# Codec properties (hypothesis): determinism, bounds, digest equality
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
# a payload whose amax/127 is denormal: its scale used to flush to zero
@example(n_parts=1, n=2, d=2, expo=-36, zero_rows=False, seed=0)
@given(
    n_parts=st.integers(1, 6),
    n=st.integers(2, 12),
    d=st.integers(2, 700),
    expo=st.integers(-40, 10),
    zero_rows=st.booleans(),
    seed=st.integers(0, 99999),
)
def test_property_codec_roundtrip(n_parts, n, d, expo, zero_rows, seed):
    """Wire-codec invariants over ragged shapes, magnitudes down to f32
    denormal territory (1e-40), and all-zero partitions: quantize is
    deterministic, all-zero payloads are exact, int8 error is bounded by
    half a quantization step, bf16 is a pure dtype cast."""
    x = jax.random.normal(
        jax.random.key(seed), (n_parts, n, d), jnp.float32
    ) * jnp.float32(10.0 ** expo)
    if zero_rows:
        x = x.at[0].set(0.0)  # whole-partition zeros (padding looks like this)

    for codec in comp.CODECS:
        q, scales = comp.quantize(x, codec)
        q2, scales2 = comp.quantize(x, codec)  # determinism — bitwise
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(scales), np.asarray(scales2))
        rt = np.asarray(comp.roundtrip(x, codec))
        xs = np.asarray(x)
        if zero_rows:
            assert not rt[0].any()  # all-zero payloads round-trip exactly
        if codec == "bf16":
            np.testing.assert_array_equal(
                rt, np.asarray(xs.astype(jnp.bfloat16), np.float32)
            )
        else:
            assert q.dtype == jnp.int8
            sc = np.asarray(scales)[..., None]
            amax = np.abs(xs).max(axis=-1, keepdims=True)
            # half a quantization step, plus slack for denormal flushing
            # (a flushed scale leaves at most |x| <= amax of error)
            atol = 0.5 * sc + amax * 1e-5 + 1e-37
            assert (np.abs(xs - rt) <= atol).all()


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(2, 12),
    d=st.integers(2, 500),
    expo=st.integers(-6, 6),
    seed=st.integers(0, 99999),
)
def test_property_wire_digest_equality(n, d, expo, seed):
    """The exact-verification contract: digests recomputed from the wire
    values by ANY party equal the owner's bit-for-bit. compressed
    spec_tables == inner spec_tables over the same wire parts (one code
    path — the dispatch only strips the wrapper), and compressed_aggregate
    returns exactly the wire_grads projection as its parts."""
    g = jax.random.normal(jax.random.key(seed), (n, d), jnp.float32)
    g = g * jnp.float32(10.0 ** expo)
    part = bf.pad_to_parts(d, n) // n
    z = bf.get_random_directions(seed + 1, n, part)
    for codec in comp.CODECS:
        spec = _spec("compressed:verified:mean", codec)
        agg, parts, s, norms, _ = verif.spec_aggregate(spec, g, z=z)
        # parts ARE the wire projection (peer payload boundaries fixed by
        # the butterfly layout)
        want_parts = bf.split_parts(comp.wire_grads(g, codec, n), n)
        np.testing.assert_array_equal(
            np.asarray(parts), np.asarray(want_parts)
        )
        # a validator's standalone recompute over those wire parts:
        # identical digests, whether or not it strips the wrapper itself
        s_c, n_c = verif.spec_tables(spec, parts, agg, z)
        s_i, n_i = verif.spec_tables(comp.inner_spec(spec), parts, agg, z)
        np.testing.assert_array_equal(np.asarray(s_c), np.asarray(s_i))
        np.testing.assert_array_equal(np.asarray(n_c), np.asarray(n_i))
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(s_c), atol=1e-5 * 10.0 ** expo
        )
        np.testing.assert_allclose(
            np.asarray(norms), np.asarray(n_c), atol=1e-5 * 10.0 ** expo
        )


@settings(max_examples=6, deadline=None)
@given(
    n_parts=st.integers(1, 5),
    n=st.integers(2, 10),
    d=st.integers(2, 600),
    codec=st.sampled_from(comp.CODECS),
    banned=st.booleans(),
    seed=st.integers(0, 99999),
)
def test_property_fused_dequant_kernels_match_ref(
    n_parts, n, d, codec, banned, seed
):
    """The fused dequantize+clip+digest and dequantize+mean+digest kernels
    == the kernels/ref.py oracles per partition, over ragged shapes and
    both wire dtypes (wire-dtype zero padding must be exact)."""
    from repro.kernels.ops import (
        butterfly_clip_fused_dequant_op,
        mean_digest_fused_dequant_op,
    )
    from repro.kernels.ref import (
        centered_clip_fused_dequant_ref,
        mean_digest_fused_dequant_ref,
    )

    x = jax.random.normal(jax.random.key(seed), (n_parts, n, d)) * 2
    qs, scales = comp.quantize(x, codec)
    z = jax.random.normal(jax.random.key(seed + 2), (n_parts, d))
    z = z / jnp.maximum(jnp.linalg.norm(z, axis=1, keepdims=True), 1e-30)
    w = jnp.where(jnp.arange(n) % 3 == 0, 0.0, 1.0) if banned else None

    n_iters = 5
    agg, s, norms = butterfly_clip_fused_dequant_op(
        qs, scales, 1.0, z, w, n_iters=n_iters
    )
    taus = jnp.full((n_iters,), 1.0, jnp.float32)
    for j in range(n_parts):
        v_r, s_r, n_r = centered_clip_fused_dequant_ref(
            qs[j], scales[j], taus, z[j], weights=w
        )
        np.testing.assert_allclose(np.asarray(agg[j]), np.asarray(v_r),
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s[:, j]), np.asarray(s_r),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(norms[:, j]), np.asarray(n_r),
                                   atol=1e-4, rtol=1e-4)

    agg, s, norms = mean_digest_fused_dequant_op(qs, scales, z, w)
    for j in range(n_parts):
        v_r, s_r, n_r = mean_digest_fused_dequant_ref(
            qs[j], scales[j], z[j], w
        )
        np.testing.assert_allclose(np.asarray(agg[j]), np.asarray(v_r),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s[:, j]), np.asarray(s_r),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(norms[:, j]), np.asarray(n_r),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# The adversarial attack x codec engine grid
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("codec", comp.CODECS)
@pytest.mark.parametrize("attack", sorted(ATTACKS))
def test_grid_bans_byzantine_and_scan_equals_stepwise(attack, codec):
    """Every compressed spec bans every Byzantine peer within BAN_WITHIN
    steps under every attack and codec, never bans an honest peer, and the
    stepwise and scanned engines agree exactly on bans/accusations."""
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])
    for spec in _grid_specs(codec):
        cfg = _cfg(spec, ATTACKS[attack])
        state_sw, step_outs = _run_stepwise(cfg, byz_mask, GRID_STEPS)
        state_sc, _, outs = _run_scan(cfg, byz_mask, GRID_STEPS)

        banned_sw = np.stack([np.asarray(o.banned_now) for o in step_outs])
        accuse_sw = np.stack([np.asarray(o.accuse_mat) for o in step_outs])
        np.testing.assert_array_equal(np.asarray(outs.banned_now), banned_sw)
        np.testing.assert_array_equal(np.asarray(outs.accuse_mat), accuse_sw)
        np.testing.assert_array_equal(
            np.asarray(state_sc.ban_step), np.asarray(state_sw.ban_step)
        )

        ban_step = np.asarray(state_sc.ban_step)
        label = f"{spec.canonical()} under {attack}"
        for i in BYZ:
            assert 0 <= ban_step[i] < BAN_WITHIN, (
                f"{label}: byz peer {i} ban_step={ban_step[i]}"
            )
        for i in range(N):
            if i not in BYZ:
                assert ban_step[i] == -1, f"{label}: honest peer {i} banned"


@pytest.mark.slow
@pytest.mark.parametrize("codec", comp.CODECS)
def test_honest_runs_have_zero_accusations(codec):
    """50 honest steps per codec, both engines: not a single peer or system
    accusation — rounding error can never slander anyone because every
    digest is computed over the dequantized wire values."""
    byz_mask = jnp.zeros((N,), jnp.float32)
    for spec in _grid_specs(codec):
        cfg = _cfg(spec, dict(kind="none"))
        state_sc, _, outs = _run_scan(cfg, byz_mask, HONEST_STEPS)
        label = spec.canonical()
        assert not np.asarray(outs.accuse_mat).any(), label
        assert not np.asarray(outs.sys_accuse).any(), label
        assert not np.asarray(outs.banned_now).any(), label
        assert not (np.asarray(state_sc.ban_step) >= 0).any(), label

        state_sw, step_outs = _run_stepwise(cfg, byz_mask, HONEST_STEPS)
        assert not any(np.asarray(o.accuse_mat).any() for o in step_outs)
        assert not any(np.asarray(o.sys_accuse).any() for o in step_outs)
        assert not (np.asarray(state_sw.ban_step) >= 0).any()


@pytest.mark.parametrize("codec", comp.CODECS)
def test_engine_bans_single_coordinate_cheater(codec):
    """A cheater perturbing ONE coordinate by more than the quantization
    step changes its wire row, so its recomputed digests mismatch and the
    audit bans it — under both codecs."""
    cheater = 2
    STEPS = 12  # >= worst-case audit latency at m_validators=3

    def grads_fn(params, t, flips):
        base = _grads_fn()
        G, H = base(params, t, flips)
        G = G.at[cheater, 5].add(0.5)  # far above the int8 step here
        return G, H

    for spec in _grid_specs(codec):
        cfg = _cfg(spec, dict(kind="none"))
        state, _, outs = _run_scan(
            cfg, jnp.zeros(N), STEPS, grads_fn=grads_fn
        )
        ban_step = np.asarray(state.ban_step)
        assert ban_step[cheater] >= 0, (
            f"{spec.canonical()}: single-coordinate cheater never banned"
        )
        assert all(
            ban_step[i] == -1 for i in range(N) if i != cheater
        ), spec.canonical()


def test_subquantization_cheat_is_invisible_and_harmless():
    """A perturbation BELOW the int8 quantization step never reaches the
    wire: the cheater's wire row is bit-identical to honest, so it is
    neither banned nor accused — correctly, because its perturbation also
    never entered the aggregate (identical g_hat). The wire representation
    IS the protocol-visible contribution."""
    cheater, coord, STEPS = 2, 5, 12
    base = _grads_fn()

    # freeze the gradient matrix so the wire-equality precondition holds
    # at EVERY step the validator rotation audits (with evolving params a
    # fixed delta can drift across a rounding boundary mid-run, which is a
    # different — banned — cheater)
    G0, _ = base(jnp.zeros(D, jnp.float32), 0, None)
    part = bf.pad_to_parts(D, N) // N
    row = bf.split_parts(G0, N)[cheater, coord // part]
    delta = float(np.abs(np.asarray(row)).max()) / 127.0 * 1e-3
    Gp = G0.at[cheater, coord].add(delta)

    # precondition: the perturbed gradient projects to the SAME wire bits
    np.testing.assert_array_equal(
        np.asarray(comp.wire_grads(Gp, "int8", N)),
        np.asarray(comp.wire_grads(G0, "int8", N)),
    )
    assert delta > 0

    def grads_fn(params, t, flips):
        return Gp, G0

    def grads_fn_h(params, t, flips):
        return G0, G0

    spec = _spec("compressed:butterfly_clip", "int8")
    cfg = _cfg(spec, dict(kind="none"))
    state, _, outs = _run_scan(cfg, jnp.zeros(N), STEPS, grads_fn=grads_fn)
    state_h, _, outs_h = _run_scan(
        cfg, jnp.zeros(N), STEPS, grads_fn=grads_fn_h
    )
    assert not np.asarray(outs.accuse_mat).any()
    assert not np.asarray(outs.sys_accuse).any()
    assert not (np.asarray(state.ban_step) >= 0).any()
    np.testing.assert_array_equal(
        np.asarray(outs.g_hat), np.asarray(outs_h.g_hat)
    )


# ---------------------------------------------------------------------------
# Wire-vs-raw commitment semantics
# ---------------------------------------------------------------------------
def test_compressed_aggregate_equals_inner_over_wire():
    """compressed_aggregate == the inner spec applied to the wire-projected
    gradients, for both the jnp and (interpret-mode) Pallas paths — the
    wrapper changes the wire representation, never the aggregation
    contract."""
    g = jax.random.normal(jax.random.key(11), (N, D + 3), jnp.float32) * 3
    n_parts = N
    part = bf.pad_to_parts(D + 3, n_parts) // n_parts
    z = bf.get_random_directions(5, n_parts, part)
    for codec in comp.CODECS:
        for inner_name in ("butterfly_clip", "verified:mean"):
            spec = comp.compressed(
                AggregatorSpec(inner_name).with_defaults(
                    tau=1.0, n_iters=30, adaptive_tol=None, warm_start=False
                ),
                codec=codec,
            )
            wire = comp.wire_grads(g, codec, n_parts)
            for use_pallas in (False, True):
                agg, parts, s, norms, _ = verif.spec_aggregate(
                    spec, g, z=z, use_pallas=use_pallas
                )
                agg_i, parts_i, s_i, n_i, _ = verif.spec_aggregate(
                    comp.inner_spec(spec), wire, z=z, use_pallas=False
                )
                np.testing.assert_array_equal(
                    np.asarray(parts), np.asarray(parts_i)
                )
                np.testing.assert_allclose(
                    np.asarray(agg), np.asarray(agg_i), atol=3e-5
                )
                np.testing.assert_allclose(
                    np.asarray(s), np.asarray(s_i), atol=1e-4
                )
                np.testing.assert_allclose(
                    np.asarray(norms), np.asarray(n_i), atol=1e-4
                )
