"""AggregatorSpec API: registry contract, the attack x aggregator grid
through the engine (stepwise == scanned), the weighted trimmed-mean / Krum
fixes, and the deprecation shims onto equivalent specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregators as agg_mod
from repro.core import butterfly as bf
from repro.core import engine as eng
from repro.core.aggregators import (
    AggregatorSpec,
    krum,
    registered_aggregators,
    resolve_spec,
    trimmed_mean,
    verified_aggregate,
)
from repro.core.protocol import AttackConfig

N, D, STEPS = 8, 48, 8
BYZ = (5, 6, 7)

SPECS = [
    AggregatorSpec("butterfly_clip"),
    AggregatorSpec("mean"),
    AggregatorSpec("coordinate_median"),
    AggregatorSpec("trimmed_mean", (("trim_ratio", 0.25),)),
    AggregatorSpec("geometric_median"),
    AggregatorSpec("krum", (("n_byzantine", 3),)),
    AggregatorSpec("centered_clip"),
]


# ---------------------------------------------------------------------------
# Spec / registry contract
# ---------------------------------------------------------------------------
def test_registry_covers_all_paper_baselines():
    names = set(registered_aggregators())
    assert {"mean", "coordinate_median", "trimmed_mean", "geometric_median",
            "krum", "centered_clip", "butterfly_clip"} <= names
    # the verifiable set: the flagship plus exactly one verified:<base>
    # wrapper per coordinatewise baseline (core.verification), each also
    # available with quantized wire payloads (core.compression)
    verifiable = {
        "butterfly_clip", "verified:mean", "verified:trimmed_mean",
        "verified:coordinate_median",
    }
    assert {n for n in names if AggregatorSpec(n).verifiable} == (
        verifiable | {f"compressed:{n}" for n in verifiable}
    )


def test_spec_parse_and_canonical_roundtrip():
    spec = AggregatorSpec.parse("krum:n_byzantine=3")
    assert spec.name == "krum" and spec.get("n_byzantine") == 3
    spec2 = AggregatorSpec.parse(spec.canonical())
    assert spec2 == spec
    multi = AggregatorSpec.parse(
        "butterfly_clip:warm_start=true,adaptive_tol=1e-4"
    )
    assert multi.get("warm_start") is True
    assert multi.get("adaptive_tol") == pytest.approx(1e-4)


def test_spec_rejects_unknown_names_and_params():
    with pytest.raises(ValueError, match="unknown aggregator"):
        AggregatorSpec.parse("medoid")
    with pytest.raises(ValueError, match="no param"):
        AggregatorSpec.parse("mean:tau=1.0")
    with pytest.raises(ValueError, match="no param"):
        AggregatorSpec("krum", (("trim_ratio", 0.1),)).param_dict()


def test_with_defaults_fills_only_declared_unset_params():
    spec = AggregatorSpec("butterfly_clip", (("tau", 3.0),))
    out = spec.with_defaults(tau=1.0, n_iters=25, trim_ratio=0.4)
    assert out.get("tau") == 3.0  # explicit param wins
    assert out.get("n_iters") == 25  # filled
    assert "trim_ratio" not in dict(out.params)  # undeclared: ignored
    # mean declares nothing — engine knobs fall away silently
    assert AggregatorSpec("mean").with_defaults(tau=1.0).params == ()


def test_uniform_signature_across_registry():
    xs = jax.random.normal(jax.random.key(0), (N, D))
    w = jnp.ones((N,)).at[-1].set(0.0)
    for spec in SPECS:
        v, info = agg_mod.aggregate(
            spec, xs, weights=w, v0=jnp.zeros((D,)), key=jax.random.key(1)
        )
        assert v.shape == (D,), spec.name
        assert np.isfinite(np.asarray(v)).all(), spec.name
        assert np.asarray(info.iters).dtype == np.int32, spec.name


# ---------------------------------------------------------------------------
# Satellite fixes: weighted trimmed mean / Krum distance masking
# ---------------------------------------------------------------------------
def test_trimmed_mean_banned_rows_never_enter_trim_window():
    """3 banned rows at +1000 with trim_ratio=0.2: the old code trimmed
    k=int(10*0.2)=2 rows per end over ALL rows, so one banned row survived
    into the mean. The fix trims over the active block only."""
    n, d = 10, 6
    honest = jax.random.normal(jax.random.key(0), (n - 3, d))
    xs = jnp.concatenate([honest, 1000.0 * jnp.ones((3, d))])
    w = jnp.concatenate([jnp.ones((n - 3,)), jnp.zeros((3,))])
    v = trimmed_mean(xs, trim_ratio=0.2, weights=w)
    # reference: numpy trimmed mean over the 7 active rows, k = floor(7*.2)=1
    ref = np.sort(np.asarray(honest), axis=0)[1:-1].mean(0)
    np.testing.assert_allclose(np.asarray(v), ref, rtol=1e-5, atol=1e-5)


def test_trimmed_mean_unweighted_matches_legacy():
    xs = jax.random.normal(jax.random.key(1), (9, 5))
    got = trimmed_mean(xs, trim_ratio=0.25)
    k = int(9 * 0.25)
    ref = np.sort(np.asarray(xs), axis=0)[k : 9 - k].mean(0)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-6)
    # all-active weights == no weights (same window, same mean)
    got_w = trimmed_mean(xs, trim_ratio=0.25, weights=jnp.ones((9,)))
    np.testing.assert_allclose(np.asarray(got_w), ref, rtol=1e-5, atol=1e-6)


def test_krum_banned_rows_are_not_neighbours():
    """An active attacker surrounded by BANNED clones must not win: the old
    code masked only the final scores, so the clones still served as
    zero-distance nearest neighbours and deflated the attacker's score."""
    n, d = 8, 4
    honest = 0.1 * jax.random.normal(jax.random.key(2), (4, d))
    attacker = 5.0 * jnp.ones((1, d))
    clones = attacker + 1e-3 * jax.random.normal(jax.random.key(3), (3, d))
    xs = jnp.concatenate([honest, attacker, clones])
    w = jnp.concatenate([jnp.ones((5,)), jnp.zeros((3,))])  # clones banned
    v = krum(xs, n_byzantine=3, weights=w)
    assert float(jnp.linalg.norm(v)) < 1.0, np.asarray(v)
    # sanity: without masking the pairwise matrix the attacker would win
    # (its k=3 nearest neighbours are its three zero-distance banned clones)
    d2 = jnp.sum((xs[:, None, :] - xs[None, :, :]) ** 2, -1) + jnp.eye(n) * 1e30
    k = max(1, n - 3 - 2)
    scores = jnp.sort(d2, 1)[:, :k].sum(1)
    old_pick = int(jnp.argmin(jnp.where(w > 0, scores, jnp.inf)))
    assert old_pick == 4  # the attacker — the bug this fix removes


def test_krum_banned_rows_never_selected():
    xs = jnp.concatenate([
        0.1 * jax.random.normal(jax.random.key(4), (6, 3)),
        100.0 * jnp.ones((2, 3)),
    ])
    w = jnp.ones((8,)).at[6:].set(0.0)
    v = krum(xs, n_byzantine=2, weights=w)
    assert float(jnp.linalg.norm(v)) < 2.0


# ---------------------------------------------------------------------------
# The attack x aggregator grid: stepwise == scanned, degradation contract
# ---------------------------------------------------------------------------
def _grads_fn():
    w_true = jax.random.normal(jax.random.key(9), (D,))

    def peer_grad(peer, step, params):
        k = jax.random.key((peer * 7919 + step) % (2**31 - 1))
        X = jax.random.normal(k, (4, D))
        return 2 * X.T @ (X @ params - X @ w_true) / 4

    def grads_fn(params, t, flips):
        G = jax.vmap(lambda i: peer_grad(i, t, params))(jnp.arange(N))
        return G, G

    return grads_fn


@pytest.mark.parametrize("attack", ["sign_flip", "alie", "ipm_06"])
@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_grid_scan_equals_stepwise(spec, attack):
    """Every registered aggregator, under every collusion attack, in BOTH
    engine entry points: N jit_protocol_step calls == one scan_protocol —
    identical bans/accusations, f32-tolerance aggregates. Non-verifiable
    specs must produce ZERO accusations and bans on both paths."""
    cfg = eng.config_from_attack(
        N, D, AttackConfig(kind=attack, start_step=2, lam=100.0),
        tau=1.0, clip_iters=20, m_validators=2, aggregator=spec,
    )
    grads_fn = _grads_fn()
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])
    params = jnp.zeros(D, jnp.float32)

    # stepwise: N jitted single steps
    step_fn = eng.jit_protocol_step(cfg)
    state = eng.init_state(cfg, seed=0)
    flips = jnp.zeros((N,), bool)
    step_outs = []
    for _ in range(STEPS):
        G, H = grads_fn(params, state.step, flips)
        state, out = step_fn(state, byz_mask, G, H)
        step_outs.append(out)

    # scanned: one lax.scan (params fixed — no update_fn — matching above)
    state_s, _, outs = jax.jit(
        lambda s, b, p: eng.scan_protocol(cfg, s, b, p, grads_fn, STEPS)
    )(eng.init_state(cfg, seed=0), byz_mask, params)

    banned_step = np.stack([np.asarray(o.banned_now) for o in step_outs])
    accuse_step = np.stack([np.asarray(o.accuse_mat) for o in step_outs])
    np.testing.assert_array_equal(np.asarray(outs.banned_now), banned_step)
    np.testing.assert_array_equal(np.asarray(outs.accuse_mat), accuse_step)
    g_step = np.stack([np.asarray(o.g_hat) for o in step_outs])
    scale = np.abs(g_step).max(axis=1, keepdims=True) + 1.0
    np.testing.assert_allclose(
        np.asarray(outs.g_hat) / scale, g_step / scale, atol=2e-5
    )

    if not spec.verifiable:
        assert not accuse_step.any(), spec.name
        assert not np.asarray(outs.sys_accuse).any(), spec.name
        assert not banned_step.any(), spec.name
        assert not (np.asarray(state_s.ban_step) >= 0).any(), spec.name
    elif attack == "sign_flip":
        # the flagship's detection arm still fires where PR 2 proved it does
        assert banned_step.any(), "butterfly_clip stopped banning sign_flip"


def test_grid_non_verifiable_robust_specs_survive_sign_flip():
    """The Fig. 3 story in miniature: under amplified sign flip the robust
    baselines keep a bounded aggregate while plain mean is dragged to the
    attack scale (they just never BAN anyone — detection is butterfly-only)."""
    grads_fn = _grads_fn()
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])
    norms = {}
    for name in ("mean", "krum", "geometric_median", "centered_clip"):
        spec = AggregatorSpec(name)
        if name == "krum":
            spec = spec.override(n_byzantine=len(BYZ))
        cfg = eng.config_from_attack(
            N, D, AttackConfig(kind="sign_flip", start_step=0, lam=1000.0),
            tau=1.0, clip_iters=20, m_validators=2, aggregator=spec,
        )
        _, _, outs = jax.jit(
            lambda s, b, p, cfg=cfg: eng.scan_protocol(
                cfg, s, b, p, grads_fn, 4
            )
        )(eng.init_state(cfg, seed=0), byz_mask, jnp.zeros(D, jnp.float32))
        norms[name] = float(np.linalg.norm(np.asarray(outs.g_hat[-1])))
    assert norms["mean"] > 50 * max(
        norms["krum"], norms["geometric_median"], norms["centered_clip"]
    ), norms


# ---------------------------------------------------------------------------
# Deprecation shims resolve to equivalent specs
# ---------------------------------------------------------------------------
def test_butterfly_clip_verified_shim_warns_and_matches_spec_path():
    g = jax.random.normal(jax.random.key(5), (N, 40))
    z = bf.get_random_directions(7, N, 5)
    with pytest.warns(DeprecationWarning, match="AggregatorSpec"):
        a1, p1, s1, n1 = bf.butterfly_clip_verified(g, 1.0, z, n_iters=7)
    spec = AggregatorSpec(
        "butterfly_clip", (("n_iters", 7), ("tau", 1.0)),
    ).with_defaults(adaptive_tol=None, warm_start=False)
    a2, p2, s2, n2, iters = verified_aggregate(spec, g, z)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    assert int(iters) == 7


def test_butterfly_stage_shim_warns_and_matches_aggregation_stage():
    from repro.launch import steps as lsteps

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("peers",))
    g = jax.random.normal(jax.random.key(6), (24,))
    w = jnp.ones((1,))

    def run(fn):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(lsteps.P("peers"), lsteps.P()),
            out_specs=(lsteps.P(), {
                "checksum": lsteps.P("peers"), "votes": lsteps.P("peers"),
                "clip_iters": lsteps.P("peers"),
                "s_table": lsteps.P(None, None),
                "norm_table": lsteps.P(None, None),
                "audit_target": lsteps.P("peers"),
                "audit_grad_mismatch": lsteps.P("peers"),
                "audit_agg_mismatch": lsteps.P("peers"),
            }),
            axis_names={"peers"}, check_vma=False,
        )(g[None, :], w)

    with pytest.warns(DeprecationWarning, match="aggregation_stage"):
        full_old, verif_old = run(
            lambda gv, ww: lsteps.butterfly_stage(
                gv[0], "peers", 1, 2.0, 6, ww, 13
            )
        )
    spec = AggregatorSpec("butterfly_clip", (("n_iters", 6), ("tau", 2.0)))
    full_new, verif_new = run(
        lambda gv, ww: lsteps.aggregation_stage(
            gv[0], "peers", 1, spec.with_defaults(
                adaptive_tol=None, warm_start=False
            ), ww, 13,
        )
    )
    np.testing.assert_array_equal(np.asarray(full_old), np.asarray(full_new))
    np.testing.assert_array_equal(
        np.asarray(verif_old["s_table"]), np.asarray(verif_new["s_table"])
    )


def test_krum_launch_keeps_full_vector_semantics():
    """Krum is not coordinate-decomposable: on a model-sharded mesh the
    launch stage must join the shards before scoring so ONE peer wins
    globally — per-shard application can elect different winners per shard
    and emit a composite gradient no peer proposed (this scenario is
    constructed so it would). Subprocess: fake devices need XLA_FLAGS
    before jax import."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch import steps as lsteps
from repro.launch.mesh import make_mesh
from repro.core.aggregators import AggregatorSpec, krum

mesh = make_mesh((4, 2), ("peers", "model"))
n, d = 4, 8
# rows ~ [0, .1, .2, .3]; peer 0 is an outlier in shard A only, peer 3 in
# shard B only -> per-shard krum picks DIFFERENT winners (1 then 0) while
# full-vector krum picks peer 1 everywhere
G = np.tile(np.asarray([0.0, 0.1, 0.2, 0.3])[:, None], (1, d)).astype(np.float32)
G[0, : d // 2] = 50.0
G[3, d // 2 :] = 100.0
G = jnp.asarray(G)
w = jnp.ones((n,))
spec = AggregatorSpec("krum", (("n_byzantine", 1),))

def f(gv, ww):
    out, _ = lsteps.aggregation_stage(
        gv.reshape(-1), ("peers",), n, spec, ww, 3, gather_axes=("model",)
    )
    return out

agg = jax.shard_map(
    f, mesh=mesh, in_specs=(P("peers", "model"), P()), out_specs=P("model"),
    axis_names={"peers", "model"}, check_vma=False,
)(G, w)
want = krum(G, n_byzantine=1, weights=w)
np.testing.assert_array_equal(np.asarray(agg), np.asarray(want))
print("KRUM_JOIN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + "\n---\n" + r.stderr[-2000:]
    assert "KRUM_JOIN_OK" in r.stdout


def test_cli_clip_flag_shims_resolve_to_spec():
    from repro.launch.train import resolve_cli_aggregator

    with pytest.warns(DeprecationWarning, match="--warm-start-clip"):
        spec = resolve_cli_aggregator("butterfly_clip", True, None, 0)
    assert spec.get("warm_start") is True
    with pytest.warns(DeprecationWarning, match="--adaptive-clip"):
        spec = resolve_cli_aggregator("butterfly_clip", False, 1e-4, 0)
    assert spec.get("adaptive_tol") == pytest.approx(1e-4)
    # explicit spec params beat legacy knobs downstream (with_defaults)
    spec = resolve_cli_aggregator(
        "butterfly_clip:adaptive_tol=1e-2", False, None, 0
    ).with_defaults(tau=1.0, n_iters=60, adaptive_tol=None, warm_start=False)
    assert spec.get("adaptive_tol") == pytest.approx(1e-2)
    # krum inherits n_byzantine from the --byzantine list
    assert resolve_cli_aggregator("krum", False, None, 5).get(
        "n_byzantine"
    ) == 5
    # the flags are ignored (with a warning) for specs that can't use them
    with pytest.warns(UserWarning, match="ignored"):
        spec = resolve_cli_aggregator("mean", True, None, 0)
    assert spec.params == ()


def test_engine_default_spec_matches_legacy_knobs():
    """EngineConfig.aggregator=None resolves the legacy tau/clip_iters/
    warm_start/adaptive_tol knobs into the flagship spec — the pre-spec
    configuration surface keeps meaning exactly what it meant."""
    cfg = eng.EngineConfig(n=N, d=D, tau=2.5, clip_iters=11, warm_start=True,
                           adaptive_tol=1e-3)
    spec = cfg.agg_spec()
    assert spec.name == "butterfly_clip" and spec.verifiable
    assert spec.get("tau") == 2.5
    assert spec.get("n_iters") == 11
    assert spec.get("warm_start") is True
    assert spec.get("adaptive_tol") == pytest.approx(1e-3)
    assert resolve_spec(None).name == "butterfly_clip"
