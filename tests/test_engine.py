"""ProtocolState engine properties (core.engine).

The acceptance bar for the scan engine: a jitted ``lax.scan`` over N >= 8
protocol steps must produce IDENTICAL ban sets / accusations and
f32-tolerance-identical aggregates to N legacy ``BTARDProtocol.step`` calls,
across attack types — plus the warm-start CenteredClip property (same fixed
point, fewer iterations).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import attacks as attacks_mod
from repro.core.centered_clip import centered_clip, centered_clip_to_tol
from repro.core.protocol import AttackConfig, BTARDProtocol

N, D, STEPS = 8, 48, 12
BYZ = (5, 6, 7)


def _make_grads(n=N, d=D):
    """Pure per-step gradient matrices for a public-seed linear problem —
    the same function drives the host wrapper AND the scanned engine."""
    w_true = jax.random.normal(jax.random.key(9), (d,))

    def peer_grad(peer, step, params, flipped):
        k = jax.random.key((peer * 7919 + step) % (2**31 - 1))
        X = jax.random.normal(k, (4, d))
        y = X @ w_true
        y = jnp.where(flipped, -y, y)
        return 2 * X.T @ (X @ params - y) / 4

    def grads_fn(params, t, flips):
        idx = jnp.arange(n)
        G = jax.vmap(lambda i, f: peer_grad(i, t, params, f))(idx, flips)
        H = jax.vmap(lambda i: peer_grad(i, t, params, False))(idx)
        return G, H

    return peer_grad, grads_fn


def _run_wrapper(attack, steps=STEPS, **kw):
    peer_grad, grads_fn = _make_grads()
    jitted = jax.jit(grads_fn)

    def host_grad(i, t, params, flipped=False):
        flips = jnp.zeros((N,), bool).at[i].set(bool(flipped))
        G, H = jitted(jnp.asarray(params, jnp.float32), t, flips)
        return np.asarray(G[i])

    proto = BTARDProtocol(
        n_peers=N, d=D, grad_fn=host_grad, byzantine=set(BYZ),
        attack=attack, tau=1.0, m_validators=2, seed=0, **kw,
    )
    params = np.zeros(D, np.float32)
    g_hats, banned_per_step, accusations = [], [], []
    for t in range(steps):
        g, info = proto.step(params, t)
        params = params - 0.05 * g
        g_hats.append(g)
        banned_per_step.append(sorted(p for p, _ in info.banned_now))
        accusations.append(
            sorted((a, b) for a, b, _, _ in info.accusations if a is not None)
        )
    return proto, np.stack(g_hats), banned_per_step, accusations


def _run_scan(attack, steps=STEPS, **kw):
    _, grads_fn = _make_grads()
    cfg = eng.config_from_attack(
        N, D, attack, tau=1.0, clip_iters=60, m_validators=2, **kw
    )
    state = eng.init_state(cfg, seed=0)
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])

    def update(p, g, t):
        return p - 0.05 * g

    runner = jax.jit(
        lambda s, b, p: eng.scan_protocol(
            cfg, s, b, p, grads_fn, steps, update
        )
    )
    state, params, outs = runner(state, byz_mask, jnp.zeros(D, jnp.float32))
    return state, outs


@pytest.mark.parametrize(
    "kind", ["sign_flip", "ipm_06", "alie", "random_direction", "label_flip"]
)
def test_scan_bitmatches_legacy_stepwise(kind):
    """lax.scan over 12 steps == 12 wrapper step() calls: same bans (per
    step), same accusation pairs, aggregates within f32 tolerance."""
    attack = AttackConfig(kind=kind, start_step=2, lam=100.0)
    proto, g_wrap, bans_wrap, acc_wrap = _run_wrapper(attack)
    state, outs = _run_scan(attack)

    banned_scan = {
        int(i) for i in np.nonzero(np.asarray(state.ban_step) >= 0)[0]
    }
    assert banned_scan == proto.banned, (kind, banned_scan, proto.banned)
    assert banned_scan, f"{kind}: attack never triggered a ban in {STEPS} steps"
    assert banned_scan <= set(BYZ)

    banned_now = np.asarray(outs.banned_now)
    for t in range(STEPS):
        assert sorted(np.nonzero(banned_now[t])[0].tolist()) == bans_wrap[t], t
    acc_scan = np.asarray(outs.accuse_mat)
    for t in range(STEPS):
        pairs = sorted((int(v), int(u)) for v, u in zip(*np.nonzero(acc_scan[t])))
        assert pairs == acc_wrap[t], (kind, t)

    g_scan = np.asarray(outs.g_hat)
    scale = np.abs(g_wrap).max(axis=1, keepdims=True) + 1.0
    np.testing.assert_allclose(g_scan / scale, g_wrap / scale, atol=2e-5)


def test_scan_delayed_gradient_ring_buffer():
    """The delay ring buffer in ProtocolState reproduces the wrapper's
    host-side history exactly (delayed rows = honest grads from t - D)."""
    attack = AttackConfig(kind="delayed_gradient", start_step=3, delay=3)
    proto, g_wrap, bans_wrap, _ = _run_wrapper(attack)
    state, outs = _run_scan(attack)
    banned_scan = {
        int(i) for i in np.nonzero(np.asarray(state.ban_step) >= 0)[0]
    }
    assert banned_scan == proto.banned
    scale = np.abs(g_wrap).max(axis=1, keepdims=True) + 1.0
    np.testing.assert_allclose(
        np.asarray(outs.g_hat) / scale, g_wrap / scale, atol=2e-5
    )


@pytest.mark.parametrize("kind", ["sign_flip", "none", "delayed_gradient"])
def test_gradient_history_only_for_delayed_attack(kind):
    """At albert-large's width (d = 78,223,360, 4 peers) only a
    delayed_gradient run carries the (delay, n, d) history, and only it can
    trip the carry-size guard; every other attack carries an empty buffer
    and starts. Shapes only (eval_shape): nothing full-size is allocated."""
    cfg = eng.config_from_attack(
        4, 78_223_360, AttackConfig(kind=kind, start_step=0, delay=5))
    if kind == "delayed_gradient":
        with pytest.raises(ValueError, match="ring buffer"):
            jax.eval_shape(lambda: eng.init_state(cfg))
        return
    state = jax.eval_shape(lambda: eng.init_state(cfg))
    assert cfg.delay_depth == 0
    assert state.delay_buf.shape == (0, 4, 78_223_360)


def test_scan_no_attack_no_bans_and_stable():
    state, outs = _run_scan(AttackConfig(kind="none"))
    assert not np.any(np.asarray(state.ban_step) >= 0)
    assert np.all(np.isfinite(np.asarray(outs.g_hat)))
    assert np.all(np.asarray(outs.n_active) == N)


def test_attack_registry_matches_named_fns():
    """apply_attack(index) == the named attack on identical inputs (the
    lax.switch registry is a pure re-indexing of the host dict)."""
    G = jax.random.normal(jax.random.key(0), (N, D))
    byz = jnp.zeros((N,), bool).at[jnp.asarray(BYZ)].set(True)
    key = jax.random.key(7)
    for kind in attacks_mod.ATTACK_NAMES:
        if kind == "delayed_gradient":
            delayed = jax.random.normal(jax.random.key(1), (N, D))
        else:
            delayed = None
        got = attacks_mod.apply_attack(
            attacks_mod.attack_index(kind), G, byz,
            key=key, lam=50.0, delayed=delayed,
        )
        want = attacks_mod.GRADIENT_ATTACKS[kind](
            G, byz, key=key, lam=50.0,
            **({"delayed": delayed} if delayed is not None else {}),
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=kind)


def test_attack_registry_traced_index_dispatch():
    """The attack index stays traced through jit — one compiled program
    serves every attack (the composability the registry exists for)."""
    G = jax.random.normal(jax.random.key(0), (N, D))
    byz = jnp.zeros((N,), bool).at[jnp.asarray(BYZ)].set(True)

    @jax.jit
    def run(idx):
        return attacks_mod.apply_attack(idx, G, byz, key=jax.random.key(3))

    flip = run(jnp.int32(attacks_mod.attack_index("sign_flip")))
    none = run(jnp.int32(attacks_mod.attack_index("none")))
    np.testing.assert_allclose(np.asarray(none), np.asarray(G), atol=0)
    assert np.abs(np.asarray(flip)[list(BYZ)]).max() > np.abs(np.asarray(G)).max()


# ---------------------------------------------------------------------------
# Warm-start CenteredClip
# ---------------------------------------------------------------------------
def _drifting_problem(d=512, n=16, b=3):
    mu = jax.random.normal(jax.random.key(1), (d,))
    mu = mu / jnp.linalg.norm(mu) * 20.0
    honest = mu + jax.random.normal(jax.random.key(2), (n - b, d))
    attack = jnp.broadcast_to(-10.0 * mu, (b, d))
    xs0 = jnp.concatenate([honest, attack])
    drift = 0.05 * jax.random.normal(jax.random.key(3), (n, d))
    return xs0, xs0 + drift


def test_warm_start_same_fixed_point_fewer_iters():
    """v0 = last step's aggregate reaches the SAME fixed point in strictly
    fewer iterations (the fixed point is unique for tau > 0; warm starting
    only changes the trajectory). This is the Fig. 9 argument for cutting
    clip_iters below the default 60."""
    xs0, xs1 = _drifting_problem()
    tau = 5.0
    v_prev, _ = centered_clip_to_tol(xs0, tau, eps=1e-7, max_iters=3000)
    v_cold, it_cold = centered_clip_to_tol(xs1, tau, eps=1e-6, max_iters=3000)
    v_warm, it_warm = centered_clip_to_tol(
        xs1, tau, eps=1e-6, max_iters=3000, v0=v_prev
    )
    np.testing.assert_allclose(
        np.asarray(v_warm), np.asarray(v_cold), atol=1e-3
    )
    assert int(it_warm) < int(it_cold), (int(it_warm), int(it_cold))


def test_warm_start_fixed_budget_beats_cold():
    """At a fixed small iteration budget, warm start lands closer to the
    converged fixed point than a cold start — the basis for running the
    protocol at clip_iters well below 60."""
    xs0, xs1 = _drifting_problem()
    tau = 5.0
    v_prev, _ = centered_clip_to_tol(xs0, tau, eps=1e-7, max_iters=3000)
    ref, _ = centered_clip_to_tol(xs1, tau, eps=1e-8, max_iters=5000)
    budget = 8
    err_cold = jnp.linalg.norm(centered_clip(xs1, tau, n_iters=budget) - ref)
    err_warm = jnp.linalg.norm(
        centered_clip(xs1, tau, n_iters=budget, v0=v_prev) - ref
    )
    assert float(err_warm) < 0.1 * float(err_cold), (
        float(err_warm), float(err_cold),
    )


def test_engine_warm_start_cuts_iteration_budget():
    """Slow-drift regime (fixed per-peer datasets, small lr — the realistic
    large-model setting the ROADMAP's warm-start item targets): at a fixed
    15-iteration budget, warm-started steps track the converged (400-iter)
    aggregates several times closer than cold-started ones."""
    w_true = jax.random.normal(jax.random.key(9), (D,))

    def peer_grad(peer, params):
        k = jax.random.key(peer * 7919 + 17)
        X = jax.random.normal(k, (4, D))
        return 2 * X.T @ (X @ params - X @ w_true) / 4

    def grads_fn(params, t, flips):
        G = jax.vmap(lambda i: peer_grad(i, params))(jnp.arange(N))
        return G, G

    byz_mask = jnp.zeros((N,), jnp.float32)

    def run(iters, warm):
        cfg = eng.config_from_attack(
            N, D, AttackConfig(kind="none"), tau=1.0, clip_iters=iters,
            m_validators=0, warm_start=warm,
        )
        st = eng.init_state(cfg, seed=0)
        runner = jax.jit(
            lambda s, b, p: eng.scan_protocol(
                cfg, s, b, p, grads_fn, STEPS, lambda p, g, t: p - 0.02 * g
            )
        )
        _, _, outs = runner(st, byz_mask, jnp.zeros(D, jnp.float32))
        return np.asarray(outs.g_hat)

    ref = run(400, False)
    # step 0 is cold for both by definition; judge the warm steps
    err_cold = np.abs(run(15, False) - ref).max(axis=1)[1:].mean()
    err_warm = np.abs(run(15, True) - ref).max(axis=1)[1:].mean()
    assert err_warm < 0.3 * err_cold, (err_warm, err_cold)


def test_engine_pallas_path_matches_jnp():
    """One jitted engine step with use_pallas=True equals the jnp path."""
    attack = AttackConfig(kind="sign_flip", start_step=0, lam=10.0)
    _, grads_fn = _make_grads()
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])
    outs = {}
    for pallas in (False, True):
        cfg = eng.config_from_attack(
            N, D, attack, tau=1.0, clip_iters=10, m_validators=2,
            use_pallas=pallas,
        )
        state = eng.init_state(cfg, seed=0)
        G, H = grads_fn(jnp.zeros(D), jnp.asarray(0), jnp.zeros((N,), bool))
        _, out = eng.jit_protocol_step(cfg)(state, byz_mask, G, H)
        outs[pallas] = np.asarray(out.g_hat)
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-4)


# ---------------------------------------------------------------------------
# Adaptive CenteredClip budget (engine-side early exit)
# ---------------------------------------------------------------------------
def test_engine_adaptive_tol_zero_reproduces_fixed_exactly():
    """adaptive_tol=0.0 runs the full cap through the shared update rule:
    aggregates BITWISE equal, bans/accusations identical — the fixed path is
    a special case of the adaptive one."""
    attack = AttackConfig(kind="sign_flip", start_step=2, lam=100.0)
    _, outs_fixed = _run_scan(attack)
    _, outs_adapt = _run_scan(attack, adaptive_tol=0.0)
    np.testing.assert_array_equal(
        np.asarray(outs_adapt.g_hat), np.asarray(outs_fixed.g_hat)
    )
    np.testing.assert_array_equal(
        np.asarray(outs_adapt.banned_now), np.asarray(outs_fixed.banned_now)
    )
    np.testing.assert_array_equal(
        np.asarray(outs_adapt.accuse_mat), np.asarray(outs_fixed.accuse_mat)
    )
    assert np.all(np.asarray(outs_adapt.clip_iters_used) == 60)


@pytest.mark.parametrize("kind", ["sign_flip", "ipm_06", "label_flip"])
def test_engine_adaptive_matches_legacy_wrapper(kind):
    """The acceptance property: a scanned adaptive+warm run produces the
    SAME bans/accusations as the host-pipeline fixed-iter wrapper and
    f32-tolerance aggregates — in the regime where the clip CONVERGES
    within the cap (tau comparable to the gradient scale; the early exit
    then lands on the unique fixed point the fixed budget also reaches).
    With the cap binding instead (unconverged), only the cold path is
    bitwise comparable — covered by the tol=0 test above."""
    tau = 25.0
    attack = AttackConfig(kind=kind, start_step=2, lam=100.0)

    peer_grad, grads_fn = _make_grads()
    jitted = jax.jit(grads_fn)

    def host_grad(i, t, params, flipped=False):
        flips = jnp.zeros((N,), bool).at[i].set(bool(flipped))
        G, _ = jitted(jnp.asarray(params, jnp.float32), t, flips)
        return np.asarray(G[i])

    proto = BTARDProtocol(
        n_peers=N, d=D, grad_fn=host_grad, byzantine=set(BYZ),
        attack=attack, tau=tau, m_validators=2, seed=0,
    )
    params = np.zeros(D, np.float32)
    g_hats, bans_wrap, acc_wrap = [], [], []
    for t in range(STEPS):
        g, info = proto.step(params, t)
        params = params - 0.05 * g
        g_hats.append(g)
        bans_wrap.append(sorted(p for p, _ in info.banned_now))
        acc_wrap.append(
            sorted((a, b) for a, b, _, _ in info.accusations if a is not None)
        )
    g_wrap = np.stack(g_hats)

    cfg = eng.config_from_attack(
        N, D, attack, tau=tau, clip_iters=60, m_validators=2,
        adaptive_tol=1e-6, warm_start=True,
    )
    state = eng.init_state(cfg, seed=0)
    byz_mask = jnp.asarray([1.0 if i in BYZ else 0.0 for i in range(N)])
    runner = jax.jit(
        lambda s, b, p: eng.scan_protocol(
            cfg, s, b, p, grads_fn, STEPS, lambda p, g, t: p - 0.05 * g
        )
    )
    state, _, outs = runner(state, byz_mask, jnp.zeros(D, jnp.float32))

    banned_scan = {
        int(i) for i in np.nonzero(np.asarray(state.ban_step) >= 0)[0]
    }
    assert banned_scan == proto.banned, (kind, banned_scan, proto.banned)
    assert banned_scan, f"{kind}: attack never triggered a ban"
    banned_now = np.asarray(outs.banned_now)
    for t in range(STEPS):
        assert sorted(np.nonzero(banned_now[t])[0].tolist()) == bans_wrap[t], t
    acc_scan = np.asarray(outs.accuse_mat)
    for t in range(STEPS):
        pairs = sorted(
            (int(v), int(u)) for v, u in zip(*np.nonzero(acc_scan[t]))
        )
        assert pairs == acc_wrap[t], (kind, t)
    used = np.asarray(outs.clip_iters_used)
    assert used.max() < 60, used  # the early exit actually triggered
    scale = np.abs(g_wrap).max(axis=1, keepdims=True) + 1.0
    np.testing.assert_allclose(
        np.asarray(outs.g_hat) / scale, g_wrap / scale, atol=2e-4
    )


def test_engine_adaptive_reports_budget_and_early_exits():
    """clip_iters_used surfaces the real per-step budget; in the no-attack
    slow-drift regime with warm start it early-exits far below the cap."""
    w_true = jax.random.normal(jax.random.key(9), (D,))

    def peer_grad(peer, params):
        k = jax.random.key(peer * 7919 + 17)
        X = jax.random.normal(k, (4, D))
        return 2 * X.T @ (X @ params - X @ w_true) / 4

    def grads_fn(params, t, flips):
        G = jax.vmap(lambda i: peer_grad(i, params))(jnp.arange(N))
        return G, G

    cfg = eng.config_from_attack(
        N, D, AttackConfig(kind="none"), tau=100.0, clip_iters=60,
        m_validators=0, warm_start=True, adaptive_tol=1e-5,
    )
    st = eng.init_state(cfg, seed=0)
    runner = jax.jit(
        lambda s, b, p: eng.scan_protocol(
            cfg, s, b, p, grads_fn, STEPS, lambda p, g, t: p - 0.02 * g
        )
    )
    _, _, outs = runner(st, jnp.zeros((N,), jnp.float32),
                        jnp.zeros(D, jnp.float32))
    used = np.asarray(outs.clip_iters_used)
    assert used.shape == (STEPS,)
    assert used.max() <= 60
    # warm-started steps after the first need only a handful of iterations
    assert used[1:].mean() < 15, used
