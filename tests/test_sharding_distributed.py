"""Distributed-step tests: run in a SUBPROCESS with 8 host devices so the
session's device count stays 1 for every other test."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-W", "ignore", "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
    )
    assert r.returncode == 0, r.stdout[-3000:] + "\n---\n" + r.stderr[-3000:]
    return r.stdout


def test_btard_step_equals_baseline_when_honest():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_baseline_train_step, make_btard_train_step
        from repro.models import get_model
        from repro.optim import sgd
        from repro.configs.base import InputShape

        mesh = make_mesh((4, 2), ('data', 'model'))
        m = get_model('qwen3-1.7b', reduced=True)
        shape = InputShape('t', 64, 8, 'train')
        opt = sgd(0.05)
        params = m.init_params(jax.random.key(0)); st = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 65), 0, m.cfg.vocab_size)
        bl, _ = make_baseline_train_step(m, opt, mesh, shape)
        bt, _ = make_btard_train_step(m, opt, mesh, shape, tau=1e9, clip_iters=3)
        p1, _, _ = bl(params, st, {'tokens': toks}, jnp.int32(0))
        byz = jnp.zeros((4,), jnp.float32); w = jnp.ones((4,), jnp.float32)
        p2, _, met, _ = bt(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(7), byz, w)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        m = max(jax.tree.leaves(diffs))
        assert m < 5e-3, m
        print('EQUIV OK', m)
        """
    )
    assert "EQUIV OK" in out


def test_device_attack_detected_and_clipped():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_btard_train_step
        from repro.models import get_model
        from repro.optim import sgd
        from repro.configs.base import InputShape

        mesh = make_mesh((4, 2), ('data', 'model'))
        m = get_model('qwen3-1.7b', reduced=True)
        shape = InputShape('t', 64, 8, 'train')
        opt = sgd(0.05)
        params = m.init_params(jax.random.key(0)); st = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 65), 0, m.cfg.vocab_size)
        bt, _ = make_btard_train_step(m, opt, mesh, shape, tau=0.05, clip_iters=30,
                                      attack='sign_flip', delta_max=0.2)
        byz = jnp.asarray([0., 0., 0., 1.]); w = jnp.ones((4,), jnp.float32)
        p2, _, met, verif = bt(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(7), byz, w)
        # honest-majority aggregate stays bounded despite a -100x attacker
        import numpy as np
        norms = np.asarray(verif['norm_table'])
        assert np.isfinite(norms).all()
        # the attacked peer's residual norm dominates every partition
        assert (norms[:, 3] >= norms[:, :3].max(1) - 1e-6).mean() > 0.9
        # and banning it via weights restores the checksum
        w2 = jnp.asarray([1., 1., 1., 0.])
        p3, _, met3, verif3 = bt(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(7), byz, w2)
        assert float(met3['checksum_max']) < 1e-3
        print('ATTACK OK')
        """
    )
    assert "ATTACK OK" in out


def test_multi_pod_mesh_axes():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_btard_train_step
        from repro.models import get_model
        from repro.optim import sgd
        from repro.configs.base import InputShape

        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        m = get_model('qwen3-1.7b', reduced=True)
        shape = InputShape('t', 64, 8, 'train')
        opt = sgd(0.05)
        bt, bargs = make_btard_train_step(m, opt, mesh, shape, tau=2.0, clip_iters=5)
        bt.lower(*bargs).compile()
        params = m.init_params(jax.random.key(0)); st = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 65), 0, m.cfg.vocab_size)
        byz = jnp.zeros((4,), jnp.float32); w = jnp.ones((4,), jnp.float32)
        p, _, met, _ = bt(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(3), byz, w)
        assert float(met['checksum_max']) < 1e-3
        print('MULTIPOD OK', float(met['loss']))
        """
    )
    assert "MULTIPOD OK" in out


def test_scan_step_equals_stepwise_and_warm_start_runs():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_btard_scan_train_step, make_btard_train_step
        from repro.models import get_model
        from repro.optim import sgd
        from repro.configs.base import InputShape

        mesh = make_mesh((4, 2), ('data', 'model'))
        m = get_model('qwen3-1.7b', reduced=True)
        shape = InputShape('t', 64, 8, 'train')
        opt = sgd(0.05)
        params = m.init_params(jax.random.key(0)); st = opt.init(params)
        N = 3
        toks = [jax.random.randint(jax.random.key(i), (8, 65), 0, m.cfg.vocab_size)
                for i in range(N)]
        byz = jnp.zeros((4,), jnp.float32); w = jnp.ones((4,), jnp.float32)

        one, _ = make_btard_train_step(m, opt, mesh, shape, tau=2.0, clip_iters=5)
        p1, s1 = params, st
        for i in range(N):
            p1, s1, met, _ = one(p1, s1, {'tokens': toks[i]}, jnp.int32(i),
                                 jnp.int32(i * 7919 + 13), byz, w)

        scan, _ = make_btard_scan_train_step(m, opt, mesh, shape, n_scan_steps=N,
                                             tau=2.0, clip_iters=5)
        batches = {'tokens': jnp.stack(toks)}
        steps = jnp.arange(N, dtype=jnp.int32)
        seeds = steps * 7919 + 13
        v0 = jax.tree.map(jnp.zeros_like, params)
        p2, s2, mets, verifs, v_last = scan(params, st, batches, steps, seeds, byz, w, v0)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        mx = max(jax.tree.leaves(diffs))
        assert mx < 5e-3, mx
        assert mets['loss'].shape == (N,)

        # warm start: runs end-to-end and stays checksum-clean when honest
        warm, _ = make_btard_scan_train_step(m, opt, mesh, shape, n_scan_steps=N,
                                             tau=2.0, clip_iters=5, warm_start=True)
        p3, s3, mets3, _, _ = warm(params, st, batches, steps, seeds, byz, w, v0)
        assert float(mets3['checksum_max'].max()) < 1e-3
        print('SCAN EQUIV OK', mx)
        """
    )
    assert "SCAN EQUIV OK" in out


def test_pallas_kernel_inside_distributed_step():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_btard_train_step
        from repro.models import get_model
        from repro.optim import sgd
        from repro.configs.base import InputShape

        mesh = make_mesh((4, 2), ('data', 'model'))
        m = get_model('qwen3-1.7b', reduced=True)
        shape = InputShape('t', 64, 8, 'train')
        opt = sgd(0.05)
        params = m.init_params(jax.random.key(0)); st = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 65), 0, m.cfg.vocab_size)
        byz = jnp.zeros((4,), jnp.float32); w = jnp.ones((4,), jnp.float32)
        ref, _ = make_btard_train_step(m, opt, mesh, shape, tau=2.0, clip_iters=6)
        ker, _ = make_btard_train_step(m, opt, mesh, shape, tau=2.0, clip_iters=6, use_pallas=True)
        p1, _, _, _ = ref(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(7), byz, w)
        p2, _, _, _ = ker(params, st, {'tokens': toks}, jnp.int32(0), jnp.int32(7), byz, w)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        mx = max(jax.tree.leaves(diffs))
        assert mx < 5e-3, mx
        print('PALLAS DIST OK', mx)
        """
    )
    assert "PALLAS DIST OK" in out
