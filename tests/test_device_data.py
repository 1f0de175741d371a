"""Device-resident data pipeline properties.

BTARD's verification model requires PUBLIC batches: any peer (or validator)
recomputing xi_i^t gets the same bits on ANY execution path. These tests pin
that down for the new in-scan generator:

* ``device_batch`` traced under jit/scan (with concrete OR traced step/peer)
  is bitwise identical to the host ``batch()`` for the same
  (global_seed, step, peer) — property-tested over the seed space including
  step*peer products far past int32 (the overflow hazard the ``peer_key``
  fold-in chain removes);
* the launch-layer device-resident scan step consumes exactly the host
  pipeline's batches (subprocess, 8 host devices): identical params out.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from tests._hypothesis_compat import given, settings, strategies as st

from repro.data import TokenPipeline, peer_key

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@settings(max_examples=20, deadline=None)
@given(
    global_seed=st.integers(0, 2**31 - 2),
    step=st.integers(0, 2**31 - 2),
    peer=st.integers(0, 2**20),
)
def test_device_batch_bitwise_matches_host(global_seed, step, peer):
    """jit(device_batch)(traced step, traced peer) == host batch(step, peer)
    bit for bit — including (step, peer) whose product overflows int32 (the
    legacy affine peer_seed hazard)."""
    pipe = TokenPipeline(257, 8, 2, global_seed=global_seed)
    host = pipe.batch(step, peer)
    dev = jax.jit(lambda s, p: pipe.device_batch(s, p))(
        jnp.int32(step), jnp.int32(peer)
    )
    np.testing.assert_array_equal(
        np.asarray(host["tokens"]), np.asarray(dev["tokens"])
    )


def test_device_batch_in_scan_matches_host():
    """The generator INSIDE a lax.scan body (the device-resident loop's data
    phase) emits the host pipeline's exact tokens step by step."""
    pipe = TokenPipeline(512, 12, 4)
    steps = jnp.arange(5, dtype=jnp.int32)

    @jax.jit
    def gen(steps):
        def body(c, s):
            return c, pipe.device_batch(s)["tokens"]

        return jax.lax.scan(body, 0, steps)[1]

    got = np.asarray(gen(steps))
    want = np.stack([np.asarray(pipe.batch(s)["tokens"]) for s in range(5)])
    np.testing.assert_array_equal(got, want)


def test_device_batch_extras_traceable_and_close():
    """Modality extras generate under jit with a process-stable stream tag
    (crc32, not the PYTHONHASHSEED-randomized hash()). Float extras agree
    with the host path to 1 ulp (XLA may fuse the normal*scale chain
    differently across programs); the verification-critical integer tokens
    are exact (above)."""
    pipe = TokenPipeline(64, 8, 2)
    ex = {"memory_raw": ((4, 6), jnp.float32)}
    host = pipe.batch(3, 1, extras=ex)
    dev = jax.jit(lambda s, p: pipe.device_batch(s, p, extras=ex))(
        jnp.int32(3), jnp.int32(1)
    )
    np.testing.assert_array_equal(
        np.asarray(host["tokens"]), np.asarray(dev["tokens"])
    )
    np.testing.assert_allclose(
        np.asarray(host["memory_raw"]), np.asarray(dev["memory_raw"]),
        rtol=1e-6, atol=1e-9,
    )


@settings(max_examples=6, deadline=None)
@given(
    vocab=st.sampled_from([2**16, 151_936, 262_144]),
    seq=st.sampled_from([31, 129, 2049]),
)
def test_device_batch_bitwise_at_zoo_shapes(vocab, seq):
    """The in-scan == host bitwise property at REAL vocab sizes (>= 2^16)
    and zoo sequence lengths: token ids stay int32, in [0, V), and the
    affine transition a*x+c never wraps int32 (audited in TokenPipeline)."""
    pipe = TokenPipeline(vocab, seq, 2, global_seed=3)
    host = pipe.batch(7, 5)
    dev = jax.jit(lambda s, p: pipe.device_batch(s, p))(jnp.int32(7), jnp.int32(5))
    tok = np.asarray(host["tokens"])
    assert tok.dtype == np.int32
    assert tok.min() >= 0 and tok.max() < vocab
    np.testing.assert_array_equal(tok, np.asarray(dev["tokens"]))


def test_affine_overflow_guard():
    """Parameterizations whose transition a*x+c would wrap int32 must raise
    loudly at construction — pre-fix they silently wrapped (tokens stayed in
    [0, V) so nothing downstream noticed the process was not the documented
    bigram). Defaults stay exact for every zoo vocab."""
    import pytest

    with pytest.raises(ValueError, match="overflows int32"):
        TokenPipeline(2**30, 8, 2, a=2**20 + 5)
    # defaults at the largest zoo-ish vocab are fine
    TokenPipeline(262_144, 8, 2)
    # a, c are canonicalized mod V
    p = TokenPipeline(257, 8, 2, a=257 + 5, c=257 + 7)
    assert (p.a, p.c) == (5, 7)


def test_peer_key_injective_and_overflow_free():
    """Distinct (step, peer) -> distinct keys, including coordinates whose
    affine combination wraps int32."""
    pairs = [(0, 0), (0, 1), (1, 0), (2**30, 10**6), (10**6, 2**30),
             (2**31 - 2, 2**20)]
    keys = {
        tuple(np.asarray(jax.random.key_data(peer_key(0, s, p))).tolist())
        for s, p in pairs
    }
    assert len(keys) == len(pairs)


def test_launch_scan_device_data_equals_host_batches():
    """make_btard_scan_train_step(pipeline=...) == the host-batch mode on
    identical inputs: same params out (the in-scan data phase is invisible
    to training), adaptive+warm variant runs checksum-clean."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_btard_scan_train_step
    from repro.models import get_model
    from repro.optim import sgd
    from repro.configs.base import InputShape
    from repro.data import TokenPipeline

    mesh = make_mesh((4, 2), ('data', 'model'))
    m = get_model('qwen3-1.7b', reduced=True)
    shape = InputShape('t', 16, 8, 'train')
    opt = sgd(0.05)
    params = m.init_params(jax.random.key(0)); st = opt.init(params)
    pipe = TokenPipeline(m.cfg.vocab_size, 16, 8)
    N = 3
    byz = jnp.zeros((4,), jnp.float32); w = jnp.ones((4,), jnp.float32)
    v0 = jax.tree.map(jnp.zeros_like, params)
    steps = jnp.arange(N, dtype=jnp.int32); seeds = steps * 7919 + 13

    host_fn, _ = make_btard_scan_train_step(
        m, opt, mesh, shape, n_scan_steps=N, tau=2.0, clip_iters=5)
    dev_fn, _ = make_btard_scan_train_step(
        m, opt, mesh, shape, n_scan_steps=N, tau=2.0, clip_iters=5,
        pipeline=pipe)
    batches = jax.tree.map(lambda *ls: jnp.stack(ls),
                           *[pipe.batch(s) for s in range(N)])
    p1, _, met1, _, _ = host_fn(params, st, batches, steps, seeds, byz, w, v0)
    p2, _, met2, _, _ = dev_fn(params, st, steps, seeds, byz, w, v0)
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
    mx = max(jax.tree.leaves(diffs))
    assert mx == 0.0, f'device-data params diverged from host-batch: {mx}'

    # adaptive early exit + warm start on the device-resident path
    ad_fn, _ = make_btard_scan_train_step(
        m, opt, mesh, shape, n_scan_steps=N, tau=2.0, clip_iters=20,
        warm_start=True, adaptive_tol=1e-4, pipeline=pipe)
    _, _, met3, _, _ = ad_fn(params, st, steps, seeds, byz, w, v0)
    assert float(met3['checksum_max'].max()) < 1e-3
    assert met3['clip_iters_max'].shape == (N,)
    print('DEVICE DATA OK', mx)
    """
    r = subprocess.run(
        [sys.executable, "-W", "ignore", "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert r.returncode == 0, r.stdout[-3000:] + "\n---\n" + r.stderr[-3000:]
    assert "DEVICE DATA OK" in r.stdout
