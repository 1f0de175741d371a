"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels.ops import butterfly_clip_op, centered_clip_op, verify_tables_op
from repro.kernels.ref import centered_clip_ref, verify_tables_ref

SHAPES = [(4, 128), (8, 257), (16, 1000), (32, 2048), (7, 999), (3, 130)]
DTYPES = ["float32", "bfloat16"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_centered_clip_kernel_sweep(shape, dtype):
    n, d = shape
    xs = (jax.random.normal(jax.random.key(n * d), (n, d)) * 2 + 0.5).astype(dtype)
    tau = 1.0
    taus = jnp.full((12,), tau, jnp.float32)
    got = centered_clip_op(xs, tau, n_iters=12)
    want = centered_clip_ref(xs, taus)
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_verify_tables_kernel_sweep(shape, dtype):
    n, d = shape
    xs = (jax.random.normal(jax.random.key(d), (n, d)) * 3).astype(dtype)
    v = jax.random.normal(jax.random.key(1), (d,)).astype(dtype)
    z = jax.random.normal(jax.random.key(2), (d,))
    z = (z / jnp.linalg.norm(z)).astype(dtype)
    s_k, n_k = verify_tables_op(xs, v, z, 0.7)
    s_r, n_r = verify_tables_ref(xs, v, z, 0.7)
    tol = 1e-4 if dtype == "float32" else 1e-1
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(n_k), np.asarray(n_r), atol=tol, rtol=tol)


def test_kernel_weights_mask():
    xs = jax.random.normal(jax.random.key(0), (8, 300))
    w = jnp.array([1, 0, 1, 0, 1, 1, 1, 0], jnp.float32)
    got = centered_clip_op(xs, 2.0, w, n_iters=10)
    want = centered_clip_ref(xs, jnp.full((10,), 2.0), w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_kernel_tau_inf_mean():
    xs = jax.random.normal(jax.random.key(0), (6, 500))
    got = centered_clip_op(xs, np.inf, n_iters=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xs.mean(0)), atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 24),
    d=st.integers(2, 1500),
    tau=st.floats(0.2, 50.0),
    iters=st.integers(1, 20),
    seed=st.integers(0, 99999),
)
def test_property_kernel_matches_ref(n, d, tau, iters, seed):
    xs = jax.random.normal(jax.random.key(seed), (n, d)) * 2
    got = centered_clip_op(xs, tau, n_iters=iters)
    want = centered_clip_ref(xs, jnp.full((iters,), tau, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("shape", [(8, 8, 300), (4, 16, 1025), (3, 6, 128)])
def test_butterfly_batched_kernel_matches_per_partition_ref(shape):
    """The all-partition ButterflyClip kernel == per-partition oracle."""
    n_parts, n, d = shape
    parts = jax.random.normal(jax.random.key(n_parts * d), (n_parts, n, d)) * 2
    w = jnp.where(jnp.arange(n) % 4 == 0, 0.0, 1.0)
    got = butterfly_clip_op(parts, 1.0, w, n_iters=10)
    taus = jnp.full((10,), 1.0, jnp.float32)
    want = jnp.stack([centered_clip_ref(parts[j], taus, w) for j in range(n_parts)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 16),
    d=st.integers(2, 2000),
    blk=st.sampled_from([128, 256, 512, 1024]),
    seed=st.integers(0, 99999),
)
def test_property_block_size_invariance(n, d, blk, seed):
    """Kernel output must not depend on the VMEM block geometry."""
    xs = jax.random.normal(jax.random.key(seed), (n, d))
    a = centered_clip_op(xs, 1.0, n_iters=8, block=blk)
    b = centered_clip_op(xs, 1.0, n_iters=8, block=2048)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _multi_pass_cases():
    """The kernels that revisit each lane block in several grid passes."""
    from repro.core import compression as comp
    from repro.kernels import centered_clip as k

    xs = jax.random.normal(jax.random.key(40), (2, 4, 384)) * 3
    z = jax.random.normal(jax.random.key(41), (2, 384))
    v0 = jax.random.normal(jax.random.key(42), (2, 384))
    taus = jnp.full((3,), 1.0, jnp.float32)
    qs, sc = comp.quantize(xs, "int8")
    kw = dict(block=128)  # 3 lane blocks: every pass revisits each block
    return {
        "centered_clip": lambda m: k.centered_clip_pallas(
            xs[0], taus, v0=v0[0], interpret=m, **kw),
        "butterfly_clip": lambda m: k.butterfly_clip_pallas(
            xs, taus, interpret=m, **kw),
        "centered_clip_fused": lambda m: k.centered_clip_fused_pallas(
            xs[0], taus, z[0], interpret=m, **kw),
        "butterfly_clip_fused": lambda m: k.butterfly_clip_fused_pallas(
            xs, taus, z, v0=v0, interpret=m, **kw),
        "butterfly_clip_fused_dequant": lambda m: (
            k.butterfly_clip_fused_dequant_pallas(
                qs, sc, taus, z, interpret=m, **kw)),
        "mean_digest_fused": lambda m: k.mean_digest_fused_pallas(
            xs, z, interpret=m, **kw),
        "mean_digest_fused_dequant": lambda m: (
            k.mean_digest_fused_dequant_pallas(qs, sc, z, interpret=m, **kw)),
    }


@pytest.mark.parametrize("kernel", [
    "centered_clip", "butterfly_clip", "centered_clip_fused",
    "butterfly_clip_fused", "butterfly_clip_fused_dequant",
    "mean_digest_fused", "mean_digest_fused_dequant",
])
def test_multi_pass_kernel_under_tpu_pipeline_semantics(kernel):
    """The TPU pipeline writes an output block back to HBM and never reads
    it in again, so a kernel that carried its iterate in an output block
    read stale VMEM on the chip while the plain interpreter (which reads the
    whole output array) hid it. The TPU interpreter models the pipeline and
    refuses a revisited output block; under it every multi-pass kernel must
    equal the plain interpreter bitwise."""
    from jax.experimental.pallas import tpu as pltpu

    run = _multi_pass_cases()[kernel]
    want = run(True)
    got = run(pltpu.InterpretParams())
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
