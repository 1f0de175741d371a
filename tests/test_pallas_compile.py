"""Native TPU compiles of the kernel family, with no TPU attached.

Interpret mode hides a whole class of kernel bugs: block shapes that break
the TPU (8, 128) tile minimum, scalar operands that must live in SMEM,
sublane-1 slices of batched outputs, more VMEM than a kernel may use. Every
test here compiles its kernel with the TPU compiler for one chip of a
described v5e:2x2 topology (``jax.experimental.topologies``) and checks
that the Mosaic kernel (``tpu_custom_call``) is in the compiled program.
Nothing runs, so results are checked by the interpret-mode tests
(tests/test_kernels.py); the spec-dispatch tests here also compare the
CPU-interpreted kernels with the jnp path.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers must all collect the same tests.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import centered_clip as _k


@pytest.fixture(scope="module")
def one_chip(described_chip):
    return SingleDeviceSharding(described_chip)


def _validate(one_chip, fn, *args):
    """Compile fn for the described chip; args are arrays or shapes.
    Returns the compiled program."""
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    return compiled


N, D, PARTS, ITERS = 8, 384, 4, 5
# albert-large (78,223,360 params) split over the engine's 4 simulated
# peers: each owner aggregates a (4 peers, d/4) stack
ALBERT_D, ENGINE_PEERS = 78_223_360, 4


def _stack(key, shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def test_centered_clip_lowers_natively(one_chip):
    taus = jnp.full((ITERS,), 1.0, jnp.float32)
    _validate(one_chip,
              lambda x: _k.centered_clip_pallas(x, taus, interpret=False),
              _stack(0, (N, D)))


def test_butterfly_clip_lowers_natively(one_chip):
    taus = jnp.full((ITERS,), 1.0, jnp.float32)
    _validate(one_chip,
              lambda p: _k.butterfly_clip_pallas(p, taus, interpret=False),
              _stack(1, (PARTS, N, D)))


@pytest.mark.parametrize("warm", [False, True])
def test_fused_butterfly_lowers_natively(one_chip, warm):
    taus = jnp.full((ITERS,), 1.0, jnp.float32)

    def fn(p, zz, *v0):
        return _k.butterfly_clip_fused_pallas(
            p, taus, zz, v0=v0[0] if warm else None, interpret=False
        )

    args = [_stack(2, (PARTS, N, D)), _stack(3, (PARTS, D))]
    if warm:
        args.append(_stack(4, (PARTS, D)))
    _validate(one_chip, fn, *args)


def test_fused_single_lowers_natively(one_chip):
    taus = jnp.full((ITERS,), 1.0, jnp.float32)
    _validate(
        one_chip,
        lambda x, zz: _k.centered_clip_fused_pallas(
            x, taus, zz, interpret=False
        ),
        _stack(5, (N, D)), _stack(6, (D,)),
    )


def test_verify_tables_batched_lowers_natively(one_chip):
    _validate(
        one_chip,
        lambda p, a, zz: _k.verify_tables_batched_pallas(
            p, a, zz, 1.0, interpret=False
        ),
        _stack(7, (PARTS, N, D)), _stack(8, (PARTS, D)), _stack(9, (PARTS, D)),
    )


def test_verify_tables_lowers_natively(one_chip):
    """The single (unbatched) verification kernel — its SMEM tau operand
    is exactly the (1, 1)-block class the Mosaic pass rejects."""
    _validate(
        one_chip,
        lambda x, vv, zz: _k.verify_tables_pallas(
            x, vv, zz, 1.0, interpret=False
        ),
        _stack(20, (N, D)), _stack(21, (D,)), _stack(22, (D,)),
    )


def test_digest_tables_batched_lowers_natively(one_chip):
    """The generalized verification wrapper's standalone digest pass
    (s_i = <z, x_i - v>, ||x_i - v||, no clip weight)."""
    _validate(
        one_chip,
        lambda p, a, zz: _k.digest_tables_batched_pallas(
            p, a, zz, interpret=False
        ),
        _stack(16, (PARTS, N, D)), _stack(17, (PARTS, D)),
        _stack(18, (PARTS, D)),
    )


@pytest.mark.parametrize("tau", [0.0, 1.0])
def test_digest_tables_rows_lowers_natively(one_chip, tau):
    """The sampled-digest audit kernel: one HBM pass over only the k
    sampled partitions, their ids scalar-prefetched into SMEM to steer the
    grid — the dynamic-index block maps are exactly what interpret mode
    cannot validate. tau=0 is the verified:* digest, tau>0 the
    ButterflyClip clip-weighted variant."""

    def fn(p, a, zz, r):
        return _k.digest_tables_rows_pallas(
            p, a, zz, r, tau, interpret=False
        )

    compiled = _validate(
        one_chip, fn, _stack(27, (PARTS, N, D)), _stack(28, (PARTS, D)),
        _stack(29, (PARTS, D)), jnp.asarray([3, 1], jnp.int32),
    )
    s_shape = compiled.out_info[0].shape
    assert s_shape == (2, N)


@pytest.mark.parametrize("weighted", [False, True])
def test_mean_digest_fused_lowers_natively(one_chip, weighted):
    """verified:mean's fused aggregation + digest-epilogue kernel (2 HBM
    passes, two grid phases sharing the aggregate output ref) must lower
    as a unit."""
    w = jnp.ones((N,)).at[1].set(0.0) if weighted else None

    def fn(p, zz):
        return _k.mean_digest_fused_pallas(p, zz, w, interpret=False)

    _validate(one_chip, fn, _stack(19, (PARTS, N, D)), _stack(20, (PARTS, D)))


@pytest.mark.parametrize("base", ["mean", "coordinate_median"])
def test_verified_wrapped_spec_dispatch_lowers(one_chip, base):
    """The verified:* route into the digest kernels: verified_aggregate on
    a wrapped spec with use_pallas=True reaches the fused mean-digest
    kernel (verified:mean) / the standalone digest kernel (the sort-based
    bases) through spec dispatch. It compiles natively for the chip, and
    on the CPU the interpreted kernels match the jnp path."""
    from repro.core.aggregators import AggregatorSpec, verified_aggregate

    n, d = N, N * D
    g = _stack(21, (n, d))
    z = _stack(22, (n, D))
    spec = AggregatorSpec(f"verified:{base}")

    def fn(gg, zz):
        agg, _parts, s, norms, iters = verified_aggregate(
            spec, gg, zz, use_pallas=True
        )
        return agg, s, norms, iters

    _validate(one_chip, fn, g, z)
    got = jax.jit(fn)(g, z)
    ref = verified_aggregate(spec, g, z, use_pallas=False)
    for a, b in zip(got[:3], (ref[0], ref[2], ref[3])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ops_interpret_on_cpu_and_refuse_other_platforms():
    """The ops layer picks the kernel mode from the platform it is lowered
    for: the Pallas interpreter for the CPU (no Mosaic kernel in the
    program, results equal to an explicit interpret=True call), the native
    kernel for a TPU, and an error for any other platform."""
    from repro.kernels import ops

    parts = _stack(30, (PARTS, N, D))
    z = _stack(31, (PARTS, D))
    fn = jax.jit(lambda p, zz: ops.butterfly_clip_fused_op(
        p, 1.0, zz, n_iters=3))
    assert "tpu_custom_call" not in fn.lower(parts, z).as_text()
    got = fn(parts, z)
    want = _k.butterfly_clip_fused_pallas(
        parts, jnp.full((3,), 1.0, jnp.float32), z, interpret=True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]).T)

    tpu = jax.export.export(fn, platforms=["tpu"])(parts, z)
    assert "tpu_custom_call" in tpu.mlir_module()
    with pytest.raises(NotImplementedError, match="platform"):
        jax.export.export(fn, platforms=["cuda"])(parts, z)


@pytest.mark.parametrize("codec", ["int8", "bf16"])
def test_fused_dequant_butterfly_lowers_natively(one_chip, codec):
    """The compressed:butterfly_clip hot path — fused dequantize + clip +
    digest over WIRE payloads (int8/bf16 blocks in HBM, f32 sidecar scales
    in a (1, n, 1) block), per wire dtype."""
    from repro.core import compression as comp

    qs, scales = comp.quantize(_stack(23, (PARTS, N, D)), codec)
    taus = jnp.full((ITERS,), 1.0, jnp.float32)

    def fn(q, s, zz):
        return _k.butterfly_clip_fused_dequant_pallas(
            q, s, taus, zz, interpret=False
        )

    _validate(one_chip, fn, qs, scales, _stack(24, (PARTS, D)))


@pytest.mark.parametrize("codec", ["int8", "bf16"])
def test_mean_digest_fused_dequant_lowers_natively(one_chip, codec):
    """compressed:verified:mean's fused dequantize + mean + digest kernel
    must lower as a unit for both wire dtypes (the int8 path exercises
    integer-block loads that interpret mode cannot validate)."""
    from repro.core import compression as comp

    qs, scales = comp.quantize(_stack(25, (PARTS, N, D)), codec)
    w = jnp.ones((N,)).at[2].set(0.0)

    def fn(q, s, zz):
        return _k.mean_digest_fused_dequant_pallas(q, s, zz, w,
                                                   interpret=False)

    _validate(one_chip, fn, qs, scales, _stack(26, (PARTS, D)))


@pytest.mark.parametrize("kernel", [
    "butterfly_clip_fused", "butterfly_clip_fused_dequant",
    "mean_digest_fused_dequant",
])
def test_kernel_compiles_at_albert_engine_width(one_chip, kernel):
    """The engine's partition stack at albert-large's full width: 4 owner
    partitions of 4 peers x d/4 coordinates (1.25 GB of f32, 313 MB of
    int8 wire words). The flagship fused kernel and the compressed int8
    kernels at the size the chip runs, not only at test sizes."""
    part = ALBERT_D // ENGINE_PEERS
    p, n = ENGINE_PEERS, ENGINE_PEERS
    f32 = jnp.float32
    z = jax.ShapeDtypeStruct((p, part), f32)
    taus = jnp.full((ITERS,), 1.0, f32)
    if kernel == "butterfly_clip_fused":
        args = (jax.ShapeDtypeStruct((p, n, part), f32), z)
        fn = lambda x, zz: _k.butterfly_clip_fused_pallas(
            x, taus, zz, interpret=False)
    else:
        q = jax.ShapeDtypeStruct((p, n, part), jnp.int8)
        scales = jax.ShapeDtypeStruct((p, n), f32)
        args = (q, scales, z)
        if kernel == "butterfly_clip_fused_dequant":
            fn = lambda x, s, zz: _k.butterfly_clip_fused_dequant_pallas(
                x, s, taus, zz, interpret=False)
        else:
            fn = lambda x, s, zz: _k.mean_digest_fused_dequant_pallas(
                x, s, zz, interpret=False)
    _validate(one_chip, fn, *args)


def test_adaptive_step_kernel_lowers_natively(one_chip):
    """The one-pass adaptive clip iteration (cw from carried sq, v update,
    incremental next-sq)."""
    parts = _stack(10, (PARTS, N, D))
    v = _stack(11, (PARTS, 1, D)) * 0.1
    sq = jnp.sum((parts - v) ** 2, axis=-1, keepdims=True)

    def fn(p, vv, ss):
        return _k.adaptive_clip_step_pallas(p, vv, ss, 1.0, interpret=False)

    _validate(one_chip, fn, parts, v, sq)


@pytest.mark.parametrize("adaptive", [False, True])
def test_spec_dispatched_fused_kernels_lower(one_chip, adaptive):
    """The AggregatorSpec route into the fused kernels: verified_aggregate
    (the engine's aggregation phase) with use_pallas=True reaches the
    fused / adaptive Mosaic kernels through spec dispatch. It compiles
    natively for the chip, and on the CPU the interpreted kernels match
    the jnp path."""
    from repro.core.aggregators import AggregatorSpec, verified_aggregate

    n, d = 8, 8 * D
    g = _stack(14, (n, d))
    z = _stack(15, (n, D))
    params = (("adaptive_tol", 1e-4 if adaptive else None),
              ("n_iters", ITERS), ("tau", 1.0), ("warm_start", False))
    spec = AggregatorSpec("butterfly_clip", params)

    def fn(gg, zz):
        agg, _parts, s, norms, iters = verified_aggregate(
            spec, gg, zz, use_pallas=True
        )
        return agg, s, norms, iters

    _validate(one_chip, fn, g, z)
    got = jax.jit(fn)(g, z)
    ref = verified_aggregate(spec, g, z, use_pallas=False)
    for a, b in zip(got[:3], (ref[0], ref[2], ref[3])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("warm", [False, True])
def test_adaptive_driver_lowers_natively(one_chip, warm):
    """The full early-exit driver: lax.while_loop wrapped around the Mosaic
    step kernel must lower as a unit (early-exit kernels cannot merge
    interpreter-only)."""
    v0 = _stack(13, (PARTS, D)) * 0.1 if warm else None

    def fn(p):
        return _k.butterfly_clip_adaptive_pallas(
            p, 1.0, 1e-4, ITERS, v0=v0, interpret=False
        )

    _validate(one_chip, fn, _stack(12, (PARTS, N, D)))


# ---------------------------------------------------------------------------
# Fused flash attention inside the model's gradient step
# ---------------------------------------------------------------------------
# the splash kernels' names: the forward (it saves its residuals in the
# primal pass and in the rematerialisation alike) and the fused backward
FLASH_KERNELS = ("splash_mha_fwd", "splash_mha_dkv")


def _one_chip_mesh(chip):
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array([[chip]]), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _grads_program(chip, arch, batch, seq):
    """The model's value-and-grad step compiled for the described chip, with
    a one-chip mesh of it registered (the step decides its attention path by
    the mesh's platform). Returns the compiled program's text."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import get_model
    from repro.sharding import set_mesh

    mesh = _one_chip_mesh(chip)
    set_mesh(mesh)
    rep = NamedSharding(mesh, P())
    m = get_model(arch, reduced=True)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        m.abstract_params())
    tokens = jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32, sharding=rep)
    step = jax.jit(jax.value_and_grad(m.loss_fn, has_aux=True))
    return step.lower(params, {"tokens": tokens}).compile().as_text()


def _kernel_lines(text):
    """The Mosaic calls of a compiled program's text, one instruction each
    (a kernel's JSON metadata may carry the instruction over lines)."""
    instructions = re.split(r"\n(?=\s*(?:ROOT )?%\S+ = )", text)
    return [i for i in instructions
            if 'custom_call_target="tpu_custom_call"' in i]


def test_albert_btard_step_runs_flash_attention_natively(described_chip):
    """albert-large's BTARD step at S = 512 (the benchmark cell's sequence,
    8 rows), its gradients inside the per-peer shard_map: the forward, its
    rematerialisation and the fused backward kernel are Mosaic calls under
    ``model.attention``, and no (B, H, S, S) tensor is left in the program."""
    from repro.configs import InputShape
    from repro.launch.steps import make_btard_train_step
    from repro.models import get_model
    from repro.optim import sgd

    B, H, S = 8, 16, 512
    step, abstract = make_btard_train_step(
        get_model("albert-large"), sgd(0.01), _one_chip_mesh(described_chip),
        InputShape("albert", S, B, "train"))
    text = step.lower(*abstract).compile().as_text()
    kernels = _kernel_lines(text)
    names = [l.split("=")[0].strip().lstrip("%") for l in kernels]
    for kernel in FLASH_KERNELS:
        assert any(n.startswith(kernel) for n in names), (kernel, names)
    assert sum(n.startswith("splash_mha_fwd") for n in names) == 2  # fwd, remat
    assert sum(n.startswith("splash_mha_dkv") for n in names) == 1
    assert all("model.attention" in l for l in kernels)
    assert f"[{B},{H},{S},{S}]" not in text


def test_mla_layer_keeps_the_jnp_attention(described_chip):
    """MLA's k head dim (nope + rope) differs from v's: its gradient step,
    compiled for the same chip, holds none of the attention kernels."""
    text = _grads_program(described_chip, "deepseek-v2-lite-16b", 2, 512)
    names = [l.split("=")[0].strip().lstrip("%") for l in _kernel_lines(text)]
    assert not [n for n in names if n.startswith(FLASH_KERNELS)], names
