"""The fused flash-attention path of ``models/attention.py``.

Numerics: the kernel runs on the CPU in the Pallas TPU interpreter
(``pltpu.force_tpu_interpret_mode``) and is compared in bfloat16 with the
dense jnp attention, forward and the gradients of q, k and v. Dispatch: which
shapes, platforms and meshes take the kernel. The native TPU lowering of the
whole path is in tests/test_pallas_compile.py.
"""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.configs.base import LayerSpec
from repro.models import attention as attn
from repro.sharding import set_mesh
from repro.sharding.specs import set_manual_axes

# bfloat16 has an 8-bit significand (eps 2**-8): the forward differs from
# the dense path by at most a few of its ulps at the outputs' scale, and a
# gradient by about 2.5 eps in norm, from where each path rounds to bf16
FWD_ATOL = 2.0**-6
GRAD_RTOL = 1e-2


@pytest.fixture
def kernel_allowed(monkeypatch):
    """Take the kernel path on the CPU, where it runs interpreted."""
    monkeypatch.setattr(attn, "_kernel_allowed", lambda: True)


def _causal_dense(q, k, v):
    """The jnp path on heads-major (B, H, S, D) inputs."""
    S = q.shape[2]
    pos = jnp.arange(S)
    msk = (pos[None, :] <= pos[:, None])[None, None, None]
    out = attn._dense_attention(q.swapaxes(1, 2)[:, :, :, None],
                                k.swapaxes(1, 2), v.swapaxes(1, 2), msk)
    return out[:, :, :, 0].swapaxes(1, 2)


def _expand(t, H):
    """gqa_apply's KV-head expand, on the heads-major layout."""
    return jnp.repeat(t, H // t.shape[1], axis=1)


# (B, H, n_kv, S, head_dim)
CASES = [
    (2, 4, 4, 256, 64),
    (2, 4, 4, 256, 128),
    (2, 4, 4, 384, 64),   # T = S = 384: three 128-row tiles
    (2, 4, 2, 256, 64),   # GQA: two query heads per KV head
]


@pytest.mark.parametrize("B,H,Kv,S,D", CASES)
def test_flash_kernel_matches_dense_attention(kernel_allowed, B, H, Kv, S, D):
    ks = jax.random.split(jax.random.key(S + D + Kv), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Kv, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Kv, S, D), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, H, S, D), jnp.float32)
    blocks = attn.flash_blocks(S, S, D, D)
    assert blocks is not None

    def kernel(q, k, v):
        return attn.flash_causal_attention(q, _expand(k, H), _expand(v, H),
                                           blocks)

    def dense(q, k, v):
        return _causal_dense(q, _expand(k, H), _expand(v, H))

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * ct)

    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(kernel)(q, k, v)
        grads = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    ref = jax.jit(dense)(q, k, v)
    ref_grads = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)

    assert out.dtype == ref.dtype == jnp.bfloat16
    diff = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
    assert float(jnp.max(diff)) <= FWD_ATOL
    for name, g, r in zip("qkv", grads, ref_grads):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        rel = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert rel <= GRAD_RTOL, (name, rel)


def _gqa_setup(arch, **over):
    cfg = dataclasses.replace(get_config(arch), **over)
    spec = LayerSpec("attn_full", "dense")
    p = attn.gqa_init(jax.random.key(0), cfg, spec)
    p = {n: a + 0.02 * jax.random.normal(jax.random.key(i), a.shape, a.dtype)
         if n.endswith("bias") else a for i, (n, a) in enumerate(p.items())}
    return cfg, spec, p


@pytest.mark.parametrize("arch,over", [
    ("albert-large", dict(d_model=256, n_heads=4, n_kv_heads=4)),
    # rope, q/k norm and grouped KV heads
    ("qwen3-1.7b", dict(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64)),
    # biases and GLM's half rope
    ("chatglm3-6b", dict(d_model=256, n_heads=4, n_kv_heads=2, head_dim=64)),
])
def test_gqa_apply_kernel_layout_matches_jnp_path(monkeypatch, arch, over):
    """The kernel path's own projections (straight into (B, H, S, D)), rope,
    norms and output projection give the jnp path's block output and
    parameter gradients. A leaf's gradient error is taken over the larger of
    its own and the median leaf's reference norm, as the benchmark's
    ``grad_gap`` takes it: the key bias's exact gradient is all but zero
    (softmax does not see a shift shared by every key), so its norm alone
    measures rounding, not the kernel."""
    cfg, spec, p = _gqa_setup(arch, **over)
    B, S = 2, 256
    x = jax.random.normal(jax.random.key(7), (B, S, cfg.d_model), jnp.bfloat16)
    pos = jnp.arange(S)

    def loss(p, x):
        y, _ = attn.gqa_apply(p, cfg, spec, x, pos=pos)
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    (_, ref), ref_g = step(p, x)
    monkeypatch.setattr(attn, "_kernel_allowed", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        (_, out), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(p, x)
        assert "pallas_call" in str(jax.make_jaxpr(loss)(p, x))

    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    diff = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
    assert float(jnp.max(diff)) <= 2 * FWD_ATOL * max(scale, 1.0)
    ref_leaves = [r.astype(jnp.float32) for r in jax.tree.leaves(ref_g)]
    median = float(np.median([jnp.linalg.norm(r) for r in ref_leaves]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            ref_leaves):
        err = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        rel = err / max(float(jnp.linalg.norm(b)), median)
        assert rel <= GRAD_RTOL, (jax.tree_util.keystr(path), rel)


def _mesh(platform, **axes):
    """A stand-in for a registered mesh: what ``_kernel_allowed`` reads."""
    dev = SimpleNamespace(platform=platform)
    n = int(np.prod(list(axes.values())))
    return SimpleNamespace(devices=np.array([dev] * n).reshape(
        tuple(axes.values())), shape=dict(axes), axis_names=tuple(axes))


ALBERT = (512, 512, 64, 64)  # the benchmark cell: S, T, D, Dv


@pytest.mark.parametrize("case,shape,window,mesh,manual,kernel", [
    ("albert cell, one chip", ALBERT, 0, dict(data=1, model=1), (), True),
    ("4 peers, peers manual", ALBERT, 0, dict(data=4, model=1), ("data",), True),
    ("window covers T", ALBERT, 512, dict(data=1, model=1), (), True),
    ("head dim 128", (1024, 1024, 128, 128), 0, dict(data=1, model=1), (), True),
    ("MLA: k 192, v 128", (512, 512, 192, 128), 0, dict(data=1, model=1), (), False),
    ("window < T", ALBERT, 256, dict(data=1, model=1), (), False),
    ("S not a multiple of 128", (500, 500, 64, 64), 0, dict(data=1, model=1), (), False),
    ("T > 8192", (16384, 16384, 64, 64), 0, dict(data=1, model=1), (), False),
    ("head dim 192", (512, 512, 192, 192), 0, dict(data=1, model=1), (), False),
    ("model axis 2", ALBERT, 0, dict(data=2, model=2), ("data",), False),
    ("auto data axis 4", ALBERT, 0, dict(data=4, model=1), (), False),
    ("CPU mesh", ALBERT, 0, dict(data=1, model=1), (), False),
    ("CPU lowering, no mesh", ALBERT, 0, None, (), False),
])
def test_flash_dispatch(case, shape, window, mesh, manual, kernel):
    if mesh is not None:
        set_mesh(_mesh("cpu" if case == "CPU mesh" else "tpu", **mesh))
    set_manual_axes(manual)
    blocks = attn.flash_blocks(*shape, window=window)
    assert (blocks is not None) == kernel, case
    if kernel:
        S, T = shape[:2]
        assert S % blocks.block_q == 0 and T % blocks.block_kv == 0
        assert blocks.use_fused_bwd_kernel
