"""Mamba-2's mixer (``ssm_apply``) against the benchmark's float32 reference
(``bench/reference._ssm``, which imports nothing of the program), on one
block at Mamba-2's head and state widths: heads of 64, state 128, conv 4,
chunk 256, over two chunks (S 512) so that the inter-chunk scan runs.

For the output and the gradient of a fixed random projection of it with
respect to the input and to every leaf of the mixer, the error is
‖program − reference‖ / ‖reference‖, with the reference fed the program's
own (rounded) parameters and input. Two initialisations of ``dt_bias``:
Mamba-2's published one (dt log-uniform in [1e-3, 0.1]) and zero (dt 0.69,
what ``bench/weights.py`` makes).
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import reference  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import ssm  # noqa: E402

D_MODEL, BATCH, SEQ = 256, 1, 512

# float32: the same sums in another order (the chunked dual against the
# reference's segment-sum form). The worst leaf is A_log, whose gradient
# sums products of exp(cumsum) over a 256-long chunk: 1.3e-4 at dt 0.69.
F32_BOUND = 1e-3
# bfloat16: unit roundoff 2^-9, and each value is rounded at about eight
# sites forward (in_proj, conv, SiLU, the SSD's inputs and output, gate,
# norm, out_proj) and as many backward. The worst leaf reads 1.2e-2 with the
# published dt and 3.2e-2 (conv_w) at dt 0.69, where the decays are steep
# and the SSD's sums cancel. 2^-4 keeps every gradient's direction (cosine
# above 0.998); a mixer that sums its log decays (dt * A) in bfloat16 reads
# 0.52 (A_log, published dt) and 10 (dt 0.69).
BF16_BOUND = 2.0 ** -4


def _cfg(dtype):
    return dataclasses.replace(get_config("mamba2-2.7b"), d_model=D_MODEL, dtype=dtype)


def _errors(dtype, dt_init):
    cfg = _cfg(dtype)
    k_p, k_x, k_w, k_dt = jax.random.split(jax.random.key(0), 4)
    params = ssm.ssm_init(k_p, cfg)
    if dt_init == "published":
        dt = jnp.exp(jax.random.uniform(k_dt, params["dt_bias"].shape,
                                        minval=np.log(1e-3), maxval=np.log(0.1)))
        params["dt_bias"] = jnp.log(jnp.expm1(dt))
    x = jax.random.normal(k_x, (BATCH, SEQ, D_MODEL), jnp.float32).astype(params["in_proj"].dtype)
    w = jax.random.normal(k_w, (BATCH, SEQ, D_MODEL), jnp.float32)
    m = {"ssm_expand": cfg.ssm_expand, "ssm_state": cfg.ssm_state,
         "ssm_head_dim": cfg.ssm_head_dim, "ssm_chunk": cfg.ssm_chunk,
         "norm_eps": cfg.norm_eps}
    mm = reference._matmul("f32")

    def ref_loss(p, x):
        y = reference._ssm(m, mm, lambda t: t, p, x)
        return (y * w).sum(), y

    def prog_loss(p, x):
        y, _ = ssm.ssm_apply(p, cfg, None, x)
        return (y.astype(jnp.float32) * w).sum(), y

    grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1), has_aux=True))
    up = lambda t: t.astype(jnp.float32)
    (g_ref, dx_ref), y_ref = grad(ref_loss)(jax.tree.map(up, params), up(x))
    (g, dx), y = grad(prog_loss)(params, x)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    return {"out": rel(y, y_ref), "d_input": rel(dx, dx_ref),
            **{f"d_{k}": rel(g[k], g_ref[k]) for k in params}}


@pytest.mark.parametrize("dt_init", ["published", "zero"])
@pytest.mark.parametrize("dtype,bound", [("float32", F32_BOUND), ("bfloat16", BF16_BOUND)],
                         ids=["float32", "bfloat16"])
def test_mixer_matches_the_float32_reference(dtype, bound, dt_init):
    errors = _errors(dtype, dt_init)
    assert len(errors) == 10
    worst = max(errors, key=errors.get)
    assert errors[worst] <= bound, (worst, errors)
