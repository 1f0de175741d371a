"""Shared building blocks: norms, MLPs, RoPE, embeddings, causal conv."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import shard


def cdtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _init(key, shape, scale, dtype):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def dense_init(key, d_in, d_out, dtype, scale=1.0):
    return _init(key, (d_in, d_out), scale, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_init(cfg, dim=None):
    dim = dim or cfg.d_model
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(p, cfg, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm" and "bias" in p:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = (xf**2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


def rms_head_norm(scale, x, eps=1e-6):
    """QK-norm over the head dim. x: (..., head_dim)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense MLP (optionally gated)
# ---------------------------------------------------------------------------
def mlp_init(key, cfg, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    dt = cdtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "wi": dense_init(k1, cfg.d_model, d_ff, dt),
        "wdown": dense_init(k3, d_ff, cfg.d_model, dt),
    }
    if cfg.glu:
        p["wg"] = dense_init(k2, cfg.d_model, d_ff, dt)
    return p


def act_fn(cfg, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


@jax.named_scope("model.mlp")
def apply_mlp(p, cfg, x):
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if "wg" in p:
        h = act_fn(cfg, jnp.einsum("...d,df->...f", x, p["wg"])) * h
    else:
        h = act_fn(cfg, h)
    h = shard(h, "batch", None, "model")
    return jnp.einsum("...f,fd->...d", h, p["wdown"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(cfg, dim):
    half = dim // 2
    return 1.0 / (cfg.rope_theta ** (np.arange(0, half, dtype=np.float32) / half))


def apply_rope(x, pos, cfg, dim=None):
    """x: (..., seq, heads, head_dim) or (..., heads, head_dim) with pos (...,seq)/scalar.

    cfg.rope == 'standard': rotate the full head dim (NeoX halves layout).
    cfg.rope == 'half':     GLM 2d-rope — rotate only the first half of the
                            head dim, pass through the second half.
    cfg.rope == 'none':     identity.
    """
    if cfg.rope == "none":
        return x
    hd = dim or x.shape[-1]
    rot = hd if cfg.rope == "standard" else hd // 2
    freqs = jnp.asarray(rope_freqs(cfg, rot))  # (rot/2,)
    angles = pos[..., None].astype(jnp.float32) * freqs  # (..., seq, rot/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., seq, 1, rot/2)
    sin = jnp.sin(angles)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2 :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------
def embed_init(key, cfg):
    dt = cdtype(cfg)
    p = {"embed": _init(key, (cfg.vocab_size, cfg.d_model), 1.0, dt)}
    if cfg.learned_pos:
        p["pos_embed"] = _init(
            jax.random.fold_in(key, 1), (cfg.max_position, cfg.d_model), 1.0, dt
        )
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(
            jax.random.fold_in(key, 2), cfg.d_model, cfg.vocab_size, dt
        )
    return p


@jax.named_scope("model.embed")
def embed_tokens(p, cfg, tokens, pos=None):
    x = jnp.take(p["embed"], tokens, axis=0)
    if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.learned_pos and pos is not None:
        x = x + jnp.take(p["pos_embed"], pos, axis=0)
    return x


def logits_out(p, cfg, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, p["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, p["lm_head"])
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return shard(logits, "batch", None, "model")


# ---------------------------------------------------------------------------
# Causal depthwise conv (SSM / RG-LRU front conv)
# ---------------------------------------------------------------------------
def conv1d_init(key, channels, width, dtype):
    return {
        "conv_w": _init(key, (width, channels), 1.0, dtype),
        "conv_b": jnp.zeros((channels,), dtype),
    }


def causal_conv1d(p, x):
    """x: (B, S, C). Depthwise causal conv, kernel width K."""
    w = p["conv_w"]  # (K, C)
    k = w.shape[0]
    pad = jnp.zeros(x.shape[:1] + (k - 1,) + x.shape[2:], x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros(x.shape, x.dtype)
    for i in range(k):  # unrolled: K is 4
        out = out + xp[:, i : i + x.shape[1], :] * w[i]
    return out + p["conv_b"]


def causal_conv1d_step(p, buf, x_t):
    """Single decode step. buf: (B, K-1, C) past inputs; x_t: (B, C)."""
    w = p["conv_w"]
    k = w.shape[0]
    window = jnp.concatenate([buf, x_t[:, None, :]], axis=1)  # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", window, w) + p["conv_b"]
    new_buf = window[:, 1:, :] if k > 1 else buf
    return new_buf, out
