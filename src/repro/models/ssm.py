"""Mamba-2 SSD (state-space duality) mixer — chunked dual form.

Training/prefill uses the chunked algorithm from arXiv:2405.21060 §6: each
chunk is a small quadratic attention-like block (MXU-friendly matmuls), and
chunk states are combined with an *associative scan* (log-depth, fully
counted by cost_analysis — see DESIGN.md on scan accounting).

Decode carries (state, conv buffer) and performs the linear recurrence step.
n_groups = 1 (B/C shared across heads).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import cdtype, conv1d_init, causal_conv1d, causal_conv1d_step, dense_init
from repro.sharding import shard


def ssm_init(key, cfg, spec=None):
    dt = cdtype(cfg)
    ks = jax.random.split(key, 4)
    d_in = cfg.ssm_inner
    H = cfg.ssm_heads
    N = cfg.ssm_state
    conv_ch = d_in + 2 * N
    p = {
        "in_proj": dense_init(ks[0], cfg.d_model, 2 * d_in + 2 * N + H, dt),
        "out_proj": dense_init(ks[1], d_in, cfg.d_model, dt),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)
        ),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
    }
    p.update(conv1d_init(ks[2], conv_ch, cfg.ssm_conv, dt))
    return p


def _split_proj(cfg, zxbcdt):
    d_in, N, H = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :d_in]
    x = zxbcdt[..., d_in : 2 * d_in]
    Bc = zxbcdt[..., 2 * d_in : 2 * d_in + N]
    Cc = zxbcdt[..., 2 * d_in + N : 2 * d_in + 2 * N]
    dt = zxbcdt[..., 2 * d_in + 2 * N :]
    return z, x, Bc, Cc, dt


def _gated_norm(p, cfg, y, z):
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    yn = yf * jax.lax.rsqrt((yf**2).mean(-1, keepdims=True) + cfg.norm_eps)
    return (yn * p["gate_norm"]).astype(y.dtype)


@jax.named_scope("model.ssd")
def ssd_chunked(x, a_log, dt, Bm, Cm, chunk, init_state=None):
    """Chunked SSD.

    x:  (B, S, H, P)   inputs per head
    a_log: (B, S, H)   per-step log decay  (= dt * A, negative)
    dt: (B, S, H)      input step sizes
    Bm, Cm: (B, S, N)  shared input/output projections (n_groups=1)
    Returns y (B, S, H, P) and final state (B, H, N, P).
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:
        # pad tail with dt=0 steps: decay=1, zero input => state untouched
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q

    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    ac = a_log.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    lcum = jnp.cumsum(ac, axis=2)  # (B,nc,Q,H) inclusive cumulative log decay
    # --- intra-chunk (quadratic within chunk) ------------------------------
    # L[i,j] = exp(lcum_i - lcum_j) for j <= i  (decay from j+1..i)
    seg = lcum[:, :, :, None, :] - lcum[:, :, None, :, :]  # (B,nc,Q_i,Q_j,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc, preferred_element_type=jnp.float32)
    M = G[..., None] * L * dtc[:, :, None, :, :]  # (B,nc,Qi,Qj,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M.astype(x.dtype), xc)

    # --- chunk-local final states ------------------------------------------
    decay_to_end = jnp.exp(lcum[:, :, -1:, :] - lcum)  # (B,nc,Q,H)
    wB = Bc[:, :, :, None, :] * (dtc * decay_to_end)[..., None]  # (B,nc,Q,H,N)
    S_local = jnp.einsum("bcqhn,bcqhp->bchnp", wB.astype(x.dtype), xc)

    # --- inter-chunk associative scan ---------------------------------------
    chunk_decay = jnp.exp(lcum[:, :, -1, :])  # (B,nc,H)

    def combine(l, r):
        al, sl = l
        ar, sr = r
        return al * ar, sl * ar[..., None, None] + sr

    a_all, S_all = jax.lax.associative_scan(
        combine, (chunk_decay, S_local.astype(jnp.float32)), axis=1
    )
    if init_state is not None:
        S_all = S_all + a_all[..., None, None] * init_state[:, None].astype(jnp.float32)
    # state entering chunk c = S_all[c-1] (shifted), or init_state for c=0
    if init_state is None:
        S_in = jnp.concatenate(
            [jnp.zeros(S_all[:, :1].shape, S_all.dtype), S_all[:, :-1]], axis=1
        )
    else:
        S_in = jnp.concatenate(
            [init_state[:, None].astype(jnp.float32), S_all[:, :-1]], axis=1
        )
    y_inter = jnp.einsum(
        "bcqn,bchnp->bcqhp",
        Cc,
        S_in.astype(Cc.dtype),
    ) * jnp.exp(lcum)[..., None].astype(x.dtype)

    y = (y_intra + y_inter).reshape(Bsz, S, H, P)[:, :S_orig]
    return y, S_all[:, -1]


@jax.named_scope("model.ssm")
def ssm_apply(p, cfg, spec, x, *, pos=None, memory=None, cache=None, mode="train"):
    B, S, _ = x.shape
    d_in, N, H, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    A = -jnp.exp(p["A_log"])  # (H,) negative

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    zxbcdt = shard(zxbcdt, "batch", None, "model")
    z, xs, Bm, Cm, dt_raw = _split_proj(cfg, zxbcdt)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    new_cache = {} if cache is not None else None
    if mode == "decode":
        conv_buf, conv_out = causal_conv1d_step(p, cache["conv"], conv_in[:, 0])
        conv_out = jax.nn.silu(conv_out)[:, None]
        new_cache["conv"] = conv_buf
    else:
        conv_out = jax.nn.silu(causal_conv1d(p, conv_in))
        if new_cache is not None:
            pad = max(0, (cfg.ssm_conv - 1) - S)
            tail = conv_in[:, S - (cfg.ssm_conv - 1) :] if S >= cfg.ssm_conv - 1 else (
                jnp.pad(conv_in, ((0, 0), (pad, 0), (0, 0)))
            )
            new_cache["conv"] = tail

    xs = conv_out[..., :d_in].reshape(B, -1, H, P)
    Bm = conv_out[..., d_in : d_in + N]
    Cm = conv_out[..., d_in + N :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a_log = dt * A  # (B,S,H), negative

    if mode == "decode":
        state = cache["state"].astype(jnp.float32)  # (B,H,N,P)
        a = jnp.exp(a_log[:, 0])  # (B,H)
        inc = jnp.einsum("bn,bhp->bhnp", Bm[:, 0].astype(jnp.float32), (dt[:, 0][..., None] * xs[:, 0].astype(jnp.float32)))
        state = state * a[..., None, None] + inc
        y = jnp.einsum("bn,bhnp->bhp", Cm[:, 0].astype(jnp.float32), state)
        y = y + p["D"][:, None] * xs[:, 0].astype(jnp.float32)
        y = y[:, None].astype(x.dtype)  # (B,1,H,P)
        new_cache["state"] = state.astype(cache["state"].dtype)
    else:
        y, final_state = ssd_chunked(xs, a_log, dt, Bm, Cm, cfg.ssm_chunk)
        y = y + (p["D"][None, None, :, None] * xs.astype(jnp.float32)).astype(y.dtype)
        if new_cache is not None:
            new_cache["state"] = final_state.astype(cdtype(cfg))

    y = y.reshape(B, -1, d_in)
    y = _gated_norm(p, cfg, y, z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, new_cache


def ssm_cache_shape(cfg, spec, batch, seq_len, has_memory):
    dt = cdtype(cfg)
    d_in, N = cfg.ssm_inner, cfg.ssm_state
    return {
        "state": ((batch, cfg.ssm_heads, N, cfg.ssm_head_dim), dt),
        "conv": ((batch, cfg.ssm_conv - 1, d_in + 2 * N), dt),
    }
