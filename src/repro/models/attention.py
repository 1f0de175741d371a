"""Attention flavours: GQA (full / sliding-window / cross), MLA (DeepSeek).

All einsums keep KV heads grouped — (B, S, K, G, D) query layout — so GQA
never materializes repeated KV. Softmax runs in f32.

Long sequences use a *python-unrolled* blocked online-softmax (no lax.scan)
so the dry-run roofline sees the true FLOP/byte counts (cost_analysis counts
a scan body only once — see DESIGN.md).

On a TPU, GQA's causal self-attention (train and prefill) runs as the fused
Pallas flash-attention kernel of the installed jax
(``jax.experimental.pallas.ops.tpu.splash_attention``: the forward
``splash_mha_fwd_residuals`` (``_no_residuals`` where nothing is
differentiated) and the fused backward
``splash_mha_dkv_no_residuals``, which gives dq, dk and dv): softmax online
in VMEM, block by block, so neither pass writes an S x S tensor to HBM.
``flash_blocks`` decides from what the trace can observe, and ``gqa_apply``
then projects straight into the kernel's (B, H, S, D) layout. The kernel
runs where

- the program is traced for a TPU (the registered mesh's devices, else the
  default backend) and no automatically partitioned mesh axis of size > 1
  would have to split it (XLA cannot partition a Mosaic call; the peer axes
  of the BTARD step's shard_map are manual);
- q, k and v share one head dim, at most 128 or a multiple of 128;
- S and T are multiples of 128, T <= ``DENSE_MAX_KV``, and any sliding
  window covers all of T.

Everything else takes the jnp paths below, unchanged: the CPU, MLA (k's
head dim differs from v's), shorter windows, longer sequences, cross
attention and decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as _splash
from jax.sharding import PartitionSpec as P

from repro.models.layers import apply_rope, cdtype, dense_init, rms_head_norm
from repro.sharding import get_mesh, shard
from repro.sharding.specs import get_manual_axes

NEG_INF = -2.0e38
DENSE_MAX_KV = 8192  # use dense path when kv_len <= this
KV_BLOCK = 1024


# ---------------------------------------------------------------------------
# Core softmax-attention primitives (grouped-query layout)
# ---------------------------------------------------------------------------
def _dense_attention(q, k, v, mask):
    """q: (B,S,K,G,D); k,v: (B,T,K,D); mask: (B,1,1,S,T) or (1,1,1,S,T)."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / np.sqrt(q.shape[-1]))
    scores = jnp.where(jnp.moveaxis(mask, -2, -2), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out


def _blocked_attention(q, k, v, qpos, kpos, window=0):
    """Online-softmax over KV blocks, python-unrolled.

    q: (B,S,K,G,D); k,v: (B,T,K,D); qpos: (S,), kpos: (T,) absolute positions.
    window=0 -> plain causal; window>0 -> also restrict to the sliding window.
    """
    B, S, K, G, D = q.shape
    Dv = v.shape[-1]  # may differ from D (MLA: K=192, V=128)
    T = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    m = jnp.full((B, K, G, S), NEG_INF, jnp.float32)
    l = jnp.zeros((B, K, G, S), jnp.float32)
    acc = jnp.zeros((B, S, K, G, Dv), jnp.float32)
    n_blocks = (T + KV_BLOCK - 1) // KV_BLOCK
    for j in range(n_blocks):
        lo = j * KV_BLOCK
        hi = min(T, lo + KV_BLOCK)
        kb, vb = k[:, lo:hi], v[:, lo:hi]
        kp = kpos[lo:hi]
        msk = kp[None, :] <= qpos[:, None]
        if window:
            msk &= kp[None, :] > (qpos[:, None] - window)
        s = jnp.einsum("bskgd,btkd->bkgst", q, kb, preferred_element_type=jnp.float32)
        s = s * scale + jnp.where(msk, 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(-1)
        acc = acc * jnp.moveaxis(corr, 3, 1)[..., None] + jnp.einsum(
            "bkgst,btkd->bskgd", p.astype(v.dtype), vb
        ).astype(jnp.float32)
        m = m_new
    denom = jnp.moveaxis(l, 3, 1)[..., None]
    return (acc / jnp.maximum(denom, 1e-37)).astype(q.dtype)


def _windowed_attention(q, k, v, window):
    """Sliding-window causal self-attention, O(S * window).

    Query blocks unrolled; each block attends a static KV slice
    [qs - window, qs + Bq). q,k,v same seq length S.
    """
    B, S, K, G, D = q.shape
    Bq = min(S, max(128, KV_BLOCK))
    if S <= window:  # window covers everything: plain causal
        qpos = jnp.arange(S)
        return _blocked_attention(q, k, v, qpos, qpos, window=window)
    scale = 1.0 / np.sqrt(D)
    pad = window
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    outs = []
    for qs in range(0, S, Bq):
        qb = q[:, qs : qs + Bq]
        span = window + qb.shape[1]
        kb = kp[:, qs : qs + span]  # absolute kv positions [qs-window, qs+Bq)
        vb = vp[:, qs : qs + span]
        qpos = qs + jnp.arange(qb.shape[1])
        kpos = qs - window + jnp.arange(span)
        msk = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window
        ) & (kpos[None, :] >= 0)
        s = jnp.einsum("bskgd,btkd->bkgst", qb, kb, preferred_element_type=jnp.float32)
        s = s * scale + jnp.where(msk, 0.0, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bkgst,btkd->bskgd", p.astype(vb.dtype), vb))
    return jnp.concatenate(outs, axis=1)


def _decode_attention(q, k_cache, v_cache, pos, window=0):
    """q: (B,1,K,G,D); caches: (B,T,K,D); pos: (B,) current position."""
    B, _, K, G, D = q.shape
    T = k_cache.shape[1]
    scale = 1.0 / np.sqrt(D)
    t_idx = jnp.arange(T)
    msk = t_idx[None, :] <= pos[:, None]
    if window:
        msk &= t_idx[None, :] > (pos[:, None] - window)
    s = jnp.einsum(
        "bkgd,btkd->bkgt", q[:, 0], k_cache, preferred_element_type=jnp.float32
    )
    s = s * scale + jnp.where(msk[:, None, None, :], 0.0, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache)
    return out[:, None]  # (B,1,K,G,D)


def causal_attention(q, k, v, window=0):
    """Self-attention over full sequences (train / prefill)."""
    S, T = q.shape[1], k.shape[1]
    if window and T > window:
        return _windowed_attention(q, k, v, window)
    if T <= DENSE_MAX_KV:
        pos = jnp.arange(T)
        msk = pos[None, :] <= pos[:, None]
        if window:
            msk &= pos[None, :] > pos[:, None] - window
        return _dense_attention(q, k, v, msk[None, None, None])
    qpos = jnp.arange(S)
    return _blocked_attention(q, k, v, qpos, jnp.arange(T), window=window)


# ---------------------------------------------------------------------------
# Fused flash-attention kernel (TPU)
# ---------------------------------------------------------------------------
FLASH_TILE = 128  # the kernel's lane tile: every block is a multiple of it


def _kernel_allowed() -> bool:
    """Whether the program being traced may hold a Mosaic kernel: it is
    traced for a TPU (the registered mesh's devices, else the default
    backend), and no automatically partitioned mesh axis of size > 1 would
    have to split the kernel."""
    mesh = get_mesh()
    if mesh is None:
        return jax.default_backend() == "tpu"
    if mesh.devices.flat[0].platform != "tpu":
        return False
    manual = get_manual_axes()
    return all(n == 1 for a, n in mesh.shape.items() if a not in manual)


def _block(n: int, cap: int) -> int:
    """The largest multiple of ``FLASH_TILE`` up to ``cap`` that divides n."""
    return max(b for b in range(FLASH_TILE, min(n, cap) + 1, FLASH_TILE)
               if n % b == 0)


def flash_blocks(seq, kv_len, head_dim, v_head_dim, window=0):
    """The kernel's block sizes for causal self-attention of these shapes,
    or None where the jnp path runs (module docstring). A block spans up to
    512 rows, the whole of ALBERT's sequence, so a grid step holds one
    (head, example)'s full 512 x 512 tile; the backward is the one fused
    kernel that gives dq, dk and dv."""
    if not _kernel_allowed() or head_dim != v_head_dim:
        return None
    if head_dim > FLASH_TILE and head_dim % FLASH_TILE:
        return None
    if seq % FLASH_TILE or kv_len % FLASH_TILE or kv_len > DENSE_MAX_KV:
        return None
    if window and window < kv_len:
        return None
    bq, bk = _block(seq, 512), _block(kv_len, 512)
    return _splash.BlockSizes(
        block_q=bq, block_kv=bk, block_kv_compute=bk,
        block_q_dkv=bq, block_kv_dkv=bk, block_kv_dkv_compute=bk,
        use_fused_bwd_kernel=True,
    )


def flash_causal_attention(q, k, v, blocks):
    """Causal self-attention by the fused kernel. q, k, v: (B, H, S, D) with
    equal head counts -> (B, H, S, D). The kernel runs (B * H) heads of one
    sequence each, so the batch folds into the heads with no layout change.
    It applies no scale, so q comes in scaled by 1/sqrt(D) in its own dtype:
    exact where D is a power of 4 (64 here), else one rounding of q.

    Inside a manual region (the BTARD step's per-peer shard_map) a Mosaic
    call must see every mesh axis manual, so the kernel is wrapped in a
    shard_map over the axes still automatic, all of size 1 here."""
    B, H, S, D = q.shape
    mask = _splash.MultiHeadMask([_splash.CausalMask((S, k.shape[2]))] * (B * H))
    kernel = _splash.make_splash_mha(
        mask, block_sizes=blocks, head_shards=1, q_seq_shards=1)
    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(mesh.manual_axes)
    if mesh.manual_axes and auto:
        kernel = jax.shard_map(kernel, in_specs=(P(),) * 3, out_specs=P(),
                               axis_names=auto, check_vma=False)
    q = (q * (1.0 / np.sqrt(D))).astype(q.dtype)
    out = kernel(*(t.reshape(B * H, *t.shape[2:]) for t in (q, k, v)))
    return out.reshape(B, H, S, D)


# ---------------------------------------------------------------------------
# GQA block (full / local / cross)
# ---------------------------------------------------------------------------
def gqa_init(key, cfg, spec):
    dt = cdtype(cfg)
    ks = jax.random.split(key, 6)
    H, Kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cross_only = spec.mixer == "attn_cross"
    p = {
        "wq": dense_init(ks[0], cfg.d_model, H * D, dt),
        "wo": dense_init(ks[3], H * D, cfg.d_model, dt),
    }
    if not cross_only:
        p["wk"] = dense_init(ks[1], cfg.d_model, Kv * D, dt)
        p["wv"] = dense_init(ks[2], cfg.d_model, Kv * D, dt)
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((H * D,), dt)
        if not cross_only:
            p["wk_bias"] = jnp.zeros((Kv * D,), dt)
            p["wv_bias"] = jnp.zeros((Kv * D,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((D,), jnp.float32)
        p["k_norm"] = jnp.ones((D,), jnp.float32)
    if spec.cross or spec.mixer == "attn_cross":
        # separate KV projection for the encoder memory
        p["mem_wk"] = dense_init(ks[4], cfg.d_model, Kv * D, dt)
        p["mem_wv"] = dense_init(ks[5], cfg.d_model, Kv * D, dt)
        if spec.mixer == "attn_cross":
            p["xgate"] = jnp.zeros((), jnp.float32)  # llama-vision gated x-attn
        else:  # self+cross decoder layer: separate cross projections
            kq = jax.random.fold_in(ks[4], 7)
            kw = jax.random.fold_in(ks[5], 7)
            p["mem_wq"] = dense_init(kq, cfg.d_model, H * D, dt)
            p["mem_wo"] = dense_init(kw, H * D, cfg.d_model, dt)
    return p


def _project_q(p, cfg, x):
    B, S, _ = x.shape
    H, Kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    if "wq_bias" in p:
        q = q + p["wq_bias"]
    q = q.reshape(B, S, Kv, H // Kv, D)
    if "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
    return q


def _project_kv(p, cfg, x, wk="wk", wv="wv"):
    B, S, _ = x.shape
    Kv, D = cfg.n_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,de->bse", x, p[wk])
    v = jnp.einsum("bsd,de->bse", x, p[wv])
    if wk == "wk" and "wk_bias" in p:
        k = k + p["wk_bias"]
        v = v + p["wv_bias"]
    k = k.reshape(B, S, Kv, D)
    v = v.reshape(B, S, Kv, D)
    if "k_norm" in p:
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    return k, v


@jax.named_scope("model.attention")
def gqa_apply(p, cfg, spec, x, *, pos, memory=None, cache=None, mode="train"):
    """Causal self-attention part of a GQA block.

    Returns (y, new_cache). x: (B,S,d). pos: (S,) train / (B,) decode.
    Cross-attention (``spec.cross`` or mixer=='attn_cross') is handled
    separately by ``cross_attn_apply`` (own norm/residual at block level).
    """
    B, S, _ = x.shape
    H, Kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.window if spec.mixer == "attn_local" else 0
    if mode != "decode":
        blocks = flash_blocks(S, S, D, D, window)
        if blocks is not None:
            return _gqa_flash(p, cfg, x, pos, blocks, cache)
    new_cache = {} if cache is not None else None

    q = _project_q(p, cfg, x)
    if mode == "decode":
        q = apply_rope(q.reshape(B, S, H, D), pos[:, None], cfg).reshape(
            B, S, Kv, H // Kv, D
        )
        k_new, v_new = _project_kv(p, cfg, x)
        k_new = apply_rope(k_new, pos[:, None], cfg)
        kc = _cache_insert(cache["k"], k_new, pos)
        vc = _cache_insert(cache["v"], v_new, pos)
        new_cache["k"], new_cache["v"] = kc, vc
        attn = _decode_attention(q, kc, vc, pos, window=window)
    else:
        q = apply_rope(q.reshape(B, S, H, D), pos[None, :], cfg)
        k, v = _project_kv(p, cfg, x)
        k = apply_rope(k, pos[None, :], cfg)
        if new_cache is not None:  # prefill: persist KV (grouped layout)
            new_cache["k"] = _cache_prefill(cache["k"], k)
            new_cache["v"] = _cache_prefill(cache["v"], v)
        # expand KV to full heads: keeps the head dim shardable over 'model'
        # even when n_kv < TP degree (bandwidth-for-shardability trade; the
        # cache itself stays grouped)
        if Kv < H:
            k = jnp.repeat(k, H // Kv, axis=2)
            v = jnp.repeat(v, H // Kv, axis=2)
        q = shard(q.reshape(B, S, H, 1, D), "batch", None, "model", None, None)
        k = shard(k, "batch", None, "model", None)
        v = shard(v, "batch", None, "model", None)
        attn = causal_attention(q, k, v, window=window)

    y = shard(attn.reshape(B, S, H * D), "batch", None, "model")
    y = jnp.einsum("bse,ed->bsd", y, p["wo"])
    return y, new_cache


def _project_heads(x, w, bias, n, D):
    """x (B, S, d) through w (d, n*D) straight into the kernel's layout
    (B, n, S, D)."""
    y = jnp.einsum("bsd,dhk->bhsk", x, w.reshape(w.shape[0], n, D))
    if bias is not None:
        y = y + bias.reshape(n, 1, D)
    return y


def _gqa_flash(p, cfg, x, pos, blocks, cache):
    """``gqa_apply``'s train / prefill self-attention through the fused
    kernel: the projections write (B, H, S, D) and the output projection
    reads it, so no layout change stands between them and the kernel. No
    sharding hint is placed: the kernel runs only where no automatic mesh
    axis has more than one device."""
    H, Kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _project_heads(x, p["wq"], p.get("wq_bias"), H, D)
    k = _project_heads(x, p["wk"], p.get("wk_bias"), Kv, D)
    v = _project_heads(x, p["wv"], p.get("wv_bias"), Kv, D)
    if "q_norm" in p:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    # rope over (..., S, 1, D): the sequence axis stands before a unit head
    q = apply_rope(q[:, :, :, None], pos[None, :], cfg)[:, :, :, 0]
    k = apply_rope(k[:, :, :, None], pos[None, :], cfg)[:, :, :, 0]
    new_cache = None
    if cache is not None:  # prefill: the cache keeps its (B, T, K, D) layout
        new_cache = {"k": _cache_prefill(cache["k"], k.swapaxes(1, 2)),
                     "v": _cache_prefill(cache["v"], v.swapaxes(1, 2))}
    if Kv < H:  # the same head-shardable expand as the jnp path
        k = jnp.repeat(k, H // Kv, axis=1)
        v = jnp.repeat(v, H // Kv, axis=1)
    attn = flash_causal_attention(q, k, v, blocks)
    y = jnp.einsum("bhsk,hkd->bsd", attn, p["wo"].reshape(H, D, -1))
    return y, new_cache


@jax.named_scope("model.attention")
def cross_attn_apply(p, cfg, spec, x, *, memory=None, cache=None, mode="train"):
    """Cross-attention over encoder memory. Returns (y, new_cache_entries)."""
    B, S, _ = x.shape
    H, Kv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cross_only = spec.mixer == "attn_cross"
    wq, wo = ("wq", "wo") if cross_only else ("mem_wq", "mem_wo")
    new_entries = {} if cache is not None else None

    q = jnp.einsum("bsd,de->bse", x, p[wq])
    if cross_only and "wq_bias" in p:
        q = q + p["wq_bias"]
    q = q.reshape(B, S, Kv, H // Kv, D)
    if cache is not None and mode == "decode":
        mk, mv = cache["mem_k"], cache["mem_v"]
        new_entries["mem_k"], new_entries["mem_v"] = mk, mv
    else:
        mk, mv = _project_kv(p, cfg, memory, wk="mem_wk", wv="mem_wv")
        if new_entries is not None:
            new_entries["mem_k"], new_entries["mem_v"] = mk, mv
    M = mk.shape[1]
    if mode != "decode" and Kv < H:  # head-shardable expand (see gqa_apply)
        mk = jnp.repeat(mk, H // Kv, axis=2)
        mv = jnp.repeat(mv, H // Kv, axis=2)
        q = shard(q.reshape(B, S, H, 1, D), "batch", None, "model", None, None)
        mk = shard(mk, "batch", None, "model", None)
        mv = shard(mv, "batch", None, "model", None)
    msk = jnp.ones((1, 1, 1, S, M), bool)
    xa = _dense_attention(q, mk, mv, msk).reshape(B, S, H * D)
    if "xgate" in p:
        xa = xa * jnp.tanh(p["xgate"]).astype(xa.dtype)
    xa = shard(xa, "batch", None, "model")
    y = jnp.einsum("bse,ed->bsd", xa, p[wo])
    return y, new_entries


def _cache_insert(cache, new, pos):
    """cache: (B,T,...), new: (B,1,...), pos: (B,)."""

    def ins(c, n, p):
        idx = (p,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), idx)

    return jax.vmap(ins)(cache, new, pos)


def _cache_prefill(cache, full):
    """Write the first S positions of the cache."""
    S = full.shape[1]
    if cache.shape[1] == S:
        return full.astype(cache.dtype)
    return jax.lax.dynamic_update_slice(
        cache, full.astype(cache.dtype), (0,) * cache.ndim
    )


def gqa_cache_shape(cfg, spec, batch, seq_len, has_memory):
    dt = cdtype(cfg)
    shapes = {}
    if spec.mixer != "attn_cross":
        shapes["k"] = ((batch, seq_len, cfg.n_kv_heads, cfg.head_dim), dt)
        shapes["v"] = ((batch, seq_len, cfg.n_kv_heads, cfg.head_dim), dt)
    if spec.cross or spec.mixer == "attn_cross":
        mem_len = cfg.encoder_len
        shapes["mem_k"] = ((batch, mem_len, cfg.n_kv_heads, cfg.head_dim), dt)
        shapes["mem_v"] = ((batch, mem_len, cfg.n_kv_heads, cfg.head_dim), dt)
    return shapes


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------
def mla_init(key, cfg, spec):
    dt = cdtype(cfg)
    ks = jax.random.split(key, 4)
    H = cfg.n_heads
    qd = cfg.nope_head_dim + cfg.rope_head_dim
    p = {
        "wq": dense_init(ks[0], cfg.d_model, H * qd, dt),
        "kv_a": dense_init(ks[1], cfg.d_model, cfg.kv_lora_rank + cfg.rope_head_dim, dt),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "kv_b": dense_init(
            ks[2], cfg.kv_lora_rank, H * (cfg.nope_head_dim + cfg.v_head_dim), dt
        ),
        "wo": dense_init(ks[3], H * cfg.v_head_dim, cfg.d_model, dt),
    }
    return p


def _mla_compress(p, cfg, x, pos, decode):
    """Returns (c_kv normed, k_rope roped)."""
    B, S, _ = x.shape
    a = jnp.einsum("bsd,de->bse", x, p["kv_a"])
    c_kv, k_rope = a[..., : cfg.kv_lora_rank], a[..., cfg.kv_lora_rank :]
    c_kv = rms_head_norm(p["kv_norm"], c_kv, cfg.norm_eps)
    pos_b = pos[:, None] if decode else pos[None, :]
    k_rope = apply_rope(k_rope[:, :, None, :], pos_b, cfg)[:, :, 0, :]
    return c_kv, k_rope


@jax.named_scope("model.attention")
def mla_apply(p, cfg, spec, x, *, pos, memory=None, cache=None, mode="train"):
    B, S, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    scale = 1.0 / np.sqrt(nd + rd)

    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    pos_b = pos[:, None] if mode == "decode" else pos[None, :]
    q_rope = apply_rope(q_rope, pos_b, cfg)

    kv_b = p["kv_b"].reshape(rank, H, nd + vd)
    w_k, w_v = kv_b[..., :nd], kv_b[..., nd:]

    c_new, kr_new = _mla_compress(p, cfg, x, pos, mode == "decode")
    new_cache = None
    if mode == "decode":
        c_kv = _cache_insert(cache["c_kv"], c_new, pos)
        k_rope = _cache_insert(cache["k_rope"], kr_new, pos)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        # absorbed decode: attend in the latent space (the MLA cache win)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_k)
        s = jnp.einsum("bhr,btr->bht", q_lat, c_kv, preferred_element_type=jnp.float32)
        s = s + jnp.einsum(
            "bhp,btp->bht", q_rope[:, 0], k_rope, preferred_element_type=jnp.float32
        )
        T = c_kv.shape[1]
        msk = jnp.arange(T)[None, :] <= pos[:, None]
        s = s * scale + jnp.where(msk[:, None, :], 0.0, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bht,btr->bhr", pr.astype(c_kv.dtype), c_kv)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_v)[:, None]  # (B,1,H,vd)
    else:
        if cache is not None:  # prefill persists the compressed cache
            new_cache = {
                "c_kv": _cache_prefill(cache["c_kv"], c_new),
                "k_rope": _cache_prefill(cache["k_rope"], kr_new),
            }
        # expand and run standard attention (kv heads == H)
        k_nope = jnp.einsum("btr,rhn->bthn", c_new, w_k)
        v = jnp.einsum("btr,rhv->bthv", c_new, w_v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_new[:, :, None, :], (B, S, H, rd))], -1
        )
        qq = jnp.concatenate([q_nope, q_rope], -1).reshape(B, S, H, 1, nd + rd)
        o = causal_attention(qq, k, v, window=0).reshape(B, S, H, vd)

    y = shard(o.reshape(B, S, H * vd), "batch", None, "model")
    y = jnp.einsum("bse,ed->bsd", y, p["wo"])
    return y, new_cache


def mla_cache_shape(cfg, spec, batch, seq_len, has_memory):
    dt = cdtype(cfg)
    return {
        "c_kv": ((batch, seq_len, cfg.kv_lora_rank), dt),
        "k_rope": ((batch, seq_len, cfg.rope_head_dim), dt),
    }
