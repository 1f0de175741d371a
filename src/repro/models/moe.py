"""Mixture-of-Experts MLP with capacity-based scatter dispatch.

Expert compute is FLOP-honest (proportional to active parameters): tokens are
scattered into an (E, capacity, d) buffer per expert, processed with a single
(E, d, ff) batched matmul (experts sharded over 'model' => expert
parallelism), and combined back with the router probabilities. Overflowing
tokens are dropped (standard capacity-factor semantics); a switch-style
load-balance auxiliary loss is returned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, cdtype, dense_init, mlp_init, apply_mlp
from repro.sharding import shard

def moe_init(key, cfg, spec=None):
    dt = cdtype(cfg)
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "experts_wi": jax.vmap(lambda k: dense_init(k, d, f, dt))(
            jax.random.split(ks[1], E)
        ),
        "experts_wdown": jax.vmap(lambda k: dense_init(k, f, d, dt))(
            jax.random.split(ks[3], E)
        ),
    }
    if cfg.glu:
        p["experts_wg"] = jax.vmap(lambda k: dense_init(k, d, f, dt))(
            jax.random.split(ks[2], E)
        )
    if cfg.n_shared_experts:
        import dataclasses

        shared_cfg = dataclasses.replace(cfg, d_ff=cfg.n_shared_experts * f)
        p["shared"] = mlp_init(ks[4], shared_cfg, cfg.n_shared_experts * f)
    return p


def capacity(cfg, n_tokens):
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


@jax.named_scope("model.mlp")
def moe_apply(p, cfg, x):
    """x: (B, S, d) -> (y, aux_loss).

    Dispatch is GROUPED BY BATCH ROW (vmap over B): the token-order cumsum
    and the scatter into the (E, C, d) buffer stay local to each row, so the
    batch dim shards over ('pod','data') under plain GSPMD and the
    (b,e,c,d)x(e,d,f) expert einsum shards E over 'model' (expert
    parallelism). A token-major global dispatch defeats GSPMD: the expert
    matmul then runs on the GLOBAL token set on every device — measured 9x
    FLOP inflation on dbrx (EXPERIMENTS.md §Perf H1). Capacity is per row:
    C = capacity_factor * top_k * S / E.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(cfg, S)

    def route_group(xg):
        """xg: (S, d) -> dispatch buffer + combine metadata for one row."""
        logits = jnp.einsum("td,de->te", xg.astype(jnp.float32), p["router"])
        probs = jax.nn.softmax(logits, axis=-1)  # (S, E)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # load-balance aux (Switch): E * sum_e f_e * p_e
        me = probs.mean(0)
        ce = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32).mean(0)
        aux = E * jnp.sum(me * ce)

        buf = jnp.zeros((E, C, d), x.dtype)
        base = jnp.zeros((E,), jnp.int32)
        slots, keeps = [], []
        for k in range(K):
            oh = jax.nn.one_hot(top_e[:, k], E, dtype=jnp.int32)  # (S, E)
            pos_in_e = jnp.cumsum(oh, axis=0) - 1 + base[None, :]
            slot = jnp.take_along_axis(pos_in_e, top_e[:, k : k + 1], axis=1)[:, 0]
            base = base + oh.sum(0)
            keep = slot < C
            slot = jnp.where(keep, slot, C - 1)
            buf = buf.at[top_e[:, k], slot].add(
                jnp.where(keep[:, None], xg, 0).astype(buf.dtype)
            )
            slots.append(slot)
            keeps.append(keep)
        return buf, jnp.stack(slots), jnp.stack(keeps), top_e, top_p, aux

    buf, slots, keeps, top_e, top_p, aux = jax.vmap(route_group)(x)
    buf = shard(buf, "batch", "model", None, None)  # (B, E, C, d)

    h = jnp.einsum("becd,edf->becf", buf, p["experts_wi"])
    if "experts_wg" in p:
        g = jnp.einsum("becd,edf->becf", buf, p["experts_wg"])
        h = act_fn(cfg, g) * h
    else:
        h = act_fn(cfg, h)
    expert_out = jnp.einsum("becf,efd->becd", h, p["experts_wdown"])
    expert_out = shard(expert_out, "batch", "model", None, None)

    def combine_group(eo, slots_g, keeps_g, top_e_g, top_p_g):
        out = jnp.zeros((S, d), jnp.float32)
        for k in range(K):
            gathered = eo[top_e_g[:, k], slots_g[k]]  # (S, d)
            w = (top_p_g[:, k] * keeps_g[k]).astype(jnp.float32)
            out = out + w[:, None] * gathered.astype(jnp.float32)
        return out

    y = jax.vmap(combine_group)(expert_out, slots, keeps, top_e, top_p)
    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], cfg, x)
    return y, aux.mean()
