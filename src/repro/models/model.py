"""Public model API: init / loss / prefill / decode_step.

Batch dicts (produced by data pipeline or launch.input_specs):
  train:   {"tokens": (B, S+1) i32, ["memory_raw": (B, M, enc_dim)]}
  prefill: {"tokens": (B, S) i32,  ["memory_raw"]}
  decode:  {"token": (B,) i32, "pos": (B,) i32} + cache
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tfm
from repro.models.layers import (
    apply_norm,
    cdtype,
    dense_init,
    embed_init,
    embed_tokens,
    logits_out,
    norm_init,
)
from repro.sharding import shard

LOSS_CHUNK = 2048


class Model:
    def __init__(self, cfg):
        cfg.validate()
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init_params(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p = embed_init(ks[0], cfg)
        p.update(tfm.stack_init(ks[1], cfg))
        p["final_norm"] = norm_init(cfg)
        if cfg.has_encoder or cfg.family == "vlm":
            if cfg.encoder_dim and cfg.encoder_dim != cfg.d_model:
                p["projector"] = dense_init(
                    ks[2], cfg.encoder_dim, cfg.d_model, cdtype(cfg)
                )
            if cfg.has_encoder:
                p.update(tfm.encoder_init(ks[3], cfg))
        return p

    def abstract_params(self):
        return jax.eval_shape(self.init_params, jax.random.key(0))

    def param_count(self):
        tree = self.abstract_params()
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))

    def active_param_count(self):
        """Parameters touched per token (MoE: routed experts count top_k/E)."""
        cfg = self.cfg
        total = self.param_count()
        if not cfg.n_experts:
            return total
        tree = self.abstract_params()
        expert = sum(
            int(np.prod(l.shape))
            for path, l in jax.tree_util.tree_flatten_with_path(tree)[0]
            if any("experts_" in str(k) for k in path)
        )
        return total - expert + expert * cfg.top_k / cfg.n_experts

    # -------------------------------------------------------------- memory
    def _memory(self, params, batch):
        cfg = self.cfg
        if "memory_raw" not in batch:
            return None
        mem = batch["memory_raw"].astype(cdtype(cfg))
        if "projector" in params:
            mem = jnp.einsum("bme,ed->bmd", mem, params["projector"])
        if cfg.has_encoder:
            mem = tfm.encoder_apply(params, cfg, mem)
        return shard(mem, "batch", None, None)

    # ---------------------------------------------------------------- train
    def loss_fn(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        B, S = inputs.shape
        pos = jnp.arange(S)
        memory = self._memory(params, batch)
        x = embed_tokens(params, cfg, inputs, pos=pos if cfg.learned_pos else None)
        x = shard(x, "batch", None, None)
        x, _, aux = tfm.stack_apply(
            params, cfg, x, pos=pos, memory=memory, cache=None, mode="train"
        )
        x = apply_norm(params["final_norm"], cfg, x)

        # chunked + rematted cross-entropy: never materializes (B, S, V) f32
        # logits, and the backward recomputes each chunk's logits instead of
        # storing them. Chunk count is the CEILING of S / LOSS_CHUNK with
        # balanced widths, so every chunk (ragged tail included) stays within
        # the LOSS_CHUNK memory bound — floor division let a chunk grow to
        # 2*LOSS_CHUNK-1 tokens (S=4095 materialized the full logits matrix).
        n_chunks = -(-S // LOSS_CHUNK)
        csz = -(-S // n_chunks)

        @jax.checkpoint
        def chunk_loss(emb_params, x_sl, tgt_sl):
            with jax.named_scope("model.head"):
                logits = logits_out(emb_params, cfg, x_sl)
                lse = jax.nn.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(
                    logits, tgt_sl[..., None], axis=-1)[..., 0]
                return (lse - tgt).sum()

        emb_params = {k: params[k] for k in ("embed", "lm_head") if k in params}
        total = jnp.zeros((), jnp.float32)
        for i in range(n_chunks):
            sl = slice(i * csz, min((i + 1) * csz, S))
            total = total + chunk_loss(emb_params, x[:, sl], targets[:, sl])
        loss = total / (B * S)
        metrics = {"loss": loss, "aux_loss": aux}
        if cfg.n_experts:
            loss = loss + cfg.router_aux_coef * aux
        return loss, metrics

    # -------------------------------------------------------------- prefill
    def prefill(self, params, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        pos = jnp.arange(S)
        memory = self._memory(params, batch)
        x = embed_tokens(params, cfg, tokens, pos=pos if cfg.learned_pos else None)
        x = shard(x, "batch", None, None)
        x, new_cache, _ = tfm.stack_apply(
            params, cfg, x, pos=pos, memory=memory, cache=cache, mode="prefill"
        )
        x = apply_norm(params["final_norm"], cfg, x[:, -1:])
        logits = logits_out(params, cfg, x)
        return logits[:, 0], new_cache

    # --------------------------------------------------------------- decode
    def decode_step(self, params, batch, cache):
        cfg = self.cfg
        token, pos = batch["token"], batch["pos"]
        x = embed_tokens(
            params, cfg, token[:, None], pos=pos[:, None] if cfg.learned_pos else None
        )
        x, new_cache, _ = tfm.stack_apply(
            params, cfg, x, pos=pos, memory=None, cache=cache, mode="decode"
        )
        x = apply_norm(params["final_norm"], cfg, x)
        logits = logits_out(params, cfg, x)
        return logits[:, 0], new_cache

    # ---------------------------------------------------------------- cache
    def cache_shapes(self, batch_size, seq_len):
        return tfm.stack_cache_shapes(self.cfg, batch_size, seq_len)

    def init_cache(self, batch_size, seq_len):
        shapes = self.cache_shapes(batch_size, seq_len)
        return jax.tree.map(
            lambda l: jnp.zeros(*l),
            shapes,
            is_leaf=_is_shape_leaf,
        )

    def abstract_cache(self, batch_size, seq_len):
        shapes = self.cache_shapes(batch_size, seq_len)
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l[0], l[1]),
            shapes,
            is_leaf=_is_shape_leaf,
        )


def _is_shape_leaf(l):
    return isinstance(l, tuple) and len(l) == 2 and isinstance(l[0], tuple)


@functools.lru_cache(maxsize=None)
def get_model(arch: str, reduced: bool = False) -> Model:
    from repro.configs import get_config, reduce_config

    cfg = get_config(arch)
    if reduced:
        cfg = reduce_config(cfg)
    return Model(cfg)
