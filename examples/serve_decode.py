"""Batched serving example: prefill + token-by-token decode with the
distributed serving steps (single device here; same code drives the pod).

  PYTHONPATH=src python examples/serve_decode.py --arch gemma3-27b
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape
from repro.data import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import get_model
from repro.sharding import set_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=12)
    args = ap.parse_args()

    mesh = make_mesh((1, 1), ("data", "model"))
    set_mesh(mesh)
    m = get_model(args.arch, reduced=True)
    total = args.prompt_len + args.gen
    shape = InputShape("x", total, args.batch, "decode")
    prefill_fn, _ = make_prefill_step(m, mesh, shape)
    decode_fn, _ = make_decode_step(m, mesh, shape)

    params = m.init_params(jax.random.key(0))
    pipe = TokenPipeline(m.cfg.vocab_size, args.prompt_len, args.batch)
    prompts = pipe.batch(0)["tokens"][:, : args.prompt_len]
    batch = {"tokens": prompts}
    if m.cfg.encoder_len:
        batch["memory_raw"] = jax.random.normal(
            jax.random.key(1), (args.batch, m.cfg.encoder_len, m.cfg.encoder_dim)
        ) * 0.02

    cache = m.init_cache(args.batch, total)
    logits, cache = prefill_fn(params, batch, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    generated = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
        logits, cache = decode_fn(params, cache, {"token": tok, "pos": pos})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(tok)
    ms = (time.time() - t0) / max(args.gen - 1, 1) * 1000
    print(f"{m.cfg.name}: {args.batch} seqs, {ms:.1f} ms/token (CPU, reduced model)")
    print("generations:", jnp.stack(generated, 1)[:2].tolist())


if __name__ == "__main__":
    main()
