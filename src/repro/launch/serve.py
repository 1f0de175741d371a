"""Batched serving driver: prefill a batch of prompts, then decode tokens.

  python -m repro.launch.serve --arch qwen3-1.7b --reduced --host-devices 8 \\
      --mesh 4x2 --batch 8 --prompt-len 32 --gen 16
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host-devices", type=int, default=0)
    ap.add_argument("--mesh", default="4x2")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}"
        )

    import jax
    import jax.numpy as jnp

    from repro.configs.base import InputShape
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import get_model
    from repro.sharding import set_mesh

    dims = [int(x) for x in args.mesh.split("x")]
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    mesh = make_mesh(dims, names)
    set_mesh(mesh)

    model = get_model(args.arch, reduced=args.reduced)
    total = args.prompt_len + args.gen
    shape = InputShape("cli", total, args.batch, "decode")
    pshape = InputShape("cli_p", args.prompt_len, args.batch, "prefill")

    prefill_fn, _ = make_prefill_step(model, mesh, shape)  # cache sized `total`
    decode_fn, _ = make_decode_step(model, mesh, shape)

    params = model.init_params(jax.random.key(0))
    pipe = TokenPipeline(model.cfg.vocab_size, args.prompt_len, args.batch)
    batch = pipe.batch(0)
    prompts = batch["tokens"][:, : args.prompt_len]
    pf_batch = {"tokens": prompts}
    if model.cfg.encoder_len:
        pf_batch["memory_raw"] = (
            jax.random.normal(
                jax.random.key(1),
                (args.batch, model.cfg.encoder_len, model.cfg.encoder_dim),
            )
            * 0.02
        )

    cache = model.init_cache(args.batch, total)
    t0 = time.time()
    logits, cache = prefill_fn(params, pf_batch, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    t1 = time.time()
    out = [tok]
    for i in range(args.gen - 1):
        pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
        logits, cache = decode_fn(params, cache, {"token": tok, "pos": pos})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t2 = time.time()
    gen = jnp.stack(out, 1)
    print(f"arch={model.cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill: {t1-t0:.2f}s; decode: {(t2-t1)/max(args.gen-1,1)*1000:.1f} ms/token")
    print("first sequences:", gen[:2].tolist())


if __name__ == "__main__":
    main()
