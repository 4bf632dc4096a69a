"""Serving example: batched autoregressive decoding with a KV cache on a
reduced assigned architecture, including the sliding-window long-context
path used by the ``long_500k`` dry-run shape.

    PYTHONPATH=src python examples/serve_decode.py --arch yi-6b --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import get_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models.model import build_memory
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--rolling", action="store_true",
                    help="sliding-window cache (long-context serving path)")
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    print(f"serving reduced {args.arch}: {cfg.num_layers}L d={cfg.d_model} "
          f"family={cfg.family}")
    rng = jax.random.PRNGKey(0)
    params = init_params(cfg, rng)

    batch = {"tokens": jax.random.randint(rng, (args.batch, args.prompt_len),
                                          0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["image_embed"] = jnp.zeros(
            (args.batch, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["audio_embed"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)

    total = args.prompt_len + args.tokens
    cache_len = cfg.sliding_window_serve if args.rolling else total
    memory = build_memory(cfg, params, batch)

    # prefill the prompt token by token into a fixed cache (simple server)
    cache = init_cache(cfg, args.batch, cache_len, jnp.bfloat16)
    step = jax.jit(lambda p, t, i, c: decode_step(
        cfg, p, t, i, c, memory, rolling=args.rolling))
    tok = batch["tokens"][:, :1]
    t0 = time.time()
    generated = []
    for i in range(total - 1):
        logits, cache = step(params, tok, jnp.int32(i), cache)
        if i + 1 < args.prompt_len:
            tok = batch["tokens"][:, i + 1:i + 2]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            generated.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(generated, axis=1)
    print(f"generated {gen.shape[1]} tokens x{args.batch} "
          f"in {dt:.1f}s ({gen.shape[1]*args.batch/dt:.1f} tok/s on CPU)")
    print("sample:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
