"""Serving CLI: prefill a batch of prompts, then greedy-decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \\
        --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    from repro.configs import get_arch, reduce_for_smoke
    from repro.launch.cache import use_compile_cache
    from repro.models import build_model

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.gen + (cfg.num_patch_tokens or 0)

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32), "max_len": max_len}
    if cfg.num_patch_tokens:
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.encoder_seq, cfg.d_model)),
            jnp.bfloat16)

    t0 = time.time()
    logits, cache = model.prefill(params, batch)
    print(f"prefill: {args.batch}x{args.prompt_len} in {time.time()-t0:.2f}s")

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    seqs = np.stack([np.asarray(t) for t in out], axis=1)
    print(f"decoded {args.gen} tokens/seq in {dt:.2f}s "
          f"({args.batch * args.gen / max(dt, 1e-9):.1f} tok/s)")
    print("first sequence:", seqs[0].tolist())


if __name__ == "__main__":
    main()
