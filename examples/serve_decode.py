"""Serve a small LM with batched requests: prefill + token-by-token decode
through the production cache machinery (ring buffers, GQA caches).

  PYTHONPATH=src python examples/serve_decode.py --arch qwen3-0.6b --tokens 32
(arch resolves to its reduced smoke variant so this runs on CPU in seconds)
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, smoke_config
from repro.data.tokens import synthetic_lm_batch
from repro.models import transformer as tf


def main():
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4, help="concurrent requests")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32, help="tokens to generate")
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    B = args.batch
    batch = synthetic_lm_batch(cfg.vocab_size, B, args.prompt_len, seed=0)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(1), (B, cfg.num_audio_frames, cfg.d_model)) * 0.1

    capacity = args.prompt_len + args.tokens
    enc_len = cfg.num_audio_frames if cfg.is_encoder_decoder else 0
    caches = tf.init_caches(cfg, B, capacity, enc_len=enc_len)
    if cfg.is_encoder_decoder:
        caches = tf._fill_cross_caches(cfg, params, batch, caches)

    step = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t))

    # prefill by teacher-forced ingestion (reference path; production prefill
    # is the forward lowering in launch/steps.py)
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, caches = step(params, caches, batch["tokens"][:, t : t + 1])
    prefill_s = time.time() - t0

    # greedy decode
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(args.tokens - 1):
        logits, caches = step(params, caches, tok)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    decode_s = time.time() - t0
    gen = np.concatenate([np.asarray(t) for t in out], axis=1)

    print(f"arch={cfg.name} (reduced) | {B} requests | prompt {args.prompt_len} | "
          f"generated {args.tokens}")
    print(f"prefill: {prefill_s:.2f}s   decode: {decode_s:.2f}s "
          f"({B * (args.tokens - 1) / max(decode_s, 1e-9):.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"request {b}: {gen[b][:16].tolist()} ...")


if __name__ == "__main__":
    main()
