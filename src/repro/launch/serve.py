"""Batched serving driver: prefill-free decode demo with a KV/SSM cache.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models.registry import build_model
from repro.train.steps import make_serve_step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg, max_pos=args.cache_len)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = args.batch, args.cache_len
    tmpl = model.cache_template(B, S)
    cache = {k: jnp.zeros(shape, dtype) for k, (shape, dtype) in tmpl.items()}
    step = jax.jit(make_serve_step(model, None))
    rng = np.random.default_rng(0)
    if cfg.embeddings_input:
        batch = {"embed": jnp.asarray(rng.standard_normal((B, cfg.d_model)) * 0.02,
                                      jnp.bfloat16)}
    else:
        batch = {"token": jnp.asarray(rng.integers(0, cfg.vocab, B), jnp.int32)}
    out_tokens = []
    t0 = time.time()
    for i in range(args.tokens):
        batch["cur_len"] = jnp.asarray(i, jnp.int32)
        logits, cache = step(params, cache, batch)
        nxt = jnp.argmax(logits, axis=-1)
        out_tokens.append(np.asarray(nxt))
        if not cfg.embeddings_input:
            batch["token"] = nxt.astype(jnp.int32)
    dt = time.time() - t0
    toks = np.stack(out_tokens, axis=1)
    print(f"[serve] {cfg.name}: {args.tokens} tokens x batch {B} in {dt:.2f}s "
          f"({args.tokens*B/dt:.1f} tok/s on CPU, reduced config)")
    print("[serve] sample:", toks[0][:16].tolist())
    return {"tokens": toks, "seconds": dt}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
