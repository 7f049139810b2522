"""Serve a reduced model with batched requests: prefill (returns last logits
+ KV cache) then greedy decode continuation (``launch/steps.py``'s prefill
and decode steps).

  PYTHONPATH=src python examples/serve.py [arch]
"""
import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import transformer as tf


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "rwkv6-3b"
    cfg = dataclasses.replace(get_config(arch, smoke=True), remat=False)
    if cfg.arch_type == "audio":
        raise SystemExit("use a decoder-only arch for this example")
    B, PROMPT, GEN = 4, 24, 16
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                 cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.prefix_len:
        batch["prefix"] = jnp.zeros((B, cfg.prefix_len, cfg.d_model),
                                    jnp.dtype(cfg.dtype))

    t0 = time.time()
    last, cache = tf.prefill(params, cfg, batch)
    # grow full-attention caches to hold the generated continuation
    def grow(a):
        if a.ndim == 5 and a.shape[2] == PROMPT:
            return jnp.pad(a, ((0, 0), (0, 0), (0, GEN), (0, 0), (0, 0)))
        if a.ndim == 4 and a.shape[2] == PROMPT:
            return jnp.pad(a, ((0, 0), (0, 0), (0, GEN), (0, 0)))
        return a
    if cfg.arch_type in ("dense", "moe", "vlm") and not cfg.sliding_window:
        cache = jax.tree_util.tree_map(grow, cache)
    print(f"{arch}: prefilled {B}x{PROMPT} tokens in {time.time()-t0:.1f}s "
          f"(cache leaves: {len(jax.tree_util.tree_leaves(cache))})")

    dstep = jax.jit(functools.partial(tf.decode_step, params, cfg))
    tok = jnp.argmax(last, -1)[:, None]
    out = [tok]
    t0 = time.time()
    for t in range(PROMPT, PROMPT + GEN - 1):
        logits, cache = dstep(cache, tok, t)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        out.append(tok)
    gen = jnp.concatenate(out, axis=1)
    dt = (time.time() - t0) / (GEN - 1) * 1e3
    print(f"generated {GEN} tokens/request greedily "
          f"({dt:.0f} ms/token on CPU); sample row: {gen[0][:10].tolist()}")


if __name__ == "__main__":
    main()
