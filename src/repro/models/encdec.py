"""Encoder-decoder backbone for seamless-m4t (audio).  The mel/conv audio
frontend is a STUB per the assignment carve-out: the encoder consumes
precomputed frame embeddings (B, F, d_model) from ``input_specs``.

Encoder: bidirectional self-attn blocks.  Decoder: causal self-attn +
cross-attn over encoder memory + MLP.  Scan-over-layers throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as ly
from repro.models.transformer import _logits, _maybe_remat, _stack_init


def _init_enc_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln1": ly.init_rmsnorm(cfg.d_model, dt),
        "attn": ly.init_attention(ks[0], cfg),
        "ln2": ly.init_rmsnorm(cfg.d_model, dt),
        "mlp": ly.init_mlp(ks[1], cfg),
    }


def _init_dec_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln1": ly.init_rmsnorm(cfg.d_model, dt),
        "self_attn": ly.init_attention(ks[0], cfg),
        "ln_x": ly.init_rmsnorm(cfg.d_model, dt),
        "cross_attn": ly.init_attention(ks[1], cfg),
        "ln2": ly.init_rmsnorm(cfg.d_model, dt),
        "mlp": ly.init_mlp(ks[2], cfg),
    }


def init_encdec(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    return {
        "embed": ly.dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
        "lm_head": ly.dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dt),
        "ln_f": ly.init_rmsnorm(cfg.d_model, dt),
        "encoder": _stack_init(ks[2], cfg.num_encoder_layers,
                               lambda k: _init_enc_block(k, cfg)),
        "decoder": _stack_init(ks[3], cfg.num_layers,
                               lambda k: _init_dec_block(k, cfg)),
    }


def _cross_attention(p, cfg: ModelConfig, x, memory, positions_q):
    """Decoder->encoder attention; no causal mask, no RoPE on memory keys
    beyond its own encoding (standard enc-dec)."""
    B, T, _ = x.shape
    S = memory.shape[1]
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (memory @ p["wk"]).reshape(B, S, KVH, hd)
    v = (memory @ p["wv"]).reshape(B, S, KVH, hd)
    mask = jnp.ones((1, 1, 1, T, S), bool)
    out = ly._sdpa(q, k, v, mask, scale=hd ** -0.5)
    return out.reshape(B, T, -1) @ p["wo"]


def encode(p, cfg: ModelConfig, frames: jax.Array):
    """frames: (B,F,d) stub embeddings -> encoder memory (B,F,d)."""
    B, F, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(F), (B, F))
    x = frames.astype(jnp.dtype(cfg.dtype))

    def body(x, lp):
        h = ly.rmsnorm(lp["ln1"], x, cfg.rms_eps)
        q, k, v = ly._qkv(lp["attn"], cfg, h, positions)
        mask = jnp.ones((1, 1, 1, F, F), bool)          # bidirectional
        a = ly._sdpa(q, k, v, mask, scale=cfg.hd ** -0.5)
        x = x + a.reshape(B, F, -1) @ lp["attn"]["wo"]
        x = x + ly.mlp_fwd(lp["mlp"], cfg, ly.rmsnorm(lp["ln2"], x, cfg.rms_eps))
        return x, 0.0

    x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, p["encoder"])
    return x


def forward(p, cfg: ModelConfig, batch: dict):
    """batch: {"frames": (B,F,d), "tokens": (B,T), "labels": (B,T)}."""
    memory = encode(p, cfg, batch["frames"])
    x = p["embed"][batch["tokens"]]
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))

    def body(x, lp):
        h = ly.rmsnorm(lp["ln1"], x, cfg.rms_eps)
        a, _ = ly.attention_fwd(lp["self_attn"], cfg, h, positions)
        x = x + a
        h = ly.rmsnorm(lp["ln_x"], x, cfg.rms_eps)
        x = x + _cross_attention(lp["cross_attn"], cfg, h, memory, positions)
        x = x + ly.mlp_fwd(lp["mlp"], cfg, ly.rmsnorm(lp["ln2"], x, cfg.rms_eps))
        return x, 0.0

    x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, p["decoder"])
    return _logits(p, cfg, x), jnp.float32(0.0)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, frames: int,
               dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    L, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.hd
    return {
        "memory": jnp.zeros((batch, frames, cfg.d_model), dt),
        "k": jnp.zeros((L, batch, cache_len, kvh, hd), dt),
        "v": jnp.zeros((L, batch, cache_len, kvh, hd), dt),
    }


def decode_step(p, cfg: ModelConfig, cache, tokens, pos):
    """One decoder token; encoder memory precomputed in the cache."""
    x = p["embed"][tokens]
    B = x.shape[0]
    memory = cache["memory"]
    positions = jnp.full((B, 1), pos)

    def body(x, sc):
        lp, ck, cv = sc
        h = ly.rmsnorm(lp["ln1"], x, cfg.rms_eps)
        a, (nk, nv) = ly.attention_decode(lp["self_attn"], cfg, h, ck, cv, pos)
        x = x + a
        h = ly.rmsnorm(lp["ln_x"], x, cfg.rms_eps)
        x = x + _cross_attention(lp["cross_attn"], cfg, h, memory, positions)
        x = x + ly.mlp_fwd(lp["mlp"], cfg, ly.rmsnorm(lp["ln2"], x, cfg.rms_eps))
        return x, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x, (p["decoder"], cache["k"], cache["v"]))
    new = {"memory": memory, "k": nk, "v": nv}
    return _logits(p, cfg, x), new


def lm_loss(p, cfg: ModelConfig, batch: dict):
    logits, _ = forward(p, cfg, batch)
    labels = batch["labels"]
    logf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logf, axis=-1)
    picked = jnp.take_along_axis(logf, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1.0)
    return ce, {"ce": ce}
