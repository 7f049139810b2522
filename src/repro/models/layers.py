"""Transformer building blocks: norms, RoPE, GQA / sliding-window / MLA
attention (train, prefill and one-token decode paths), dense MLPs
(SwiGLU / GELU / squared-ReLU) and capacity-based MoE.

Functional style: ``init_*`` builds a param dict (traceable, so
``jax.eval_shape`` gives allocation-free ShapeDtypeStructs),
``*_fwd`` applies it.  Per-layer params are stacked on a leading L axis by the
model wrappers and consumed through ``jax.lax.scan``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ------------------------------------------------------------------- norms
def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"]


# -------------------------------------------------------------------- rope
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                                  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs         # (...,T,hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------- attention
def init_attention(key, cfg: ModelConfig):
    d, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dt),
        "wk": dense_init(ks[1], (d, KVH * hd), dt),
        "wv": dense_init(ks[2], (d, KVH * hd), dt),
        "wo": dense_init(ks[3], (H * hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KVH * hd,), dt)
        p["bv"] = jnp.zeros((KVH * hd,), dt)
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, H, hd)
    k = k.reshape(B, T, KVH, hd)
    v = v.reshape(B, T, KVH, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, *, scale):
    """q: (B,T,H,hd)  k,v: (B,S,KVH,hd); GQA by head-group einsum."""
    B, T, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, T, KVH, G, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v)
    return out.reshape(B, T, H, hd)


def sdpa_blocked(q, k, v, *, scale, causal=True, window: int = 0,
                 offset: int | None = None, block: int = 1024):
    """Double-blocked flash-style attention in pure jnp — the HBM-safe path
    the Pallas kernel implements on TPU, used when (T x S) scores would
    otherwise materialise (hillclimb A take-3: a 32k prefill's f32 scores are
    1.1 TB/device and XLA additionally ALL-REDUCES them).

    Outer scan over query blocks, inner scan over key blocks with online
    max/sum rescaling; peak scores buffer is (B, KVH, G, bq, bk).
    q: (B,T,H,hd); k,v: (B,S,KVH,hd) -> (B,T,H,hd)."""
    B, T, H, hd = q.shape
    S, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    offset = (S - T) if offset is None else offset
    bq = min(block, T)
    bk = min(block, S)
    pq = (-T) % bq
    pk = (-S) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk
    qb = jnp.moveaxis(qp.reshape(B, nq, bq, KVH, G, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, nk, bk, KVH, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, bk, KVH, hd), 1, 0)
    NEG = -1e30

    def outer(_, qi):
        i, qblk = qi                                      # qblk (B,bq,KVH,G,hd)

        def inner(carry, kj):
            m, l, acc = carry
            j, kblk, vblk = kj
            s = jnp.einsum("bqkgh,bskh->bkgqs", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3) \
                + offset
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 4)
            mask = cols < S
            if causal:
                mask &= cols <= rows
                if window:
                    mask &= cols > rows - window
            s = jnp.where(mask, s, NEG)
            m_new = jnp.maximum(m, s.max(-1))
            r = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * r + p.sum(-1)
            acc_new = acc * r[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, bq), NEG, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(inner), (m0, l0, a0),
            (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # (B,KVH,G,bq,hd)
        return None, jnp.moveaxis(out, 3, 1)              # (B,bq,KVH,G,hd)

    _, outs = jax.lax.scan(outer, None, (jnp.arange(nq), qb))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * bq, KVH, G, hd)[:, :T]
    return out.reshape(B, T, H, hd).astype(q.dtype)


def mla_sdpa_blocked(q_nope, q_rope, c_kv, k_rope, k_b, v_b, *, scale,
                     block: int = 1024, causal: bool = True):
    """Flash-MLA in jnp: keys/values are EXPANDED FROM THE LATENT per key
    block inside the scan, so neither the (T,S) scores nor the full
    (B,S,H,nope) key tensor ever materialise.

    q_nope (B,T,H,nope); q_rope (B,T,H,rd); c_kv (B,S,r); k_rope (B,S,rd);
    k_b (r,H,nope); v_b (r,H,vd) -> (B,T,H,vd)."""
    B, T, H, nope = q_nope.shape
    S, r = c_kv.shape[1], c_kv.shape[2]
    vd = v_b.shape[-1]
    bq = min(block, T)
    bk = min(block, S)
    pq, pk = (-T) % bq, (-S) % bk
    qn = jnp.pad(q_nope, ((0, 0), (0, pq), (0, 0), (0, 0)))
    qr = jnp.pad(q_rope, ((0, 0), (0, pq), (0, 0), (0, 0)))
    ck = jnp.pad(c_kv, ((0, 0), (0, pk), (0, 0)))
    kr = jnp.pad(k_rope, ((0, 0), (0, pk), (0, 0)))
    nq, nk = qn.shape[1] // bq, ck.shape[1] // bk
    qnb = jnp.moveaxis(qn.reshape(B, nq, bq, H, nope), 1, 0)
    qrb = jnp.moveaxis(qr.reshape(B, nq, bq, H, qr.shape[-1]), 1, 0)
    ckb = jnp.moveaxis(ck.reshape(B, nk, bk, r), 1, 0)
    krb = jnp.moveaxis(kr.reshape(B, nk, bk, kr.shape[-1]), 1, 0)
    NEG = -1e30
    offset = S - T

    def outer(_, qi):
        i, qn_blk, qr_blk = qi

        def inner(carry, kj):
            m, l, acc = carry
            j, c_blk, kr_blk = kj
            k_blk = jnp.einsum("bsr,rhc->bshc", c_blk, k_b)
            v_blk = jnp.einsum("bsr,rhv->bshv", c_blk, v_b)
            s = (jnp.einsum("bqhc,bshc->bhqs", qn_blk.astype(jnp.float32),
                            k_blk.astype(jnp.float32))
                 + jnp.einsum("bqhr,bsr->bhqs", qr_blk.astype(jnp.float32),
                              kr_blk.astype(jnp.float32))) * scale
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) \
                + offset
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
            mask = cols < S
            if causal:
                mask &= cols <= rows
            s = jnp.where(mask, s, NEG)
            m_new = jnp.maximum(m, s.max(-1))
            sc = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * sc + p.sum(-1)
            acc_new = acc * sc[..., None] + jnp.einsum(
                "bhqs,bshv->bhqv", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, bq), NEG, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, vd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(inner), (m0, l0, a0),
                                      (jnp.arange(nk), ckb, krb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,H,bq,vd)
        return None, jnp.moveaxis(out, 2, 1)               # (B,bq,H,vd)

    _, outs = jax.lax.scan(outer, None, (jnp.arange(nq), qnb, qrb))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * bq, H, vd)[:, :T]
    return out.astype(q_nope.dtype)


def causal_mask(T: int, S: int, *, offset: int = 0, window: int = 0):
    """(T,S) mask: query t attends key s iff s <= t+offset and (window==0 or
    s > t+offset-window)."""
    tq = jnp.arange(T)[:, None] + offset
    ts = jnp.arange(S)[None, :]
    m = ts <= tq
    if window:
        m &= ts > (tq - window)
    return m


def attention_fwd(p, cfg: ModelConfig, x, positions, *, window: int = 0):
    """Full training/prefill attention. Returns (out, (k, v)) — k/v for cache.

    For long sequences (T >= 2*cfg.attn_block) the blocked flash-style path
    avoids materialising (T,T) scores (hillclimb A take-3)."""
    B, T, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    blk = getattr(cfg, "attn_block", 0)
    if blk and T >= 2 * blk:
        out = sdpa_blocked(q, k, v, scale=cfg.hd ** -0.5, causal=True,
                           window=window, block=blk)
    else:
        mask = causal_mask(T, T, window=window)[None, None, None]
        out = _sdpa(q, k, v, mask, scale=cfg.hd ** -0.5)
    return out.reshape(B, T, -1) @ p["wo"], (k, v)


def attention_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                     window: int = 0):
    """One-token decode. x: (B,1,d); cache_k/v: (B,S,KVH,hd); pos: scalar.

    With ``window`` the cache is a rotating buffer of size ``window``
    (S == window) indexed at ``pos % window``; otherwise S is the full
    context and we write at ``pos``."""
    B = x.shape[0]
    S = cache_k.shape[1]
    q, k, v = _qkv(p, cfg, x, jnp.full((B, 1), pos))
    slot = (pos % window) if window else pos
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, slot, axis=1)
    if window:
        valid = (jnp.arange(S) <= pos % window) | (pos >= window)
        mask = valid[None, None, None, None, :]
    else:
        mask = (jnp.arange(S) <= pos)[None, None, None, None, :]
    out = _sdpa(q, cache_k, cache_v, mask, scale=cfg.hd ** -0.5)
    return out.reshape(B, 1, -1) @ p["wo"], (cache_k, cache_v)


# --------------------------------------------------------------------- MLA
def init_mla(key, cfg: ModelConfig):
    """DeepSeek-V2 multi-head latent attention.  KV cache holds only the
    compressed latent c_kv (kv_lora_rank) + shared rope key (qk_rope_dim)."""
    d, H = cfg.d_model, cfg.num_heads
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope_d, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    return {
        "q_a": dense_init(ks[0], (d, qr), dt),
        "q_a_norm": init_rmsnorm(qr, dt),
        "q_b": dense_init(ks[1], (qr, H * (nope + rope_d)), dt),
        "kv_a": dense_init(ks[2], (d, r + rope_d), dt),
        "kv_a_norm": init_rmsnorm(r, dt),
        "k_b": dense_init(ks[3], (r, H * nope), dt),
        "v_b": dense_init(ks[4], (r, H * vd), dt),
        "wo": dense_init(ks[5], (H * vd, d), dt),
    }


def _mla_q(p, cfg, x, positions):
    B, T, _ = x.shape
    H, nope, rope_d = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    qa = rmsnorm(p["q_a_norm"], x @ p["q_a"], cfg.rms_eps)
    q = (qa @ p["q_b"]).reshape(B, T, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    B, T, _ = x.shape
    r, rope_d = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = x @ p["kv_a"]
    c_kv = rmsnorm(p["kv_a_norm"], kv[..., :r], cfg.rms_eps)
    k_rope = apply_rope(kv[..., r:].reshape(B, T, 1, rope_d), positions,
                        cfg.rope_theta)
    return c_kv, k_rope[:, :, 0]  # (B,T,r), (B,T,rope_d)


def mla_fwd(p, cfg: ModelConfig, x, positions):
    """Expanded (training/prefill) MLA.  Returns (out, (c_kv, k_rope)).

    Long sequences take the flash-MLA path: keys/values expand from the
    latent per key block, never materialising (T,S) scores or full
    (B,S,H,nope) keys."""
    B, T, _ = x.shape
    H, nope, vd = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)
    r = cfg.kv_lora_rank
    scale = (nope + cfg.qk_rope_dim) ** -0.5
    blk = getattr(cfg, "attn_block", 0)
    # flash-MLA pays a latent k/v RE-EXPANSION per block in the backward
    # pass; measured break-even is ~8k tokens (§Perf: at T=4096 it REGRESSES
    # compute 3.5x, at T=32k it wins 26x) — hence the higher threshold.
    if blk and T >= 8 * blk:
        out = mla_sdpa_blocked(
            q_nope, q_rope, c_kv, k_rope,
            p["k_b"].reshape(r, H, nope), p["v_b"].reshape(r, H, vd),
            scale=scale, block=blk).reshape(B, T, H * vd)
        return out @ p["wo"], (c_kv, k_rope)
    k_nope = (c_kv @ p["k_b"]).reshape(B, T, H, nope)
    v = (c_kv @ p["v_b"]).reshape(B, T, H, vd)
    scores = (jnp.einsum("bthc,bshc->bhts", q_nope, k_nope)
              + jnp.einsum("bthc,bsc->bhts", q_rope, k_rope)).astype(jnp.float32)
    mask = causal_mask(T, T)[None, None]
    w = jax.nn.softmax(jnp.where(mask, scores * scale, -1e30), -1).astype(x.dtype)
    out = jnp.einsum("bhts,bshv->bthv", w, v).reshape(B, T, H * vd)
    return out @ p["wo"], (c_kv, k_rope)


def mla_decode(p, cfg: ModelConfig, x, cache_c, cache_kr, pos):
    """Absorbed-matrix MLA decode: queries projected into the latent space so
    the 32k cache is only (r + rope_d) wide — the paper-architecture's memory
    win, kept intact on TPU.  cache_c: (B,S,r); cache_kr: (B,S,rope_d)."""
    B = x.shape[0]
    H, nope, vd, r = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(p, cfg, x, jnp.full((B, 1), pos))       # (B,1,H,*)
    c_new, kr_new = _mla_latent(p, cfg, x, jnp.full((B, 1), pos))
    cache_c = jax.lax.dynamic_update_slice_in_dim(cache_c, c_new, pos, axis=1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(cache_kr, kr_new, pos, axis=1)
    # absorb W_uk into q:  q_lat (B,1,H,r)
    k_b = p["k_b"].reshape(r, H, nope)
    q_lat = jnp.einsum("bthc,rhc->bthr", q_nope, k_b)
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat, cache_c)
              + jnp.einsum("bthc,bsc->bhts", q_rope, cache_kr)).astype(jnp.float32)
    scale = (nope + cfg.qk_rope_dim) ** -0.5
    mask = (jnp.arange(cache_c.shape[1]) <= pos)[None, None, None]
    w = jax.nn.softmax(jnp.where(mask, scores * scale, -1e30), -1).astype(x.dtype)
    o_lat = jnp.einsum("bhts,bsr->bthr", w, cache_c)                # (B,1,H,r)
    v_b = p["v_b"].reshape(r, H, vd)
    out = jnp.einsum("bthr,rhv->bthv", o_lat, v_b).reshape(B, 1, H * vd)
    return out @ p["wo"], (cache_c, cache_kr)


# --------------------------------------------------------------------- MLP
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_down": dense_init(ks[2], (f, d), dt)}
    if cfg.activation == "silu":
        p["w_gate"] = dense_init(ks[0], (d, f), dt)
        p["w_up"] = dense_init(ks[1], (d, f), dt)
    else:
        p["w_up"] = dense_init(ks[1], (d, f), dt)
    return p


def mlp_fwd(p, cfg: ModelConfig, x):
    if cfg.activation == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    return h @ p["w_down"]


# --------------------------------------------------------------------- MoE
def init_moe(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    gated = cfg.activation == "silu"
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "w_in": dense_init(ks[1], (E, d, (2 if gated else 1) * f), dt),
        "w_out": dense_init(ks[2], (E, f, d), dt),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[3], cfg, d_ff=cfg.num_shared_experts * f)
    if cfg.moe_dense_residual:
        p["dense"] = init_mlp(ks[4], cfg, d_ff=cfg.d_ff)
    return p


def _expert_ffn(cfg: ModelConfig, w_in, w_out, xs):
    """xs: (E, C, d) -> (E, C, d), batched expert matmuls (MXU-friendly)."""
    h = jnp.einsum("ecd,edf->ecf", xs, w_in)
    if cfg.activation == "silu":
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u
    elif cfg.activation == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _rank_in_expert_cumsum(e_flat: jax.Array, E: int) -> jax.Array:
    """GShard-style slot-major ranking via a (kN, E) one-hot cumsum.

    O(kN*E) memory/compute — the §Perf baseline.  Kept for comparison."""
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)               # (kN,E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    return jnp.sum(pos * onehot, axis=-1)                             # (kN,)


def _rank_in_expert_sort(e_flat: jax.Array, E: int) -> jax.Array:
    """O(kN log kN) sort-based ranking (megablocks-style), no (kN,E) tensor.

    rank of assignment i within its expert = its index inside the
    expert-sorted order minus the start of its expert's run."""
    n = e_flat.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # stable sort by expert keeps slot-major priority identical to cumsum
    _, sort_idx = jax.lax.sort([e_flat, iota], num_keys=1)
    sorted_e = e_flat[sort_idx]
    is_start = jnp.concatenate([jnp.ones((1,), bool),
                                sorted_e[1:] != sorted_e[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, iota, 0))
    pos_sorted = iota - run_start
    return jnp.zeros((n,), jnp.int32).at[sort_idx].set(pos_sorted)


def _flat_dispatch(p, cfg: ModelConfig, xt, gate_vals, idx, capacity,
                   dispatch):
    """Global scatter into one (E*C, d) buffer.  Under SPMD this scatters
    from the token-sharded axis into the expert-sharded buffer — XLA falls
    back to full rematerialisation (replication) of both sides; kept as the
    §Perf hillclimb-A baseline."""
    N, d = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    e_flat = idx.T.reshape(k * N)
    if dispatch == "sort":
        pos = _rank_in_expert_sort(e_flat, E)
    else:
        pos = _rank_in_expert_cumsum(e_flat, E)
    keep = pos < capacity
    flat_slot = jnp.where(keep, e_flat * capacity + pos, E * capacity)  # OOB
    src = jnp.tile(xt, (k, 1))                                          # (kN,d)
    buf = jnp.zeros((E * capacity + 1, d), xt.dtype)
    buf = buf.at[flat_slot].add(src, mode="drop")
    out_e = _expert_ffn(cfg, p["w_in"], p["w_out"],
                        buf[:-1].reshape(E, capacity, d))
    gathered = out_e.reshape(E * capacity, d)[jnp.minimum(flat_slot,
                                                          E * capacity - 1)]
    g = (gate_vals.T.reshape(k * N) * keep).astype(xt.dtype)[:, None]
    return jnp.sum((gathered * g).reshape(k, N, d), axis=0)


def _grouped_dispatch(p, cfg: ModelConfig, xt, gate_vals, idx, capacity,
                      dispatch, G: int):
    """Group-local dispatch (hillclimb A): tokens are split into G groups
    aligned with the data-parallel shards; ranking, capacity and the
    scatter/gather stay GROUP-LOCAL (batched ops with the group axis sharded
    on dp), and the only cross-shard movement is the (E, G*C/G, d) buffer
    transpose — which XLA lowers to an all-to-all instead of replicating the
    whole token tensor.  Capacity is enforced per group (C/G each), the
    standard local-capacity semantics."""
    N, d = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    n = N // G
    c_l = capacity // G
    xg = xt.reshape(G, n, d)
    idx_g = idx.reshape(G, n, k)
    gate_g = gate_vals.reshape(G, n, k)
    e_flat = jnp.swapaxes(idx_g, 1, 2).reshape(G, k * n)      # slot-major
    rank = _rank_in_expert_sort if dispatch == "sort" else \
        _rank_in_expert_cumsum
    pos = jax.vmap(lambda e: rank(e, E))(e_flat)              # (G, kn)
    keep = pos < c_l
    slot = jnp.where(keep, e_flat * c_l + pos, E * c_l)

    def scatter_one(x_one, slot_one):
        src = jnp.tile(x_one, (k, 1))
        buf = jnp.zeros((E * c_l + 1, d), xt.dtype)
        return buf.at[slot_one].add(src, mode="drop")[:-1]

    buf = jax.vmap(scatter_one)(xg, slot)                     # (G, E*c_l, d)
    # group-major -> expert-major: THE all-to-all
    buf = buf.reshape(G, E, c_l, d).transpose(1, 0, 2, 3).reshape(E, G * c_l, d)
    out_e = _expert_ffn(cfg, p["w_in"], p["w_out"], buf)
    back = out_e.reshape(E, G, c_l, d).transpose(1, 0, 2, 3)  # (G, E, c_l, d)
    back = back.reshape(G, E * c_l, d)

    def gather_one(buf_one, slot_one):
        return buf_one[jnp.minimum(slot_one, E * c_l - 1)]    # (kn, d)

    got = jax.vmap(gather_one)(back, slot)                    # (G, kn, d)
    g = (jnp.swapaxes(gate_g, 1, 2).reshape(G, k * n)
         * keep).astype(xt.dtype)[..., None]
    comb = jnp.sum((got * g).reshape(G, k, n, d), axis=1)     # (G, n, d)
    return comb.reshape(N, d)


def moe_fwd(p, cfg: ModelConfig, x, *, capacity: Optional[int] = None,
            dispatch: Optional[str] = None):
    """Capacity-based top-k dispatch into an (E, C, d) expert buffer.

    Returns (out, aux_loss).  Dropped tokens (over capacity) fall back to the
    shared/dense paths plus residual stream.  ``capacity=None`` uses the
    training capacity factor; decode passes ``capacity=N`` (no drops — a
    single-token step must be deterministic w.r.t. batching).

    ``dispatch`` selects the position-in-expert ranking: "sort" (default;
    O(kN) memory) or "cumsum" (GShard one-hot baseline, O(kN*E) — the §Perf
    before-state).  Both produce identical slot-major priority.
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    N = B * T
    xt = x.reshape(N, d)
    logits = (xt.astype(jnp.float32) @ p["router"])                  # (N,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                         # (N,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = capacity or max(1, int(N * k / E * cfg.capacity_factor))
    dispatch = dispatch or getattr(cfg, "moe_dispatch", "sort")
    groups = getattr(cfg, "moe_groups", 1)
    if groups > 1 and N % groups == 0 and capacity % groups == 0:
        combined = _grouped_dispatch(p, cfg, xt, gate_vals, idx,
                                     capacity, dispatch, groups)
    else:
        combined = _flat_dispatch(p, cfg, xt, gate_vals, idx, capacity,
                                  dispatch)

    out = combined
    if "shared" in p:
        out = out + mlp_fwd(p["shared"], cfg, xt)
    if "dense" in p:
        out = out + mlp_fwd(p["dense"], cfg, xt)

    # load-balance auxiliary loss (Switch/GShard): E * sum_e f_e * p_e
    me = probs.mean(axis=0)                                            # (E,)
    ce = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32).mean(axis=0)
    aux = E * jnp.sum(me * ce) * cfg.router_aux_coef
    return out.reshape(B, T, d), aux
