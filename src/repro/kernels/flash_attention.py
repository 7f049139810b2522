"""Block flash attention (forward) — Pallas TPU kernel with GQA support.

Online-softmax over key blocks held in VMEM; grid (B*H, Tq/BQ, Sk/BK) with
the key axis innermost so the (m, l, acc) scratch carries across key blocks.
Causal masking is right-aligned (query t attends key s iff s <= t + S - T),
so the same kernel serves prefill (T == S) and windowed variants.
GQA: the kv block index map folds the query head onto its kv head, so kv
heads are read once per group without replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc,
               *, scale, nk, bq, bk, T, S, causal, window):
    iq, jk = pl.program_id(1), pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc[...] = jnp.zeros_like(acc[...])

    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
    v = v_ref[0].astype(jnp.float32)
    s = q @ k.T                                       # (BQ, BK)

    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + (S - T)
    cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        mask = cols <= rows
        if window:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    r = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * r + jnp.sum(p, axis=-1)
    acc[...] = acc[...] * r[:, None] + p @ v
    m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _final():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q: (B,H,T,hd); k,v: (B,KVH,S,hd) -> (B,H,T,hd).

    T % block_q == 0 and S % block_k == 0 (pad at call site)."""
    B, H, T, hd = q.shape
    KVH, S = k.shape[1], k.shape[2]
    G = H // KVH
    assert T % block_q == 0 and S % block_k == 0
    nq, nk = T // block_q, S // block_k
    scale = hd ** -0.5

    qf = q.reshape(B * H, T, hd)
    kf = k.reshape(B * KVH, S, hd)
    vf = v.reshape(B * KVH, S, hd)

    def kv_index(bh, iq, jk):
        b, h = bh // H, bh % H
        return (b * KVH + h // G, jk, 0)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, nk=nk, bq=block_q,
                          bk=block_k, T=T, S=S, causal=causal, window=window),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, T, hd)
