"""k-means assignment — Pallas kernel for the server clustering step
(paper Eq. 2 inner loop): squared-distance expansion on the MXU + argmin.

Grid over client blocks; the centroid matrix (K small) is replicated into
VMEM for every block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, a_ref, d_ref):
    x = x_ref[...].astype(jnp.float32)               # (BN, F)
    c = c_ref[...].astype(jnp.float32)               # (K, F)
    d = (jnp.sum(x * x, -1, keepdims=True) + jnp.sum(c * c, -1)[None]
         - 2.0 * x @ c.T)
    d = jnp.maximum(d, 0.0)
    a_ref[...] = jnp.argmin(d, axis=-1).astype(jnp.int32)
    d_ref[...] = jnp.min(d, axis=-1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x, cents, *, block_n: int = 128, interpret: bool = True):
    """x: (N,F), cents: (K,F) -> (assign (N,) int32, sqdist (N,) f32).
    N % block_n == 0 (pad at call site)."""
    N, F = x.shape
    K = cents.shape[0]
    assert N % block_n == 0
    return pl.pallas_call(
        _kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, F), lambda i: (i, 0)),
            pl.BlockSpec((K, F), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N,), jnp.int32),
            jax.ShapeDtypeStruct((N,), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
    )(x, cents)
