"""Fused grouped weighted-mean merge — Pallas kernel for the server's
aggregation epilogue (FedAvg/FedSiKD weighted mean, paper Alg. 1 lines
16-18) WITH the semi-async staleness decay folded in (DESIGN.md §12-§13):

    out = sum_i w_i (1+s_i)^-decay x_i / sum_j w_j (1+s_j)^-decay

Eagerly this is a chain of elementwise ops per model leaf (decay pow,
normalise, N scale-adds); here the decay, the renormalisation, and the
contraction happen in ONE kernel pass over each (N, D) stack of flattened
client leaves.  Grid over D blocks; the (N,) weight/staleness vectors are
replicated into VMEM for every block, and the decayed-weight normalisation
is recomputed per block (N is tiny — clients — so the redundancy is noise
next to touching x once).

``core.aggregation`` routes every weighted merge through this contract —
the Pallas kernel on TPU, an equivalent single jitted jnp contraction on
CPU (interpret-mode Pallas would put a Python interpreter in the hot path).
Oracle: ``kernels.ref.fused_merge_ref`` (tests/test_kernels.py, including
the staleness-decay path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(w_ref, s_ref, x_ref, o_ref, *, decay):
    w = w_ref[...].astype(jnp.float32)               # (1, N)
    s = s_ref[...].astype(jnp.float32)               # (1, N)
    wn = w * (1.0 + s) ** (-decay)
    wn = wn / jnp.sum(wn)                            # pad rows carry w=0
    x = x_ref[...].astype(jnp.float32)               # (N, BD)
    # a 2-D (1, N) @ (N, BD) contraction: Mosaic has no 1-D-operand dot
    o_ref[...] = jnp.dot(wn, x, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("decay", "block_d", "interpret"))
def fused_merge(x, w, s, *, decay: float = 0.0, block_d: int = 512,
                interpret: bool = True):
    """x: (N,D), w: (N,), s: (N,) -> (D,) f32 decayed weighted mean.
    D % block_d == 0 (pad at call site; pad N rows with w=0).  The weights
    and the output travel as (1, N) / (1, D) rows so every block is 2-D."""
    N, D = x.shape
    assert D % block_d == 0
    out = pl.pallas_call(
        functools.partial(_kernel, decay=decay),
        grid=(D // block_d,),
        in_specs=[
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
        name="fused_merge",
    )(w.reshape(1, N), s.reshape(1, N), x)
    return out.reshape(D)
