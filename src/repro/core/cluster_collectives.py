"""FedSiKD aggregation as TPU collectives.

The paper's server loop (gather all student weights -> mean per cluster ->
mean of cluster means) is mapped onto the ICI torus inside ``shard_map``
over the client axis.  jax 0.8's shard_map does not implement
``psum(..., axis_index_groups=...)`` (NotImplementedError), so the grouped
reductions are expressed as ``all_gather`` + a per-device weighted-row
contraction — the weight matrix IS the grouped-mean operator, and XLA is
free to lower the gather+reduce onto the torus links.  No parameter server,
no point-to-point RPC; this is the hardware-adapted form of Alg. 1 lines
16-18 (DESIGN.md §3).

All helpers are meant to be called INSIDE a shard_map'd function where
``axis_name`` is bound.  The ``packed_*`` variants additionally handle a
local ``pack`` lane axis (several clients per device) and take their
grouped-mean operators as RUNTIME arrays, so per-round participation
changes never trigger a recompile (DESIGN.md §8).  The same contraction
serves every algorithm family: FedSiKD contracts the plan's two-level
cluster row (``RoundPlan.agg_row``), the FedAvg/FedProx baselines contract
a single all-clients example-weighted row (``RoundPlan.example_row``) —
one group spanning every active slot, no cluster structure.  The static
(baked-in-groups) helpers below remain the readable reference form of the
mapping and are exercised directly by tests/examples.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def cluster_groups(assignments: Sequence[int]) -> list[list[int]]:
    """Partition of device indices along the client axis by cluster id."""
    labels = np.asarray(assignments)
    return [np.flatnonzero(labels == k).tolist() for k in np.unique(labels)]


def _intra_matrix(groups: list[list[int]]) -> np.ndarray:
    D = sum(len(g) for g in groups)
    w = np.zeros((D, D), np.float32)
    for g in groups:
        for d in g:
            w[d, list(g)] = 1.0 / len(g)
    return w


def _global_row(groups: list[list[int]]) -> np.ndarray:
    D = sum(len(g) for g in groups)
    K = len(groups)
    row = np.zeros((D,), np.float32)
    for g in groups:
        row[list(g)] = 1.0 / (K * len(g))
    return row


def _weighted_gather(tree, axis_name: str, row_for_device):
    """out = sum_e w[e] * x_e with x_e gathered across the axis.

    ``row_for_device``: (D,) weights, or (D, D) matrix indexed by this
    device's axis position."""
    table = jnp.asarray(row_for_device)

    def leaf(x):
        gathered = jax.lax.all_gather(x.astype(jnp.float32), axis_name)
        if table.ndim == 2:
            w = table[jax.lax.axis_index(axis_name)]
        else:
            w = table
        return jnp.tensordot(w, gathered, axes=1).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def intra_cluster_mean(tree, axis_name: str, groups: list[list[int]]):
    """Per-cluster mean across the client axis (Alg. 1 line 16): after this
    call every device holds the mean over ITS OWN cluster."""
    return _weighted_gather(tree, axis_name, _intra_matrix(groups))


def fedsikd_global_mean(tree, axis_name: str, groups: list[list[int]],
                        *, weighting: str = "uniform"):
    """Two-level FedSiKD mean: (1/K) sum_k (1/|C_k|) sum_{i in C_k} w_i
    (Alg. 1 line 18) — every device ends with the same global model.

    ``weighting="size"`` applies §IV-C.5's |C_k|/N cluster weights instead of
    the literal 1/K; algebraically that collapses to the flat mean over all
    clients (matching ``aggregation.hierarchical_average(weighting="size")``).
    """
    if weighting == "size":
        D = sum(len(g) for g in groups)
        return _weighted_gather(tree, axis_name, np.full((D,), 1.0 / D,
                                                         np.float32))
    if weighting != "uniform":
        raise ValueError(
            f"weighting must be 'uniform' or 'size', got {weighting!r}")
    return _weighted_gather(tree, axis_name, _global_row(groups))


def teacher_sync(tree, axis_name: str, groups: list[list[int]]):
    """Intra-cluster teacher-replica sync (Alg. 1 line 12, mesh-mapped).

    With ``teacher_data="cluster"`` every member device of a cluster carries
    its own copy of the cluster teacher and steps it on its OWN shard; after
    a block of local teacher steps the copies are reconciled to their
    cluster mean, which implements data-parallel teacher training over the
    union of cluster data (DESIGN.md §7).  ``teacher_data="leader"`` needs
    no sync: the packed engine trains each cluster's teacher once, as a row
    of the canonical stacks (``fed/sharded.make_leader_kd_round``).

    Integer leaves (e.g. the Adam step count) are kept per-device rather
    than averaged: a float mean truncated back to int corrupts the count —
    and with it Adam's bias correction — whenever cluster members ran
    unequal step budgets; each device's own count is exact for the steps it
    actually took."""
    synced = intra_cluster_mean(tree, axis_name, groups)
    return jax.tree_util.tree_map(
        lambda orig, new: new if jnp.issubdtype(orig.dtype, jnp.floating)
        else orig, tree, synced)


# -------------------------------------------------- client-packed variants
#
# The packed mesh engine hosts a (pack,) block of clients per device: leaves
# carry a leading local ``pack`` axis inside shard_map, and the global slot
# id of lane l on device d is d * pack + l.  Cluster groups therefore span
# (device, lane) PAIRS, and — because partial participation re-draws the
# groups every round — the grouped-mean operators are RUNTIME arguments
# (jnp arrays built from the RoundPlan, see fed/schedule.py) rather than
# baked-in constants: the jitted round program is reused across rounds with
# different participant subsets at zero recompile cost.

def packed_weighted_gather(tree, axis_name: str, table, *, pack: int):
    """Packed form of ``_weighted_gather``: leaves are (pack, ...) local
    blocks; ``table`` is a traced (S,) row or (S, S) matrix over GLOBAL slot
    ids (S = axis_size * pack).  Each lane contracts its own table row
    against the all-gathered slot stack."""
    table = jnp.asarray(table, jnp.float32)

    def leaf(x):
        g = jax.lax.all_gather(x.astype(jnp.float32), axis_name)   # (D,pack,..)
        g = g.reshape((-1,) + x.shape[1:])                         # (S, ...)
        if table.ndim == 2:
            base = jax.lax.axis_index(axis_name) * pack
            w = jax.lax.dynamic_slice_in_dim(table, base, pack, 0)  # (pack,S)
        else:
            w = jnp.broadcast_to(table[None, :], (pack, table.shape[0]))
        # full f32: the TPU's default precision rounds the operands to
        # bf16, which would round every synced and aggregated parameter
        return jnp.tensordot(w, g, axes=1,
                             precision=jax.lax.Precision.HIGHEST
                             ).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def packed_teacher_sync(tree, axis_name: str, sync_matrix, *, pack: int):
    """``teacher_sync`` over (device, lane) slots with a runtime
    row-stochastic (S, S) operator (``RoundPlan.sync_matrix()``: cluster
    members average over the cluster's active slots, idle slots keep an
    identity row).  Integer leaves (Adam step counts) stay per-slot, exactly
    as in the unpacked ``teacher_sync``."""
    synced = packed_weighted_gather(tree, axis_name, sync_matrix, pack=pack)
    return jax.tree_util.tree_map(
        lambda orig, new: new if jnp.issubdtype(orig.dtype, jnp.floating)
        else orig, tree, synced)


def packed_weighted_mean(tree, axis_name: str, weights, *, pack: int):
    """Global weighted mean over slots with a runtime (S,) weight row
    (``RoundPlan.agg_row()``; weights sum to 1, idle slots weigh 0).  Every
    slot — idle ones included — ends holding the same aggregate, which is
    how the packed engine broadcasts the new global student."""
    return packed_weighted_gather(tree, axis_name, weights, pack=pack)


def fedavg_mean(tree, axis_name: str, num_examples: jax.Array):
    """Example-weighted FedAvg all-reduce: sum_i (d_i/d) w_i.

    ``num_examples`` is this device's client dataset size (scalar)."""
    total = jax.lax.psum(num_examples.astype(jnp.float32), axis_name)
    w = num_examples.astype(jnp.float32) / total

    def leaf(x):
        return jax.lax.psum(x.astype(jnp.float32) * w, axis_name).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def broadcast_from(tree, axis_name: str, src: int, groups: list[list[int]] | None = None):
    """Broadcast leader/teacher weights along the client axis.

    With ``groups``, ``src`` indexes WITHIN each group's device list,
    implementing per-cluster teacher broadcast."""
    if groups is None:
        def leaf(x):
            mask = (jax.lax.axis_index(axis_name) == src).astype(x.dtype)
            return jax.lax.psum(x * mask, axis_name)
        return jax.tree_util.tree_map(leaf, tree)

    D = sum(len(g) for g in groups)
    w = np.zeros((D, D), np.float32)
    for g in groups:
        leader = g[min(src, len(g) - 1)]
        for d in g:
            w[d, leader] = 1.0
    return _weighted_gather(tree, axis_name, w)
