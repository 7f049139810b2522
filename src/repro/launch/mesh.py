"""The federated client-mesh layout and its 1-D mesh.  FUNCTIONS, not
module constants — importing this module must never touch jax device state.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh

CLIENT_AXIS = "clients"               # the federated engines' 1-D mesh axis


def fed_mesh_layout(n_participants: int, *, pack: int = 1,
                    n_devices: int | None = None) -> tuple[int, int]:
    """Client-packed layout: (n_devices, n_slots) hosting ``n_participants``
    clients with ``pack`` client lanes per device (DESIGN.md §8).

    ``n_slots = n_devices * pack`` is the global slot count; slot ``s``
    lives on device ``s // pack``, lane ``s % pack``.  With ``pack > 1``
    the client population can exceed the device count: C = devices x pack
    clients run in one jitted program.
    """
    if pack < 1:
        raise ValueError(f"pack must be >= 1, got {pack}")
    if n_devices is None:
        n_devices = math.ceil(n_participants / pack)
    if n_devices * pack < n_participants:
        raise ValueError(
            f"{n_devices} devices x pack={pack} = {n_devices * pack} slots "
            f"cannot host {n_participants} participants")
    return n_devices, n_devices * pack


def fed_wave_layout(n_participants: int, *, pack: int = 1,
                    n_devices: int | None = None,
                    waves: int | None = None) -> tuple[int, int, int]:
    """Wave-scheduled layout: ``(n_devices, wave_slots, n_waves)`` hosting
    ``n_participants`` clients by streaming them through a FIXED mesh of
    ``wave_slots = n_devices * pack`` slots in ``n_waves`` passes
    (DESIGN.md §15).

    This is the decoupling of the cohort from the mesh: the compiled round
    programs are shaped by ``wave_slots`` alone, so the cohort (and the
    client universe behind it) can grow without a recompile — only
    ``n_waves`` grows.  Defaults reproduce the single-wave legacy layout
    exactly: with ``n_devices=None`` and ``waves=None`` the mesh is sized
    for the whole cohort (``fed_mesh_layout``) and ``n_waves == 1``.
    """
    if pack < 1:
        raise ValueError(f"pack must be >= 1, got {pack}")
    if waves is not None and waves < 1:
        raise ValueError(f"waves must be >= 1, got {waves}")
    if n_devices is not None and n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_devices is None:
        per_wave = (n_participants if waves is None
                    else math.ceil(n_participants / waves))
        n_devices = max(1, math.ceil(per_wave / pack))
    wave_slots = n_devices * pack
    if waves is None:
        waves = max(1, math.ceil(n_participants / wave_slots))
    if wave_slots * waves < n_participants:
        raise ValueError(
            f"{waves} waves x {n_devices} devices x pack={pack} = "
            f"{wave_slots * waves} lanes cannot host {n_participants} "
            "participants")
    return n_devices, wave_slots, waves


def make_fed_client_mesh(n_participants: int, *, pack: int = 1,
                         n_devices: int | None = None) -> Mesh:
    """1-D ``(CLIENT_AXIS,)`` mesh for the packed federated runtime, using
    the first ``fed_mesh_layout(...)`` devices."""
    n_devices, _ = fed_mesh_layout(n_participants, pack=pack,
                                   n_devices=n_devices)
    devs = jax.devices()
    if len(devs) < n_devices:
        raise ValueError(
            f"need {n_devices} devices for {n_participants} clients at "
            f"pack={pack}, have {len(devs)}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
            "before importing jax, or raise pack")
    return Mesh(np.asarray(devs[:n_devices]), (CLIENT_AXIS,))

