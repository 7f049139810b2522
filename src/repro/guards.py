"""Runtime sanitizers for the packed runtime (DESIGN.md §14).

Static analysis (``tools/fedlint``) proves invariants about the CODE; this
module turns the runtime halves of the same claims into executable
assertions, all enabled together by ``FedConfig.guards``:

* ``no_implicit_transfers()`` — wraps jax's thread-local
  ``transfer_guard("disallow")``: any implicit host->device transfer
  inside the block (a numpy array or Python scalar silently fed to a
  jitted program) raises instead of quietly re-staging a copy every
  round.  The hot path must stage through ``WaveStager`` / explicit
  ``jax.device_put`` — this guard is what makes "must" mean something.
  (``jnp.asarray`` does NOT count as explicit: its transfer is async and
  the guard fires when the result is consumed.)
* compile sentinel — ``install()`` registers a process-wide
  ``jax.monitoring`` listener counting compilation events;
  ``compile_count()`` snapshots the counter and
  ``assert_no_new_compiles()`` turns the "steady state never recompiles"
  claims (fixed slot layout, fixed-shape semi-async merges) into hard
  errors carrying the compile delta.  Executing an already-compiled
  program emits no event, so the counter moves only on real (re)compiles.
* ``leak_check()`` — asserts the live-device-array count returns to its
  baseline across a block (catches donated-buffer leaks and stale
  references pinning whole model stacks).

Thread-locality: the transfer guard is thread-local, so the async
checkpoint writer's device->host pulls on its own thread are unaffected
by a guard on the driver thread.  The compile counter is process-global
on purpose — a recompile is a regression no matter which thread asks.

* schedule-jitter race harness (DESIGN.md §16) — ``enable_jitter(seed)``
  arms ``jitter_point(tag)`` call sites threaded through every
  thread-handoff edge of the overlap machinery (stager prefetch workers,
  the wave LRU, the async checkpoint writer).  Each call sleeps a small,
  DETERMINISTIC duration derived from ``(seed, tag, per-tag counter)``,
  forcing adversarial interleavings — prefetch completing before/after
  the consuming ``stage``, checkpoint writes straddling round
  boundaries — without any randomness across runs.  Correctness claim
  under test: histories are bitwise identical with jitter on vs. off,
  because threads only ever overlap *timing*, never sources of truth.
  Off by default; ``jitter_point`` is a no-op (one dict lookup) unless
  ``FedConfig.guards == "jitter"`` armed it.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import threading
import time

import jax


class GuardError(RuntimeError):
    """A runtime invariant (recompile / leak) was violated under guards."""


_lock = threading.Lock()
_installed = False
_compiles = 0


def _on_event(event: str, **kwargs) -> None:
    # one event per actual trace+lower+compile; cache hits are silent
    if "compile" in event:
        global _compiles
        with _lock:
            _compiles += 1


def install() -> None:
    """Idempotently register the compile-event listener.

    jax.monitoring offers registration but no deregistration, so the
    listener is installed once per process and left in place; it is a
    counter bump, invisible when no sentinel is checking it.
    """
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_listener(_on_event)


def compile_count() -> int:
    """Compilations observed since ``install()`` (monotonic snapshot)."""
    with _lock:
        return _compiles


def assert_no_new_compiles(baseline: int, context: str = "") -> None:
    current = compile_count()
    if current > baseline:
        where = f" during {context}" if context else ""
        raise GuardError(
            f"compile sentinel: {current - baseline} recompilation(s)"
            f"{where} — the steady state must reuse round-0 programs "
            "(a shape, dtype, or static-arg changed under jit)")


@contextlib.contextmanager
def no_new_compiles(context: str = ""):
    """Assert zero jit compilations happen inside the block."""
    install()
    baseline = compile_count()
    yield
    assert_no_new_compiles(baseline, context)


@contextlib.contextmanager
def no_implicit_transfers():
    """Fail on implicit host->device transfers inside the block.

    The explicit escapes (``jax.device_put``, ``jax.device_get``) stay
    allowed — the guard rejects the silent coercions that hide a
    per-round host round-trip: numpy/Python arguments to jitted calls,
    ``jnp`` scalar constructors, eager dtype promotion, and
    ``jnp.asarray`` (whose async transfer surfaces at consumption).

    Only the host->device direction is guarded: device->device resharding
    (a committed array spreading onto the mesh) and device->host metric
    pulls are how staged data legitimately moves each round.
    """
    with jax.transfer_guard_host_to_device("disallow"):
        yield


# ------------------------------------------------------ schedule jitter
_jitter_seed: int | None = None
_jitter_counts: dict[str, int] = {}
_JITTER_MAX_S = 0.02    # longest injected sleep; enough to flip any race


def enable_jitter(seed: int) -> None:
    """Arm the race harness: every ``jitter_point`` sleeps a deterministic
    amount derived from ``(seed, tag, firing index)``."""
    global _jitter_seed
    with _lock:
        _jitter_seed = int(seed)
        _jitter_counts.clear()


def disable_jitter() -> None:
    global _jitter_seed
    with _lock:
        _jitter_seed = None
        _jitter_counts.clear()


def jitter_enabled() -> bool:
    with _lock:
        return _jitter_seed is not None


def jitter_point(tag: str) -> None:
    """A named thread-handoff edge.  No-op unless ``enable_jitter`` armed
    the harness; armed, it sleeps 0..20ms chosen by hashing ``(seed, tag,
    n-th firing of this tag)`` — the schedule is adversarial (every edge
    gets stretched differently every time) yet exactly reproducible."""
    with _lock:
        if _jitter_seed is None:
            return
        n = _jitter_counts.get(tag, 0)
        _jitter_counts[tag] = n + 1
        key = f"{_jitter_seed}:{tag}:{n}".encode()
    h = int.from_bytes(hashlib.blake2b(key, digest_size=4).digest(), "big")
    time.sleep((h % 1024) / 1024.0 * _JITTER_MAX_S)


@contextlib.contextmanager
def leak_check(allow: int = 0, context: str = ""):
    """Assert the live-device-array population grows by <= ``allow``."""
    gc.collect()
    before = len(jax.live_arrays())
    yield
    gc.collect()
    grown = len(jax.live_arrays()) - before
    if grown > allow:
        where = f" during {context}" if context else ""
        raise GuardError(
            f"leak check: {grown} device array(s) leaked{where} "
            f"(allowed {allow}) — a donated or per-round buffer is being "
            "pinned across rounds")
