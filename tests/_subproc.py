"""Shared harness for tests that need their own XLA host-device count.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set before
jax is imported, so mesh tests run their scripts in a subprocess.  The
tests run on the CPU, which the stripped environment states with
``JAX_PLATFORMS=cpu``; the TPU is reached only by ``chip_smoke.py``.
``HOME`` and ``TMPDIR`` pass through, so the subprocess stays inside the
caller's sandbox.
"""
import os
import subprocess
import sys

ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
       "JAX_PLATFORMS": "cpu",
       **{k: os.environ[k] for k in ("HOME", "TMPDIR") if k in os.environ}}


def run_script(script: str, *, timeout: int = 580) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=timeout, env=ENV)
