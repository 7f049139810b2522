"""Async checkpoint writer invariants (fed/fedstate.AsyncCheckpointWriter,
DESIGN.md §13): same bytes as the sync path, atomic publish (a kill at any
moment leaves only complete ``round_NNNNN.npz`` files), bounded queue with
backpressure (never drop), FIFO publishes + ``flush()`` barrier,
snapshot-on-submit, loud error propagation.

The writer itself is mesh-free (plain numpy pytrees), so most tests run
in-process; the kill test SIGKILLs a real writer subprocess mid-stream.
"""
import filecmp
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.fed import fedstate


def _state(rnd: int, *, size: int = 64) -> fedstate.FedState:
    rng = np.random.default_rng(rnd)
    return fedstate.FedState(
        round_index=rnd,
        arrays={"student": {"w": rng.normal(size=(size, size)).astype(
            np.float32)}},
        history={"loss": [float(i) for i in range(rnd)]},
        meta={"seed": 0, "round": rnd})


def test_async_writer_same_bytes_as_sync(tmp_path):
    sync_dir, async_dir = tmp_path / "sync", tmp_path / "async"
    w = fedstate.AsyncCheckpointWriter(async_dir)
    for rnd in (1, 2, 3):
        s = _state(rnd)
        fedstate.save_round(sync_dir, s)
        w.submit(s)
    w.flush()
    w.close()
    files = sorted(os.listdir(sync_dir))
    assert files == sorted(os.listdir(async_dir)) and files
    for f in files:
        assert filecmp.cmp(sync_dir / f, async_dir / f, shallow=False), f


def test_flush_barrier_fifo_and_keep_last(tmp_path):
    w = fedstate.AsyncCheckpointWriter(tmp_path, keep_last=2)
    for rnd in range(1, 6):
        w.submit(_state(rnd))
    w.flush()                       # barrier: everything submitted is on disk
    assert fedstate.latest_round(tmp_path) == 5
    npz = sorted(p for p in os.listdir(tmp_path) if p.endswith(".npz"))
    assert npz == ["round_00004.npz", "round_00005.npz"]   # FIFO pruning
    w.close()


def test_backpressure_bounded_queue_never_drops(tmp_path):
    # max_pending=1 forces submit() to block on the in-flight write; every
    # submitted round must still be published (none dropped)
    w = fedstate.AsyncCheckpointWriter(tmp_path, max_pending=1)
    for rnd in range(1, 9):
        w.submit(_state(rnd, size=128))
    w.close()                       # close() flushes
    published = sorted(int(p[6:11]) for p in os.listdir(tmp_path)
                       if p.endswith(".npz"))
    assert published == list(range(1, 9))


def test_history_snapshotted_on_submit(tmp_path):
    w = fedstate.AsyncCheckpointWriter(tmp_path)
    s = _state(3)
    w.submit(s)
    s.history["loss"].append(999.0)     # caller mutates after submit
    w.close()
    meta = fedstate.latest_meta(tmp_path)
    assert meta["history"]["loss"] == [0.0, 1.0, 2.0]   # pre-mutation copy


def test_write_error_raises_on_next_call(tmp_path):
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file, not a directory")
    w = fedstate.AsyncCheckpointWriter(blocker)
    w.submit(_state(1))
    with pytest.raises(RuntimeError, match="async checkpoint writer"):
        w.flush()
    w.close()                           # error already surfaced; close is clean


def test_submit_after_close_raises(tmp_path):
    w = fedstate.AsyncCheckpointWriter(tmp_path)
    w.close()
    with pytest.raises(RuntimeError, match="close"):
        w.submit(_state(1))


def test_partial_tmp_file_invisible_to_resume(tmp_path):
    """A kill between temp-write and ``os.replace`` leaves a ``.tmp`` the
    resume path must ignore: ``latest_round`` sees only published rounds."""
    w = fedstate.AsyncCheckpointWriter(tmp_path)
    w.submit(_state(1))
    w.submit(_state(2))
    w.close()
    (tmp_path / "round_00003.npz.tmp").write_bytes(b"half a checkpoint")
    (tmp_path / "round_00003.meta.json.tmp").write_bytes(b"{")
    assert fedstate.latest_round(tmp_path) == 2
    got = fedstate.restore_run(tmp_path, _state(2).arrays)
    assert got.round_index == 2
    np.testing.assert_array_equal(got.arrays["student"]["w"],
                                  _state(2).arrays["student"]["w"])


_KILL_CHILD = """
import sys
import numpy as np
from repro.fed import fedstate

d = sys.argv[1]
w = fedstate.AsyncCheckpointWriter(d)
rng = np.random.default_rng(0)
rnd = 0
print("READY", flush=True)
while True:                      # stream checkpoints until SIGKILLed
    rnd += 1
    w.submit(fedstate.FedState(
        round_index=rnd,
        arrays={"w": rng.normal(size=(256, 256)).astype(np.float32)},
        history={"loss": [0.0] * rnd}))
"""


def test_sigkill_mid_stream_leaves_only_complete_checkpoints(tmp_path):
    from _subproc import ENV
    p = subprocess.Popen([sys.executable, "-c", _KILL_CHILD, str(tmp_path)],
                         stdout=subprocess.PIPE, text=True, env=ENV)
    try:
        assert p.stdout.readline().strip() == "READY"
        deadline = time.time() + 30
        while not any(f.endswith(".npz") for f in os.listdir(tmp_path)):
            assert time.time() < deadline, "no checkpoint appeared in 30s"
            time.sleep(0.05)
        time.sleep(0.3)              # let a few more rounds into flight
    finally:
        p.kill()                     # SIGKILL: no atexit, no flush
        p.wait()
    published = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert published, "writer published nothing before the kill"
    # every PUBLISHED npz/meta pair must be complete and loadable — partial
    # writes may only ever exist under .tmp names
    for f in published:
        with np.load(tmp_path / f) as z:
            assert z["w"].shape == (256, 256)
        meta = json.loads(
            (tmp_path / f.replace(".npz", ".meta.json")).read_text())
        assert meta["step"] == int(f[6:11])
    assert fedstate.latest_round(tmp_path) == int(published[-1][6:11])
