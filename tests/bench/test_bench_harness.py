"""The harness's own arithmetic and wiring, without a run: the work of the
measured rounds from their plans, the count of rounds with outputs that
are not finite, the order in which the traffic's split and a planted fault
reach the strategy's ``setup``, and a dataset's module found by name."""
import dataclasses
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import faults, harness, models  # noqa: E402


def _plan(clients, n_slots):
    slot = np.full(n_slots, -1)
    slot[:len(clients)] = clients
    return SimpleNamespace(slot_client=slot, active=slot >= 0,
                           n_slots=n_slots)


def test_work_counts_real_rows_steps_and_share():
    sizes = np.array([100, 64, 30])
    plan = _plan([0, 1, 2], 4)
    w = harness.work([plan, plan], sizes, batch_size=64, epochs=2)
    assert w["client_rows"] == [388, 388]
    assert w["steps"] == 4                       # ceil(100 / 64) * 2
    assert w["real_share"] == 776 / (2 * 4 * 4 * 64)


def test_work_maps_virtual_clients_onto_base_shards():
    w = harness.work([_plan([5], 1)], np.array([10, 20, 30]), 8, 1)
    assert w["client_rows"] == [30] and w["steps"] == 4


def test_count_failed_reads_every_value():
    outs = [{"a": 1.0, "b": np.float32(2.0)}, {"a": float("nan")},
            {"a": 1.0, "b": np.inf}]
    assert harness.count_failed(outs) == 2
    assert harness.count_failed([]) == 0


@dataclasses.dataclass
class _Shard:
    client_id: int
    x: np.ndarray
    y: np.ndarray


def test_split_reaches_a_fault_planted_on_setup():
    alg = SimpleNamespace(setup=lambda ds, shards, cfg, key: shards)
    faults.plant_half_batch(alg)
    mine = [_Shard(0, np.zeros((4, 1)), np.arange(4))]
    harness.use_split(alg, mine)
    got = alg.setup(None, ["the driver's split"], None, None)
    assert [s.client_id for s in got] == [0]
    assert got[0].y.tolist() == [-1, 1, -1, 3]
    assert mine[0].y.tolist() == [0, 1, 2, 3]   # the split itself is kept


def test_dataset_module_found_by_name():
    mod = models.of({"dataset": {"name": "mnist"}})
    xt, yt, xv, yv = mod.twin(7, 40, 20)
    assert xt.shape == (40, 28, 28, 1) and xv.shape == (20, 28, 28, 1)
    assert np.bincount(yt).tolist() == [4] * 10
    assert np.bincount(yv).tolist() == [2] * 10
