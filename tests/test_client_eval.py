"""The staged test-set eval (fed/client.py): the set goes to the device once
per strategy, and ``evaluate`` runs one program and makes one read.  The
per-batch host loop it replaced is kept here as the reference."""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _subproc import run_script

from repro import guards, perf
from repro.core.distill import softmax_cross_entropy
from repro.fed.client import evaluate, make_steps, stage_test_set
from repro.models.cnn import make_model
from repro.optim import adamw


def teardown_function(_fn):
    perf.disable()


def _loop_evaluate(fwd, params, x, y, batch_size=256):
    """The per-batch loop: slice, transfer, dispatch, read, per batch."""
    @jax.jit
    def eval_batch(params, x, y):
        logits = fwd(params, x, train=False, key=None)
        acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return acc, softmax_cross_entropy(logits, y)

    accs, losses = [], []
    for s in range(0, len(y), batch_size):
        a, l = eval_batch(params, x[s:s + batch_size], y[s:s + batch_size])
        n = len(y[s:s + batch_size])
        accs.append(float(a) * n)
        losses.append(float(l) * n)
    return sum(accs) / len(y), sum(losses) / len(y)


def _model(seed=0):
    init, fwd = make_model("mnist", student=True)
    return init, fwd, make_steps(fwd, adamw(1e-3))["eval"], init(
        jax.random.PRNGKey(seed))


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.uniform(0, 1, (n, 28, 28, 1)).astype(np.float32),
            rng.integers(0, 10, n).astype(np.int32))


@pytest.mark.parametrize("n", [1000, 1024])
def test_staged_eval_matches_the_batch_loop(n):
    """A partial last batch (1,000 rows: 3 x 256 + 232) and a whole number
    of batches (1,024): same rows, same per-row weights; only the order of
    the float32 sums differs."""
    _init, fwd, eval_set, params = _model()
    x, y = _data(n)
    staged = stage_test_set(x, y)
    assert staged[0].shape == (-(-n // 256), 256, 28, 28, 1)
    assert int((np.asarray(staged[1]) >= 0).sum()) == n
    acc, loss = evaluate(eval_set, params, staged)
    ref_acc, ref_loss = _loop_evaluate(fwd, params, x, y)
    assert acc == pytest.approx(ref_acc, rel=1e-6)
    assert loss == pytest.approx(ref_loss, rel=1e-6)


def test_staging_pads_with_masked_rows():
    x, y = _data(300)
    xs, ys = (np.asarray(a) for a in stage_test_set(x, y))
    assert xs.shape == (2, 256, 28, 28, 1) and ys.shape == (2, 256)
    np.testing.assert_array_equal(ys.reshape(-1)[:300], y)
    assert (ys.reshape(-1)[300:] == -1).all()
    assert (xs.reshape(-1, 28, 28, 1)[300:] == 0).all()


def test_one_call_reads_once_and_sends_nothing():
    _init, _fwd, eval_set, params = _model()
    x, y = _data(1000)
    perf.enable()
    staged = stage_test_set(x, y)
    perf.end_round()
    evaluate(eval_set, params, staged)
    perf.end_round()
    staging, call = perf.export()["counts"]
    assert staging["h2d_bytes"] == 4 * 256 * (28 * 28 * 4 + 4)
    assert call.get("host_syncs") == 1
    assert call.get("h2d_bytes", 0) == 0
    names = [s["name"] for s in perf.export()["spans"]]
    assert names == ["eval_step", "sync"]


def test_new_params_compile_nothing():
    init, _fwd, eval_set, params = _model()
    staged = stage_test_set(*_data(1000))
    guards.install()
    first = evaluate(eval_set, params, staged)
    base = guards.compile_count()
    second = evaluate(eval_set, init(jax.random.PRNGKey(1)), staged)
    assert guards.compile_count() == base
    assert second != first


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.data.synthetic import load_dataset
    from repro.fed.algorithms import make_algorithm
    from repro.fed.driver import RoundDriver
    from repro.fed.rounds import FedConfig

    ds = load_dataset("mnist", small=True)
    cfg = FedConfig(engine="sharded", num_clients=16, pack=2, rounds=1,
                    local_epochs=1, batch_size=32, teacher_warmup_epochs=0,
                    seed=0)
    alg = make_algorithm(cfg)
    h = RoundDriver(ds, cfg, alg).run()
    xs, ys = alg.test_set
    assert len(jax.devices()) == 8
    assert xs.sharding.device_set == set(jax.devices()), xs.sharding
    assert xs.sharding.is_fully_replicated and ys.sharding.is_fully_replicated
    p = jax.tree_util.tree_leaves(alg.sp_global)[0]
    assert p.sharding.device_set == xs.sharding.device_set, p.sharding
    acc, loss = alg.eval()
    assert (acc, loss) == (h["acc"][-1], h["loss"][-1])
    assert 0.0 <= acc <= 1.0 and np.isfinite(loss)
    print("MESH-EVAL-OK", acc, loss)
""")


def test_sharded_round_evaluates_on_the_mesh():
    """A packed FedSiKD round on the 8-device CPU mesh: the staged set is
    replicated where the global student lives, and ``eval`` runs there."""
    r = run_script(_MESH_SCRIPT, timeout=600)
    assert "MESH-EVAL-OK" in r.stdout, r.stdout + r.stderr
