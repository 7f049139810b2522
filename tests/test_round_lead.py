"""The driver's one-round lead (DESIGN.md §13): the packed strategies hand
back their losses and eval as device values, and ``RoundDriver`` reads
round r's values only after it has dispatched round r+1.  Pinned here, on
the 8-device CPU mesh:

- the lead changes no number: a run that reads every round at once (a
  checkpoint every round) records the same history as one that reads a
  round late — bit-identical in one wave, within 1e-6 in several;
- the lead is bounded: at every ``run_round`` entry at most one earlier
  round is unread;
- ``perf`` counts one ``host_syncs`` per round and a ``read_lag`` of 1 in
  every round but the last, which the end of the run reads at once;
- a driver that never reads, or reads two rounds late, fails those checks.

Mesh-dependent, so each case runs in a subprocess (tests/_subproc.py).
"""
import textwrap

import pytest

from _subproc import run_script

_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro import perf
    from repro.data.synthetic import load_dataset
    from repro.fed.algorithms import make_algorithm
    from repro.fed.driver import RoundDriver
    from repro.fed.rounds import FedConfig

    CASE, MUTANT = {case!r}, {mutant!r}
    CASES = {{
        "fedsikd-1wave": dict(algorithm="fedsikd", pack=2),
        "fedsikd-4waves": dict(algorithm="fedsikd", pack=1, n_devices=2,
                               waves=4),
        "fedavg": dict(algorithm="fedavg", pack=2),
    }}
    ROUNDS = 4
    ds = load_dataset("mnist", small=True)

    def run(ckpt_dir=None):
        cfg = FedConfig(engine="sharded", num_clients=8, alpha=0.5,
                        rounds=ROUNDS, local_epochs=1, batch_size=32,
                        teacher_warmup_epochs=0, num_clusters=2, seed=0,
                        ckpt_dir=ckpt_dir, ckpt_every=1, **CASES[CASE])
        alg = make_algorithm(cfg)
        driver = RoundDriver(ds, cfg, alg)
        read, held = [], []
        settle = driver._settle

        def settle_(history, unread, lag):
            if MUTANT == "never":
                return
            if MUTANT == "late":           # each read one call late
                held.append((unread, lag))
                if len(held) < 2:
                    return
                unread, lag = held.pop(0)
            read.append(unread.rnd)
            settle(history, unread, lag)
        driver._settle = settle_
        run_round = alg.run_round

        def run_round_(plan, rnd):
            unread = [r for r in range(1, rnd) if r not in read]
            assert len(unread) <= 1, ("unread at entry", rnd, unread)
            out = run_round(plan, rnd)
            assert all(hasattr(v, "shape") for v in out.values()), out
            return out
        alg.run_round = run_round_
        perf.enable()
        try:
            history = driver.run()
        finally:
            perf.disable()
        return history, perf.export()["counts"]

    def close(a, b, rel):
        assert a.keys() == b.keys(), (a.keys(), b.keys())
        for k in a:
            if isinstance(a[k], list) and a[k] and isinstance(a[k][0], float):
                assert len(a[k]) == len(b[k]), k
                for x, y in zip(a[k], b[k]):
                    assert abs(x - y) <= rel * max(abs(y), 1e-30), (k, x, y)
            else:
                assert a[k] == b[k], (k, a[k], b[k])

    def verify():
        lead, counts = run()
        with tempfile.TemporaryDirectory() as d:
            now, counts_now = run(ckpt_dir=d)
        assert lead["round"] == list(range(1, ROUNDS + 1)), lead["round"]
        assert all(type(v) is float for v in lead["acc"] + lead["loss"])
        if CASE.endswith("4waves"):
            close(lead, now, 1e-6)
        else:
            assert lead == now, (lead, now)
        lags = [c.get("read_lag") for c in counts]
        assert lags == [1] * (ROUNDS - 1) + [0], lags
        assert [c.get("host_syncs") for c in counts] == [1] * ROUNDS, counts
        assert [c.get("read_lag") for c in counts_now] == [0] * ROUNDS
        return lead

    if MUTANT is None:
        print("LEAD-OK", verify()["acc"])
    else:
        try:
            verify()
        except AssertionError as e:
            print("MUTANT-CAUGHT", repr(e)[:200])
        else:
            print("MUTANT-MISSED")
""")


@pytest.mark.parametrize("case, mutant", [
    ("fedsikd-1wave", None),
    ("fedsikd-4waves", None),
    ("fedavg", None),
    ("fedsikd-1wave", "never"),
    ("fedsikd-1wave", "late"),
])
def test_one_round_lead(case, mutant):
    r = run_script(_SCRIPT.format(case=case, mutant=mutant), timeout=900)
    want = "LEAD-OK" if mutant is None else "MUTANT-CAUGHT"
    assert want in r.stdout, r.stdout + r.stderr
