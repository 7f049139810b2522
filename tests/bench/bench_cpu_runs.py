"""Runs of the fixture cell on the CPU for ``test_bench_run.py``, in one
process so that they share compiled programs: a sound run, a traced run,
each planted fault, and the control.  Prints one JSON object."""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(ROOT))

from bench import check, faults, harness  # noqa: E402

CELL, SEED = "tiny.fedsikd", 2147483651      # a seed past 2**31


def one(trace=False, plant=None, keep=None):
    r = harness.run(CELL, SEED, 0.5, trace, t0=time.perf_counter(),
                    root=FIXTURE, require_chip=False, plant=plant,
                    log=lambda *a: None, keep=keep)
    return {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "metrics": sorted(r["metrics"]),
            "device": r["device"], "checks": r["checks"],
            "keys": list(r)}


def main():
    import jax.numpy as jnp

    from bench.data import make_dataset
    from bench.reference import Reference
    keep = {}
    out = {"sound": one(keep=keep), "traced": one(trace=True)}
    for name, plant in faults.FAULTS.items():
        out[name] = one(plant=plant)
    spec = harness.Spec(FIXTURE)
    wl = spec.workload(CELL)
    config = spec.config(wl["config"])
    ctl = Reference(config, spec.traffic(wl["traffic"]),
                    make_dataset(config, SEED), SEED,
                    dtype=jnp.bfloat16).run(harness.WARM_MIN)
    ok, checks = check.verdict(check.numbers(ctl, keep["ref"]),
                               spec.cell(CELL)["limits"])
    out["control"] = {"correct": ok, "checks": checks}
    print(json.dumps(harness._finite(out)))


if __name__ == "__main__":
    main()
