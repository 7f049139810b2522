"""One run of one cell: set-up, the measured window, the check.

The window drives the program's own entry, ``RoundDriver.run``, on the
packed sharded engine with the fused KD kernel, and stamps it from outside
by wrapping the strategy instance's bound methods (``setup``, ``warmup``,
``run_round``, ``eval``).  Nothing under ``src/`` is edited.

- Set-up runs from process start to the first measured round: JAX and
  chip start-up, the dataset twin and its split, statistics sharing and
  clustering, teacher warm-up, and warm rounds.  Warm rounds go on until a
  whole round (``run_round`` entry to the next entry) asks for no
  compilation, and there are at least three.
- The window then measures rounds until the first round boundary at or
  after ``--seconds``.
- Afterwards the first three rounds, recorded during set-up, are compared
  with the plain reference (``reference.py``, ``check.py``).

Cells, configurations, traffic mixes, limits and per-layer readers are
files found by the names in ``BENCHMARK.json``; see ``Spec``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WARM_MIN = 3            # warm rounds at least: rounds 1-3 are the compared ones
WARM_MAX = 40           # a program still compiling after this many is at fault
TRACE_S = 8             # a traced run's window: a paper cell's 8 s trace is
                        # about 100 MB, and its reduction is pure Python
# the fused KD kernel's operations in the device trace, forward and backward
# (``jvp_jit_kd_loss_fwd__.N``, ``transpose_jvp_jit_kd_loss_bwd___.N``)
KD_NAMES = {"fwd": ["kd_loss_fwd"], "bwd": ["kd_loss_bwd"]}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    """The benchmark's files, by name.  ``root`` holds ``BENCHMARK.json``;
    the first entry of its ``paths`` holds, per name,
    ``traffic/<traffic>.json`` (the round's parameters), ``cells/<cell>.json``
    (the limits of the check) and ``metrics/<metric>.py`` (a per-layer
    reader).  Each configuration's file is named in ``configs``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = _load(self.root / "BENCHMARK.json")
        self.home = self.root / self.data["paths"][0]

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.data[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind} entry named {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return _load(self.root / self._entry("configs", name)["file"])

    def traffic(self, name: str) -> dict:
        return _load(self.home / "traffic" / f"{name}.json")

    def cell(self, name: str) -> dict:
        return _load(self.home / "cells" / f"{name}.json")

    def metrics(self, kind: str, workload: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.data[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of a per-layer metric's file, looked
        up in this benchmark's ``metrics/``, then in the harness's own."""
        path = self.home / "metrics" / f"{metric}.py"
        if not path.exists():
            path = BENCH / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{len(sys.modules)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


# ------------------------------------------------------------- compiles
class Compiles:
    """Compile counters from ``jax.monitoring``.  ``requests`` is the count
    the program's compile sentinel keeps (``guards.py``: every event whose
    name holds "compile", one per jit cache miss, served from the
    persistent cache or not); ``backend`` counts the persistent cache's
    misses, the programs XLA compiled.  JAX cannot unregister a listener,
    so one instance serves the process."""

    _one = None

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        self.requests = 0
        self.backend = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **kwargs):
        if "compile" in event:
            self.requests += 1
        if event == "/jax/compilation_cache/cache_misses":
            self.backend += 1


# ----------------------------------------------------------------- window
class WindowClosed(Exception):
    """Raised at the first round boundary after the window's length."""


def _host(tree):
    import jax
    return jax.device_get(tree)


class Recorder:
    """Wraps one strategy instance: records what the check compares during
    the warm rounds, decides when the window opens, stamps the window's
    rounds and closes it."""

    def __init__(self, alg, seconds, trace_dir, log, t0):
        self.alg, self.seconds, self.trace_dir = alg, seconds, trace_dir
        self.mark = lambda what: log(f"t={time.perf_counter() - t0:.3f}s "
                                     f"{what}")
        self.compiles = Compiles.get()
        self.entry = {}                  # round -> (time, compile requests)
        self.prog = {"teacher_loss": [], "student_loss": [], "eval_loss": [],
                     "eval_acc": []}
        self.t_open = self.t_close = None
        self.stamps, self.plans, self.outs, self.returned = [], [], [], []
        self.gc_pauses = []              # (generation, seconds) in the window
        self.prefetched = None           # the last plan handed to prefetch
        self._round_note = None
        self._install()

    # ------------------------------------------------------------ wrappers
    def _install(self):
        alg = self.alg
        setup, warmup, run_round, evaluate = (alg.setup, alg.warmup,
                                              alg.run_round, alg.eval)

        def setup_(*a, **k):
            out = setup(*a, **k)
            self.mark("set-up of the strategy done (statistics, clusters, "
                      "staging)")
            self.prog["labels"] = np.asarray(alg.labels)
            if self.trace_dir is not None:
                _annotate(alg.scheduler, "plan", "bench.plan")
                _annotate(alg.stager, "stage", "bench.stage")
            prefetch = alg.stager.prefetch

            def prefetch_(plan):
                self.prefetched = plan
                return prefetch(plan)
            alg.stager.prefetch = prefetch_
            return out

        def warmup_():
            self.prog["init"] = {"student": _host(alg.sp_global),
                                 "teachers": _split(_host(alg.tp_k))}
            out = warmup()
            self.mark("teacher warm-up done")
            return out

        def run_round_(plan, rnd):
            self._enter(rnd)
            if self.t_open is not None:
                self.plans.append(plan)
            with self._annotation("bench.run_round"):
                out = run_round(plan, rnd)
            if rnd <= WARM_MIN:
                self.prog["teacher_loss"].append(float(out["teacher_loss"]))
                self.prog["student_loss"].append(float(out["student_loss"]))
            elif self.t_open is not None:
                # read after the window closes: no host sync inside it
                self.outs.append(out)
                self.returned.append(time.perf_counter())
            return out

        def eval_():
            with self._annotation("bench.eval"):
                acc, loss = evaluate()
            if len(self.prog["eval_loss"]) < WARM_MIN:
                self.prog["eval_acc"].append(float(acc))
                self.prog["eval_loss"].append(float(loss))
            return acc, loss

        alg.setup, alg.warmup, alg.run_round, alg.eval = (
            setup_, warmup_, run_round_, eval_)

    def _annotation(self, name):
        import contextlib

        import jax
        if self.trace_dir is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    # --------------------------------------------------------- the window
    def _enter(self, rnd):
        now = time.perf_counter()
        alg = self.alg
        self.entry[rnd] = (now, self.compiles.requests)
        if rnd == 2:
            self.prog["after1"] = {"student": _host(alg.sp_global)}
        if rnd == WARM_MIN + 1:
            self.prog["after"] = {"student": _host(alg.sp_global),
                                  "teachers": _split(_host(alg.tp_k))}
        if self.t_open is None:
            self.mark(f"round {rnd} starts, {self.compiles.requests} compile "
                      f"requests so far")
            quiet = (rnd - 1 >= WARM_MIN and self.compiles.requests
                     == self.entry[rnd - 1][1])
            if not quiet:
                if rnd > WARM_MAX:
                    raise RuntimeError(
                        f"round {rnd - 1} still asked for compilation after "
                        f"{WARM_MAX} warm rounds")
                return
            self._open(rnd)
            now = self.t_open
        else:
            self._close_round_note()
        self.stamps.append(now)
        if now - self.t_open >= self.seconds:
            self.t_close = now
            self.stamps.pop()
            self.window_compiles = self.compiles.requests - self.open_compiles
            self.window_backend = self.compiles.backend - self.open_backend
            gc.callbacks.remove(self._gc)
            if self.trace_dir is not None:
                import jax
                jax.profiler.stop_trace()
            raise WindowClosed
        self._open_round_note()

    def _open(self, rnd):
        from repro import perf
        gc.callbacks.append(self._gc)
        self.open_round = rnd
        if self.trace_dir is not None:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            perf.enable()
        self.open_compiles = self.compiles.requests
        self.open_backend = self.compiles.backend
        self.t_open = time.perf_counter()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc_start))

    def finish(self):
        """After the window: wait for a prefetch still in flight (the
        stager's ``stage`` adopts it), and count the measured rounds whose
        outputs are not finite."""
        import jax
        if self.prefetched is not None:
            self.alg.stager.stage(self.prefetched)
        return count_failed(jax.device_get(self.outs))

    def _open_round_note(self):
        if self.trace_dir is not None:
            import jax
            self._round_note = jax.profiler.TraceAnnotation("bench.round")
            self._round_note.__enter__()

    def _close_round_note(self):
        if self._round_note is not None:
            self._round_note.__exit__(None, None, None)
            self._round_note = None


def _split(stacked):
    """A (K, ...) stacked pytree as a list of K pytrees."""
    import jax
    k = len(jax.tree_util.tree_leaves(stacked)[0])
    return [jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
            for i in range(k)]


def _annotate(obj, method, name):
    import jax
    fn = getattr(obj, method)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    setattr(obj, method, wrapped)


def use_split(alg, shards):
    """Hand the strategy's ``setup`` the traffic's split (``data.py``) in
    place of the one the driver makes from the seed.  Installed last, so it
    runs first: a fault planted on ``setup`` sees these shards."""
    setup = alg.setup

    def setup_(ds, _driver_shards, cfg, key):
        return setup(ds, list(shards), cfg, key)
    alg.setup = setup_


def count_failed(outs) -> int:
    """Measured rounds whose outputs hold a value that is not finite."""
    return sum(not all(math.isfinite(float(v)) for v in out.values())
               for out in outs)


def work(plans, sizes, batch_size, epochs) -> dict:
    """The work of the measured rounds, from their plans and the shard
    sizes: each round's real client rows (participants' shard sizes times
    the epochs; a virtual client ``v`` holds base shard ``v % len(sizes)``),
    the longest participant's optimizer steps, and the share of the lanes'
    steps, at that length, that hold real rows."""
    rows, steps, lane_rows = [], 0, 0
    for p in plans:
        n = sizes[p.slot_client[p.active] % len(sizes)]
        rows.append(int(n.sum()) * epochs)
        longest = int(np.ceil(n / batch_size).max()) * epochs
        steps = max(steps, longest)
        lane_rows += p.n_slots * longest * batch_size
    return {"client_rows": rows, "steps": steps,
            "real_share": sum(rows) / max(lane_rows, 1)}


# ------------------------------------------------------------------- run
def fed_config(config, traffic, seed):
    from repro.fed.rounds import FedConfig
    return FedConfig(**config["training"], **traffic["fed"],
                     engine="sharded", kd_impl="fused", rounds=10 ** 6,
                     seed=seed)


def devices(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_cache():
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout, or ``$JAX_COMPILATION_CACHE_DIR``), with every program kept:
    JAX's default skips those that compile in under a second, and those are
    most of a round's."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, root: Path = ROOT, require_chip: bool = True,
        plant=None, log=print, keep: dict | None = None) -> dict:
    """One run; returns the result line's object.  ``plant(alg)`` may break
    the timed path (the tests' faults) before the driver starts; ``keep``,
    if given, receives the compared readings of both sides."""
    spec = Spec(root)
    wl = spec.workload(workload)
    config, traffic = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    limits = spec.cell(workload)["limits"]
    sys.path.insert(0, str(ROOT / "src"))
    devs = devices(wl["chips"], require_chip)
    cache = enable_cache()
    from bench import flops
    from bench.data import dirichlet_partition, make_dataset
    from repro.data.pipeline import ClientShard
    from repro.data.synthetic import Dataset
    from repro.fed.algorithms import make_algorithm
    from repro.fed.driver import RoundDriver

    compiles = Compiles.get()
    base_requests, base_backend = compiles.requests, compiles.backend
    log(f"t={time.perf_counter() - t0:.3f}s JAX and {len(devs)} "
        f"{devs[0].platform} device(s) ready")
    data = make_dataset(config, seed)
    ds = Dataset(config["dataset"]["name"], *data,
                 config["dataset"]["num_classes"])
    fed = traffic["fed"]
    parts = dirichlet_partition(data[1], fed["num_clients"], fed["alpha"],
                                seed=seed, table_seed=traffic["table_seed"])
    shards = [ClientShard(i, data[0][p], data[1][p])
              for i, p in enumerate(parts)]
    sizes = np.asarray([len(p) for p in parts], np.int64)
    log(f"t={time.perf_counter() - t0:.3f}s dataset and split made: "
        f"{len(data[1])} train, {len(data[3])} test, shards "
        f"{sizes.min()}..{sizes.max()}")
    cfg = fed_config(config, traffic, seed)
    alg = make_algorithm(cfg)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = Recorder(alg, min(seconds, TRACE_S) if trace else seconds,
                   trace_dir, log, t0)
    if plant is not None:
        plant(alg)
    use_split(alg, shards)
    driver = RoundDriver(ds, cfg, alg)
    try:
        driver.run()
        raise RuntimeError("the driver ran out of rounds before the window "
                           "closed")
    except WindowClosed:
        pass
    failed = rec.finish()
    setup_s = rec.t_open - t0
    window_s = rec.t_close - rec.t_open
    durations = np.diff(rec.stamps + [rec.t_close])
    n_rounds = len(durations)
    E, B = cfg.local_epochs, cfg.batch_size
    w = work(rec.plans, sizes, B, E)
    client_rows = w["client_rows"]
    samples = sum(client_rows)
    peak_bytes = max(d.memory_stats().get("peak_bytes_in_use", 0)
                     for d in devs[:wl["chips"]]) if devs[0].platform == \
        "tpu" else 0
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}")
    log(f"set-up: {setup_s:.3f}s, {rec.open_round - 1} warm rounds, "
        f"compile requests {rec.open_compiles - base_requests}"
        f", XLA compiles {rec.open_backend - base_backend}")
    log(f"window: {n_rounds} rounds in {window_s:.3f}s, compile requests "
        f"{rec.window_compiles}, XLA compiles {rec.window_backend}"
        + ("; fewer than 100 rounds" if n_rounds < 100 else ""))
    log("rounds (ms): " + " ".join(f"{1e3 * d:.1f}" for d in durations))
    in_round = np.asarray(rec.returned) - np.asarray(rec.stamps)
    for i in np.argsort(durations)[::-1][:3]:
        log(f"long round: #{i} of the window, {1e3 * durations[i]:.1f} ms, "
            f"of which run_round {1e3 * in_round[i]:.1f} ms")
    pauses = [d for _, d in rec.gc_pauses]
    log(f"gc in the window: {len(pauses)} collections, "
        f"{1e3 * sum(pauses):.1f} ms in all, longest "
        f"{1e3 * max(pauses, default=0.0):.1f} ms (generation "
        f"{max(rec.gc_pauses, key=lambda g: g[1], default=(None,))[0]})")
    log(f"work: longest client {w['steps']} steps a round, real-row share "
        f"{w['real_share']:.4f}, K={alg.K}, clients per round "
        f"{len(rec.plans[0].participants)}")
    log(f"memory: peak {peak_bytes} bytes on the fullest chip")
    teacher_rows = []
    for p in rec.plans:
        ci = np.unique(p.slot_cluster[p.active])
        teacher_rows.append(int(sum(sizes[alg.leaders[c] % len(sizes)]
                                    for c in ci)) * E)
    window_flops = sum(flops.round_flops(config, [c], [t], len(data[3]))
                       for c, t in zip(client_rows, teacher_rows))
    perf_rounds = []
    reduced = None
    if trace:
        from repro import perf
        perf_rounds = perf.snapshot()
        perf.disable()
        from bench import trace as tr
        reduced = tr.reduce(*tr.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the check, after the window, with the program's state freed
    prog = rec.prog
    kd_info = {"lanes": cfg.pack, "tokens": cfg.batch_size,
               "vocab": config["dataset"]["num_classes"]}
    del driver, alg, rec
    gc.collect()
    from bench import check
    from bench.reference import Reference
    ref = Reference(config, traffic, data, seed).run(WARM_MIN)
    for side, d in (("program", prog), ("reference", ref)):
        log(f"{side}: " + "; ".join(
            f"{k} {[round(v, 6) for v in d[k]]}" for k in (
                "teacher_loss", "student_loss", "eval_loss", "eval_acc")))
    values = check.numbers(prog, ref)
    correct, checks = check.verdict(values, limits)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    metrics = {}
    if not trace:
        e2e = {"samples_per_s": samples / window_s,
               "round_p90_ms": float(np.percentile(durations, 90)) * 1e3,
               "setup_s": setup_s}
        for m in spec.metrics("end_to_end", workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        peaks = _load(BENCH / "peaks.json")[devs[0].device_kind] \
            if devs[0].platform == "tpu" else None
        kd_calls = ({k: tr.count_of(reduced["op_n"], v)
                     for k, v in KD_NAMES.items()} if reduced else None)
        ctx = {"perf_rounds": perf_rounds, "trace": reduced,
               "window_s": window_s, "window_flops": window_flops,
               "peaks": peaks, "kd": kd_info, "kd_names": KD_NAMES,
               "kd_calls": kd_calls, "n_rounds": n_rounds,
               "chips": wl["chips"]}
        for m in spec.metrics("per_layer", workload):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    result = {"correct": correct, "attempted": n_rounds,
              "failed": failed, "metrics": metrics,
              "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    if keep is not None:
        keep.update(prog=prog, ref=ref, values=values)
    return result


def _finite(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv, t0) -> int:
    import argparse
    import traceback
    ap = argparse.ArgumentParser(
        description="One run of one benchmark cell; the last line of "
        "stdout is the result as JSON.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    err = lambda *a: print(*a, file=sys.stderr, flush=True)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t0=t0, log=err)
    except NoChip as e:
        err(f"bench: {e}")
        return 3
    except Exception:                      # noqa: BLE001 - report and fail
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        err(f"check {name} {c['value']!r} limit {c['limit']!r}")
    err(f"correct {result['correct']}")
    print(json.dumps(_finite(result)), flush=True)
    return 0
