"""Chip benchmark of the AI+R-tree's served path, driven by data files.

    python3 -m bench.run --workload gaussian-range --seed 7 --seconds 40 --trace 0

Run from the root of a checkout that holds ``BENCHMARK.json``. The cell
named by ``--workload`` names a configuration (``bench/configs``) and a
traffic mix (``bench/traffic``); its per-layer metrics are readers in
``bench/metrics``. It runs on a TPU only, in one process:

1. set-up: load the configuration's deployment (built on the first run in
   a checkout, ``bench.deploy``), place it on the chip, and warm every
   program and shape the window drives;
2. window: one closed-loop client sends a request, waits for all its
   answers, and sends the next, until ``--seconds`` have passed; with
   ``--trace 1`` under the profiler;
3. check: every answer of the window against the brute-force reference
   (``bench.reference``).

The traffic file's ``query`` names the client driver
(``bench/drivers/<query>.py``) that builds the server and client, warms
them and checks the answers; this module keeps the clock, the window,
the count of compilations in it, the trace and the result.

Lines starting with ``#`` report progress. The last line of standard
output is one JSON object; the compared numbers and their limits are the
last lines of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import NamedTuple

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
SPEC_FILE = "BENCHMARK.json"
# fixed warm-up traffic: the same set-up work for every seed
WARM_SEED = 0


def process_start() -> float:
    """Wall-clock time at which this process started."""
    import psutil
    return psutil.Process().create_time()


# ---------------------------------------------------------------- spec

def load_spec(root: str) -> dict:
    with open(os.path.join(root, SPEC_FILE)) as f:
        return json.load(f)


def find_cell(spec: dict, name: str, root: str):
    """``(cell, config, config_path, traffic)`` for the cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg_path = os.path.join(root, entry["file"])
    with open(cfg_path) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, cfg, cfg_path, traffic


def cell_metrics(spec: dict, cell: str) -> tuple[list, list]:
    """The cell's end-to-end and per-layer metric entries."""
    def mine(m):
        return cell in m.get("workloads", [cell])
    return ([m for m in spec["end_to_end"] if mine(m)],
            [m for m in spec["per_layer"] if mine(m)])


def reader(name: str):
    """The per-layer metric's reader, ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- spans

class Spans:
    """Host spans around the calls into each layer, written into the
    profiler's trace (``jax.profiler.TraceAnnotation``; near free when no
    trace is taken). One span is open at a time: ``phase`` closes it and
    opens the next."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._make = TraceAnnotation
        self._open = None

    def phase(self, name: str | None) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if name is not None:
            self._open = self._make(name)
            self._open.__enter__()


# ------------------------------------------------------------ drivers

def driver(traffic: dict):
    """The traffic's client driver, ``bench/drivers/<query>.py``, once
    it has accepted the traffic file."""
    from bench import traffic as trlib
    trlib.check(traffic)
    drv = importlib.import_module("bench.drivers." +
                                  trlib.driver_name(traffic))
    drv.check_traffic(traffic)
    return drv


class CompileCounter:
    """Counts lowerings and backend compiles while ``on``, and the
    seconds they took by program."""

    def __init__(self):
        import jax
        self.on = False
        self.counts = {"lowerings": 0, "compiles": 0}
        self.seconds = {}       # program name → seconds lowering, compiling
        names = {"/jax/core/compile/jaxpr_to_mlir_module_duration":
                 "lowerings",
                 "/jax/core/compile/backend_compile_duration": "compiles"}

        def listen(event, duration, fun_name="", **kw):
            if self.on and event in names:
                self.counts[names[event]] += 1
                self.seconds[fun_name] = \
                    self.seconds.get(fun_name, 0.0) + duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def costliest(self, n: int = 3) -> str:
        """The ``n`` programs that took longest, with their seconds."""
        top = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:n]
        return ", ".join(f"{k} {v:.2f}s" for k, v in top) or "none"


def window(client, reqs, seconds: float) -> float:
    """Closed loop until ``seconds`` have passed; returns the window's
    length: from the first request's start to the completion of the last
    request started within ``seconds``."""
    t_begin = time.perf_counter()
    t_end = t_begin
    i = 0
    while i == 0 or t_end - t_begin < seconds:
        q = reqs.queries(i)
        t0 = time.perf_counter()
        out = client.serve(q)
        t_end = time.perf_counter()
        client.record(q, out, t_end - t0)
        i += 1
    return t_end - t_begin


def end_to_end(latencies: list, queries: int, window_s: float,
               setup_s: float) -> dict:
    """The end-to-end metrics: queries answered over the window's time,
    the median of every request's latency, and set-up time."""
    return {"qps": queries / window_s,
            "p50_ms": float(np.median(np.asarray(latencies) * 1e3)),
            "setup_s": setup_s}


# ----------------------------------------------------------------- run

class Readings(NamedTuple):
    """What a per-layer metric's reader gets."""
    counters: dict          # window totals the client kept, and the
    #                         window's ``lowerings`` and ``compiles``
    trace: object           # bench.trace.Trace of the window
    device_kind: str
    entries_per_leaf: int   # a leaf's padded entry slots (x, y f32 each)


def run_cell(cell: dict, cfg: dict, cfg_path: str, traffic: dict, *,
             seed: int, seconds: float, trace_on: bool, t_start: float,
             e2e: list, per_layer: list, cache_dir: str,
             wide_tier: bool = True) -> tuple[dict, list]:
    """One run of a cell; returns ``(result, check_lines)``. The caller
    has checked the device. ``wide_tier=False`` switches the program's
    wide re-serve off: the control that ``correct`` must fail."""
    import jax
    from bench import deploy, trace as tracelib
    drv = driver(traffic)
    index = deploy.load_index(cfg, cfg_path, cache_dir)
    fit = deploy.load_fit(cfg, cfg_path, index, cache_dir)
    print(f"# bank: grid {fit.grid}², exact-fit {fit.exact_fit:.4f}, "
          f"pool {fit.pool.shape[0]}", flush=True)
    server = drv.make_server(index, fit, cfg, traffic, cell)
    reqs = drv.requests(traffic, seed, index, fit)
    warm_reqs = drv.requests(traffic, WARM_SEED, index, fit)
    spans = Spans()
    client = drv.Client(server, spans, wide_tier=wide_tier)
    print(f"# {drv.warm(client, warm_reqs, spans)}", flush=True)
    client = drv.Client(server, spans, wide_tier=wide_tier)
    counter = CompileCounter()
    trace_dir = os.path.join(cache_dir, "traces", cell["name"])
    if trace_on:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    # the reference's answers are no part of set-up (cold runs only)
    setup_s = time.time() - t_start - fit.reference_s
    counter.on = True
    with jax.profiler.TraceAnnotation(tracelib.WINDOW):
        window_s = window(client, reqs, seconds)
    counter.on = False
    if trace_on:
        jax.profiler.stop_trace()
    devs = jax.devices()[:cell["chips"]]
    dev = devs[0]
    # the peak of the fullest chip the cell uses
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    n = client.counters["queries"]
    print(f"# window: {len(client.latencies)} requests, {n} queries in "
          f"{window_s:.3f}s; inside it {counter.counts['lowerings']} "
          f"lowerings, {counter.counts['compiles']} compiles", flush=True)
    client.counters.update(counter.counts)
    # the program's device state is freed before the reference runs
    for leaf in {id(x): x for x in jax.tree.leaves(client.state)}.values():
        leaf.delete()
    del server

    checks, failed = drv.check(client, reqs, index, fit)
    correct = all(v <= lim for v, lim in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    # requests in the window, the sample behind p50_ms
    result = {"correct": correct, "attempted": n, "failed": failed,
              "requests": len(client.latencies)}
    if not trace_on:
        values = end_to_end(client.latencies, n, window_s, setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]} for m in e2e}
    else:
        pb = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
              for f in fs if f.endswith(".xplane.pb")]
        tr = tracelib.load(sorted(pb)[-1])
        entries = -(-cfg["rtree"]["node_capacity"] // 8) * 8
        readings = Readings(counters=client.counters, trace=tr,
                            device_kind=dev.device_kind,
                            entries_per_leaf=entries)
        result["metrics"] = {}
        for m in per_layer:
            v = reader(m["name"])(readings)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tracelib.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tracelib.top_ops(tr),
                               "idle_gaps": tracelib.idle_by_span(tr)}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    lines = [f"check {k} {v} limit {lim}" for k, (v, lim) in checks.items()]
    print(f"# counters: {json.dumps(client.counters)}", flush=True)
    return result, lines


def require_chips(n: int):
    """The first ``n`` TPU chips, or exit 3 before any work."""
    # the TPU runtime logs inside the checkout, not at a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(BENCH_DIR, ".cache", "tpu_logs"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


def enable_compile_cache(cache_dir: str) -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at a fixed path inside the checkout. JAX's
    default threshold stays: programs that compile in under a second (the
    scheduler's eager key arithmetic, one set per re-served row count)
    compile in each run's set-up instead of filling the cache with
    thousands of entries."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(cache_dir, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> int:
    t_start = process_start()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    root = os.getcwd()
    spec = load_spec(root)
    cell, cfg, cfg_path, traffic = find_cell(spec, a.workload, root)
    require_chips(cell["chips"])
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 4
    sys.path.insert(0, src)
    from bench import deploy
    print(f"# compile cache: {enable_compile_cache(deploy.CACHE_DIR)}",
          flush=True)
    e2e, per_layer = cell_metrics(spec, cell["name"])
    result, lines = run_cell(
        cell, cfg, cfg_path, traffic, seed=a.seed, seconds=a.seconds,
        trace_on=bool(a.trace), t_start=t_start, e2e=e2e,
        per_layer=per_layer, cache_dir=deploy.CACHE_DIR)
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
