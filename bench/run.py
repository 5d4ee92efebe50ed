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
   answers (spatial sort, narrow step, wide re-serve of truncated rows,
   rows back in submission order through ``repro.core.schedule``), and
   sends the next, until ``--seconds`` have passed; with ``--trace 1``
   under the profiler;
3. check: every answer of the window against the brute-force reference
   (``bench.reference``).

Lines starting with ``#`` report progress. The last line of standard
output is one JSON object; the compared numbers and their limits are the
last lines of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
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
WARM_REQUESTS = 4


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


# ------------------------------------------------------------ serving

class Server(NamedTuple):
    """What a cell's client drives: the program's two-tier steps and the
    arithmetic of the leaves each row's answer needs."""
    narrow: object          # jitted [batch, 4] → stats
    wide: object
    trunc_field: str
    bbox: np.ndarray        # the scheduler's fixed key frame
    batch: int
    sort: str
    needed: object          # (stats, tier) → [rows] leaves the answer needs
    state: object           # device arrays to free before the reference


def range_server(fit, cfg: dict):
    from repro.core import schedule
    from repro.launch import serve
    sv = cfg["serve"]
    args = serve.parse_args([
        "--classifier", cfg["bank"]["classifier"],
        "--batch", str(sv["batch"]), "--sort", sv["sort"],
        "--max-visited", str(sv["max_visited"]),
        "--wide-factor", str(sv["wide_factor"])])
    import jax
    fns = serve.make_serve_fns(fit.hybrid, args, jax.devices()[:1])
    bounds = {"narrow": sv["max_visited"],
              "wide": sv["max_visited"] * sv["wide_factor"]}

    def needed(st, tier):
        # AI-path answers need their predicted leaves; R-path answers
        # the visited leaves, as far as the step's bound reaches
        return np.where(st.used_ai, st.leaf_accesses,
                        np.minimum(st.n_visited_r, bounds[tier]))

    print(f"# range steps: fused MLP kernel "
          f"{'on' if fns.ai_fused else 'off'}, kernels "
          f"{'on' if args.kernel else 'off'}", flush=True)
    return Server(fns.narrow, fns.wide, fns.trunc_field,
                  schedule.workload_bbox(fit.pool), sv["batch"], sv["sort"],
                  needed, fit.hybrid)


class Client:
    """One closed-loop client of a ``Server``: serves a request through
    ``repro.core.schedule.serve_workload`` and keeps the window's
    counters and answers."""

    def __init__(self, server: Server, spans: Spans, wide_tier: bool = True):
        self.s = server
        self.spans = spans
        self.wide_tier = wide_tier
        self.counters = {"queries": 0, "wide_rows": 0, "leaf_accesses": 0,
                         "ai_rows": 0, "refine_leaves": 0}
        self.latencies = []
        self.answers = []

    def _step(self, fn, span, calls):
        import jax

        def call(qb):
            self.spans.phase(span)
            out = fn(qb)
            self.spans.phase("to_host")
            out = jax.tree.map(np.asarray, out)
            self.spans.phase("merge")
            calls.append(out)
            return out
        return call

    def serve(self, q: np.ndarray):
        """Serve one request; returns ``(report, narrow_outs, wide_outs)``."""
        from repro.core import schedule
        narrow_outs, wide_outs = [], []
        self.spans.phase("schedule")
        rep = schedule.serve_workload(
            self._step(self.s.narrow, "narrow_step", narrow_outs), q,
            batch=self.s.batch, sort=self.s.sort, bbox=self.s.bbox,
            wide_fn=(self._step(self.s.wide, "wide_step", wide_outs)
                     if self.wide_tier else None),
            trunc_field=self.s.trunc_field)
        self.spans.phase(None)
        return rep, narrow_outs, wide_outs

    def record(self, q, rep, narrow_outs, wide_outs, latency: float) -> None:
        st = rep.stats
        c = self.counters
        c["queries"] += q.shape[0]
        c["wide_rows"] += rep.n_reserved
        c["leaf_accesses"] += int(np.sum(st.leaf_accesses))
        c["ai_rows"] += int(np.sum(st.used_ai))
        for outs, tier, n in ((narrow_outs, "narrow", q.shape[0]),
                              (wide_outs, "wide", rep.n_reserved)):
            if outs:
                cat = type(outs[0])(*(np.concatenate(f) for f in zip(*outs)))
                c["refine_leaves"] += int(
                    np.sum(self.s.needed(cat, tier)[:n]))
        self.latencies.append(latency)
        w = max(int(np.max(st.n_results)), 1)
        self.answers.append((np.asarray(st.n_results),
                             np.asarray(st.result_ids)[:, :w]))


def warm(client: Client, reqs, spans: Spans) -> str:
    """Compile and run every program and shape the window will drive:
    the fixed warm-up requests through both tiers, then the spatial sort
    at every count of re-served rows a request can plausibly hold
    (binomial over an upper estimate of the warm-up's re-serve share,
    ±5σ)."""
    from repro.core import schedule
    s = client.s
    rows = wide = 0
    t0 = time.perf_counter()
    for i in range(WARM_REQUESTS):
        q = reqs.queries(i)
        rep, _, _ = client.serve(q)
        rows += q.shape[0]
        wide += rep.n_reserved
    q0 = reqs.queries(0)
    client._step(s.wide, "wide_step", [])(q0[:s.batch])
    n = q0.shape[0]
    # an upper estimate, so that a share the warm-up barely saw still
    # warms a few counts
    p = (wide + 3) / rows
    mu, sd = n * p, math.sqrt(n * p * (1 - p))
    lo, hi = max(1, int(mu - 5 * sd)), min(n, int(math.ceil(mu + 5 * sd)) + 1)
    t1 = time.perf_counter()
    for k in range(lo, hi + 1):
        schedule.spatial_keys(q0[:k], s.sort, s.bbox)
        if (k - lo) % 32 == 31:
            print(f"# warm-up: sort at {k} re-served rows, "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
    spans.phase(None)
    return (f"warm-up: {WARM_REQUESTS} requests in {t1 - t0:.1f}s, re-serve "
            f"share {p:.4f}, sort warmed for {lo}..{hi} re-served rows in "
            f"{time.perf_counter() - t1:.1f}s")


class CompileCounter:
    """Counts lowerings and backend compiles while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.counts = {"lowerings": 0, "compiles": 0}
        names = {"/jax/core/compile/jaxpr_to_mlir_module_duration":
                 "lowerings",
                 "/jax/core/compile/backend_compile_duration": "compiles"}

        def listen(event, duration, **kw):
            if self.on and event in names:
                self.counts[names[event]] += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def window(client: Client, reqs, seconds: float) -> float:
    """Closed loop until ``seconds`` have passed; returns the window's
    length: from the first request's start to the completion of the last
    request started within ``seconds``."""
    t_begin = time.perf_counter()
    t_end = t_begin
    i = 0
    while i == 0 or t_end - t_begin < seconds:
        q = reqs.queries(i)
        t0 = time.perf_counter()
        rep, no, wo = client.serve(q)
        t_end = time.perf_counter()
        client.record(q, rep, no, wo, t_end - t0)
        i += 1
    return t_end - t_begin


def end_to_end(latencies: list, queries: int, window_s: float,
               setup_s: float) -> dict:
    """The end-to-end metrics: queries answered over the window's time,
    the median of every request's latency, and set-up time."""
    return {"qps": queries / window_s,
            "p50_ms": float(np.median(np.asarray(latencies) * 1e3)),
            "setup_s": setup_s}


# -------------------------------------------------------------- checks

def check_range(client: Client, reqs, fit) -> tuple[dict, int]:
    """Every row of the window: the served count and id set against the
    reference's, exactly. Returns ``(checks, wrong rows)``."""
    off, ids = fit.ref_offsets, fit.ref_ids
    wrong = 0
    big = np.iinfo(np.int64).max
    for i, (n_res, got) in enumerate(client.answers):
        src = reqs.src(i)
        n_ref = off[src + 1] - off[src]
        w = max(int(n_ref.max(initial=0)), got.shape[1])
        col = np.arange(w)[None, :]
        ref = np.full((src.size, w), big, np.int64)
        take = col < n_ref[:, None]
        ref[take] = ids[(off[src][:, None] + col)[take]]
        g = np.full((src.size, w), big, np.int64)
        g[:, :got.shape[1]] = got
        g[col >= n_res[:, None]] = big
        g.sort(axis=1)
        bad = (n_res != n_ref) | np.any(g != ref, axis=1)
        wrong += int(bad.sum())
    return {"wrong_rows": (wrong, 0)}, wrong


# ----------------------------------------------------------------- run

class Readings(NamedTuple):
    """What a per-layer metric's reader gets."""
    counters: dict          # window totals the client kept
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
    from bench import deploy, trace as tracelib, traffic as trlib
    trlib.check(traffic)
    index = deploy.load_index(cfg, cfg_path, cache_dir)
    fit = deploy.load_fit(cfg, cfg_path, index, cache_dir)
    print(f"# bank: grid {fit.grid}², exact-fit {fit.exact_fit:.4f}, "
          f"pool {fit.pool.shape[0]}", flush=True)
    server = range_server(fit, cfg)
    reqs = trlib.draw(traffic, seed, fit.pool)
    warm_reqs = trlib.draw(traffic, WARM_SEED, fit.pool)
    spans = Spans()
    client = Client(server, spans, wide_tier=wide_tier)
    print(f"# {warm(client, warm_reqs, spans)}", flush=True)
    client = Client(server, spans, wide_tier=wide_tier)
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
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    n = client.counters["queries"]
    print(f"# window: {len(client.latencies)} requests, {n} queries in "
          f"{window_s:.3f}s; inside it {counter.counts['lowerings']} "
          f"lowerings, {counter.counts['compiles']} compiles", flush=True)
    # the program's device state is freed before the reference runs
    for leaf in jax.tree.leaves(server.state):
        leaf.delete()
    del server

    checks, failed = check_range(client, reqs, fit)
    correct = all(v <= lim for v, lim in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
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
