"""Tests of the benchmark harness, run by hand (outside the repo's tier-1
suite):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They drive a run at a tiny cut of ``gaussian-872k`` on the CPU, skipping
only the harness's look for a chip.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import deploy, peaks, run, trace, traffic as trlib  # noqa: E402
from bench.drivers import range as range_driver  # noqa: E402
from bench.drivers import range_insert  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ------------------------------------------------------------ arithmetic

def test_p50_is_over_every_request():
    lat = [0.001 * i for i in range(1, 102)]        # 1..101 ms
    got = run.end_to_end(lat, queries=51200, window_s=10.0, setup_s=3.0)
    assert got["p50_ms"] == pytest.approx(51.0)
    assert got["qps"] == pytest.approx(5120.0)
    assert got["setup_s"] == 3.0
    # the median of every request, not of chunks of requests
    got = run.end_to_end([0.01] * 49 + [1.0] * 51, 1, 1.0, 0.0)
    assert got["p50_ms"] == pytest.approx(1000.0)
    got = run.end_to_end([0.01] * 51 + [1.0] * 49, 1, 1.0, 0.0)
    assert got["p50_ms"] == pytest.approx(10.0)


def test_roofline_arithmetic():
    kind = "TPU v5 lite"
    # bytes-bound: 819 GB at 819 GB/s is 1 s; measured 2 s
    assert peaks.roofline_share(kind, flops=0.0, bytes_moved=819e9,
                                kernel_s=2.0) == pytest.approx(50.0)
    # flops-bound when the operations dominate
    assert peaks.roofline_share(kind, flops=197e12, bytes_moved=1.0,
                                kernel_s=4.0) == pytest.approx(25.0)
    with pytest.raises(KeyError):
        peaks.roofline_share("TPU v99", flops=1.0, bytes_moved=1.0,
                             kernel_s=1.0)
    with pytest.raises(ValueError):
        peaks.roofline_share(kind, flops=1.0, bytes_moved=1.0, kernel_s=0)


def test_cache_key_follows_every_program_file(tmp_path):
    src = tmp_path / "repro"
    (src / "core").mkdir(parents=True)
    (src / "core" / "a.py").write_text("x = 1\n")
    (src / "kernels").mkdir()
    (src / "kernels" / "cache.json").write_text("{}\n")
    cfg = tmp_path / "cfg.json"
    cfg.write_text("{}")
    key = deploy.cache_key(str(cfg), str(src))
    (src / "core" / "__pycache__").mkdir()
    (src / "core" / "__pycache__" / "a.cpython.pyc").write_bytes(b"\0")
    assert deploy.cache_key(str(cfg), str(src)) == key
    (src / "kernels" / "cache.json").write_text('{"a": 1}\n')
    key2 = deploy.cache_key(str(cfg), str(src))
    assert key2 != key
    (src / "core" / "b.py").write_text("")
    key3 = deploy.cache_key(str(cfg), str(src))
    assert key3 != key2
    cfg.write_text('{"x": 1}')
    assert deploy.cache_key(str(cfg), str(src)) != key3


# ----------------------------------------------------------------- trace

def _trace(ops, spans, window=(0, 100)):
    return trace.Trace(window=window,
                       ops=[trace.Op(n, s, e, n) for n, s, e in ops],
                       spans=spans, n_chips=1)


def test_trace_reduction_arithmetic():
    # a loop with a kernel nested in it, then another operation
    tr = _trace([("while", 10, 40), ("leaf_refine", 15, 25),
                 ("leaf_refine", 60, 70)],
                [("schedule", 0, 10), ("narrow_step", 10, 50),
                 ("to_host", 50, 55), ("merge", 55, 90)])
    assert trace.busy_s(tr) == pytest.approx(40e-9)
    assert trace.kernel_s(tr, "leaf_refine") == pytest.approx(20e-9)
    # self time: the loop's 30 ns less the 10 ns nested in it
    assert dict(trace.top_ops(tr)) == pytest.approx(
        {"while": 20e-9, "leaf_refine": 20e-9})
    idle = dict(trace.idle_by_span(tr))
    # gaps: [0,10) schedule; [40,60) narrow 10 + to_host 5 + merge 5;
    # [70,100) merge 20 + nothing 10
    assert idle == pytest.approx({"schedule": 10e-9, "narrow_step": 10e-9,
                                  "to_host": 5e-9, "merge": 25e-9,
                                  "other": 10e-9})
    assert sum(idle.values()) + trace.busy_s(tr) == pytest.approx(
        tr.window_s)


def test_trace_reduction_on_a_recorded_chip_trace():
    """The 10 s window of a crimes-range run on one TPU v5e, recorded
    with ``--trace 1``; only the planes and lines the reduction reads
    are kept (the chip's ``XLA Ops``, the host thread with the spans)."""
    tr = trace.load(os.path.join(DATA, "crimes-range.xplane.pb.gz"))
    assert tr.n_chips == 1 and tr.window_s > 0
    busy = trace.busy_s(tr)
    assert 0 < busy <= tr.window_s
    assert trace.kernel_s(tr, "leaf_refine") > 0
    labels = {name for name, _ in trace.top_ops(tr, n=50)}
    assert {"leaf_refine", "mlp_union"} <= labels
    # the wide step's result-id gather, inside its while loop
    assert trace.top_ops(tr, n=1)[0][0] == "fusion.81 s32[2097152]"
    idle = trace.idle_by_span(tr, n=50)
    assert {n for n, _ in idle} <= set(trace.SPANS) | {"other"}
    assert sum(s for _, s in idle) + busy == pytest.approx(tr.window_s,
                                                           rel=1e-6)


def test_readers_leave_out_what_they_cannot_read():
    r = run.Readings(counters={"queries": 10, "wide_rows": 1,
                               "leaf_accesses": 30, "ai_rows": 2,
                               "refine_leaves": 5},
                     trace=_trace([("fusion", 0, 10)], []),
                     device_kind="TPU v5 lite", entries_per_leaf=200)
    assert run.reader("leaf_refine_roofline")(r) is None   # no kernel op
    assert run.reader("ai_answer_share")(r) == pytest.approx(20.0)
    assert run.reader("wide_row_share")(r) == pytest.approx(10.0)
    assert run.reader("leaf_accesses_per_query")(r) == pytest.approx(3.0)
    assert run.reader("idle_share")(r) == pytest.approx(90.0)
    r = r._replace(trace=_trace([("leaf_refine", 0, 10)], []))
    # 5 leaves x 200 entries x 8 B at 819 GB/s, over 10 ns
    assert run.reader("leaf_refine_roofline")(r) == pytest.approx(
        100 * 5 * 200 * 8 / 819e9 / 10e-9)
    r = r._replace(counters={"queries": 0}, trace=None)
    for m in ("ai_answer_share", "wide_row_share", "leaf_accesses_per_query",
              "idle_share", "leaf_refine_roofline"):
        assert run.reader(m)(r) is None


# ------------------------------------------------------------------ spec

def test_benchmark_file_names_files_that_exist():
    spec = run.load_spec(ROOT)
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        cell, cfg, path, tr = run.find_cell(spec, w["name"], ROOT)
        trlib.check(tr)
        assert cfg["name"] == cell["config"]
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for m in spec["per_layer"]:
        assert callable(run.reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells


# ------------------------------------------------------------- whole run

TINY = {
    "dataset": {"generator": "gaussian", "params": {"mean": 0.5, "sd": 0.1},
                "points": 5000, "seed": 1},
    "rtree": {"node_capacity": 32},
    "pool": {"queries": 128, "selectivity": 2e-3, "seed": 0},
    "bank": {"classifier": "mlp", "hidden": 16, "grid": 2, "tau": 0.75,
             "max_cells": 4, "max_pred": 16},
    # a narrow bound of 8 leaves sends rows to the wide tier
    "serve": {"batch": 64, "sort": "hilbert", "max_visited": 8,
              "wide_factor": 8},
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    spec = run.load_spec(ROOT)
    cfg = dict(json.load(open(os.path.join(
        ROOT, "bench", "configs", "gaussian-872k.json"))), **TINY)
    path = str(base / "tiny.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    return spec, cfg, path, str(base / "cache")


def _run(tiny, seed=2**31 + 77, **kw):
    spec, cfg, path, cache = tiny
    cell = {"name": "gaussian-range", "config": "gaussian-872k",
            "traffic": "range-pool", "chips": 1}
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        tr = dict(json.load(f), request=64)
    return run.run_cell(cell, cfg, path, tr, seed=seed, seconds=0.5,
                        trace_on=False, t_start=time.time(),
                        e2e=spec["end_to_end"], per_layer=[],
                        cache_dir=cache, **kw)


def test_run_is_correct_and_reports_its_metrics(tiny):
    res, lines = _run(tiny)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert lines and all(ln.startswith("check ") for ln in lines)


def test_control_without_the_wide_tier_is_not_correct(tiny):
    res, _ = _run(tiny, wide_tier=False)
    assert not res["correct"] and res["failed"] > 0


def _altered(out):
    """One answer altered where it is produced."""
    return out._replace(n_results=out.n_results.at[3].add(1))


def _half_left_out(out):
    """The second half of the batch's rows left unanswered."""
    half = out.n_results.shape[0] // 2
    return out._replace(n_results=out.n_results.at[half:].set(0))


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
def test_a_broken_timed_path_is_not_correct(tiny, fault, monkeypatch):
    orig = range_driver.range_server

    def broken(*a):
        s = orig(*a)
        return s._replace(narrow=lambda q: fault(s.narrow(q)),
                          wide=lambda q: fault(s.wide(q)))
    monkeypatch.setattr(range_driver, "range_server", broken)
    res, _ = _run(tiny)
    assert not res["correct"]


# --------------------------------------------------------------- drivers

def _counters(out: str) -> dict:
    """The ``# counters:`` line a run printed."""
    line = [ln for ln in out.splitlines() if ln.startswith("# counters: ")]
    return json.loads(line[-1][len("# counters: "):])


def test_traffic_without_a_driver_fails_clearly(tiny):
    with pytest.raises(ValueError, match=r"traffic query 'knn-browse' has "
                       r"no driver bench/drivers/knn_browse\.py"):
        trlib.check({"query": "knn-browse", "request": 8, "draw": "pool"})
    spec, cfg, path, cache = tiny
    cell = {"name": "gaussian-knn", "config": "gaussian-872k",
            "traffic": "knn", "chips": 1}
    with pytest.raises(ValueError, match="has no driver"):
        run.run_cell(cell, cfg, path, {"query": "knn", "request": 8,
                                       "draw": "pool"},
                     seed=1, seconds=0.5, trace_on=False,
                     t_start=time.time(), e2e=spec["end_to_end"],
                     per_layer=[], cache_dir=cache)


@pytest.mark.parametrize("bad, msg", [
    ({"draw": "points"}, r"traffic draw must be one of \('pool',\)"),
    ({"draw": None}, r"range traffic lacks \['draw'\]"),
    ({"query": "range-insert"}, r"range-insert traffic lacks \['delta_cap'"),
])
def test_a_driver_refuses_traffic_it_cannot_serve(bad, msg):
    tr = {k: v for k, v in dict({"query": "range", "request": 8,
                                 "draw": "pool"}, **bad).items()
          if v is not None}
    with pytest.raises(ValueError, match=msg):
        run.driver(tr)


def test_the_insert_stream_is_never_replayed():
    s = trlib.Stream(np.arange(20.0).reshape(10, 2))
    assert s.take(8, 2).tolist() == [[16.0, 17.0], [18.0, 19.0]]
    with pytest.raises(ValueError, match="holds 10 points; the run needs 11"):
        s.take(8, 3)


def test_range_pool_runs_through_the_range_driver(tiny, capsys):
    with open(os.path.join(ROOT, "bench", "traffic", "range-pool.json")) \
            as f:
        assert run.driver(json.load(f)) is range_driver
    res, _ = _run(tiny)
    got = _counters(capsys.readouterr().out)
    assert set(got) == {"queries", "wide_rows", "leaf_accesses", "ai_rows",
                        "refine_leaves", "lowerings", "compiles"}
    assert got["queries"] == res["attempted"] == 64 * res["requests"]
    assert 0 < got["wide_rows"] < got["queries"]


# a scaled-down range-insert mix: the repack runs after the window's
# third request, the next would need 128 more (a 0.5 s window on the CPU
# holds about 30)
MIXED = {"request": 64, "delta_cap": 1024, "repack_at": 512, "prestage": 500}


def _run_mixed(tiny, seed=2**31 + 91, **kw):
    spec, cfg, path, cache = tiny
    cell = {"name": "gaussian-mixed", "config": "gaussian-872k",
            "traffic": "range-insert", "chips": 1}
    with open(os.path.join(ROOT, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        tr = dict(json.load(f), **MIXED)
    tr["inserts"] = dict(tr["inserts"], points=2000, per_request=4)
    return run.run_cell(cell, cfg, path, tr, seed=seed, seconds=0.5,
                        trace_on=False, t_start=time.time(),
                        e2e=spec["end_to_end"], per_layer=[],
                        cache_dir=cache, **kw)


def test_mixed_run_is_correct_with_one_repack_and_nothing_compiled(
        tiny, capsys):
    res, lines = _run_mixed(tiny)
    out = capsys.readouterr().out
    got = _counters(out)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "p50_ms", "setup_s"}
    assert lines == ["check wrong_rows 0 limit 0"]
    assert "# window: 1 repacks, after requests [3]\n" in out, \
        [ln for ln in out.splitlines() if "repacks" in ln]
    assert got["repacks"] == 1 and got["repack_s"] > 0
    # set-up reports what the retrace after its rehearsed repack cost
    assert re.search(r"# warm-up: .* 1 repack at \d+ staged in [\d.]+s, "
                     r"after it \d+ lowerings, \d+ compiles \(costliest: "
                     r".*\), the first request [\d.]+s", out)
    # the warm-up rehearsed the repack: the window's steps after it find
    # their programs compiled
    assert got["lowerings"] == 0 and got["compiles"] == 0
    assert got["inserts"] == 4 * res["requests"]
    assert got["delta_hits"] > 0
    assert got["probe_tests"] > 0 and got["probe_rows"] >= got["queries"]


def test_mixed_control_without_the_wide_tier_is_not_correct(tiny):
    res, _ = _run_mixed(tiny, wide_tier=False)
    assert not res["correct"] and res["failed"] > 0


def _never_staged(m):
    """Inserts acknowledged but never staged."""
    from repro.core import monitor
    m.setattr(monitor.FreshServer, "insert", lambda self, points: None)


def _hits_dropped(m):
    """The delta buffer's hits left out of the merged answers."""
    from repro.core import delta
    m.setattr(delta, "merge_hybrid_result", lambda res, hits: res)


def _shifted_after_repack(m):
    """The repack numbers the staged points one slot off."""
    import dataclasses
    import jax.numpy as jnp
    from repro.core import delta
    orig = delta.repack

    def repack(base_points, store, **kw):
        xy = np.asarray(store.xy).copy()
        xy[:store.n] = np.roll(xy[:store.n], 1, axis=0)
        return orig(base_points,
                    dataclasses.replace(store, xy=jnp.asarray(xy)), **kw)
    m.setattr(delta, "repack", repack)


def _in_step(fault):
    def plant(m):
        from repro.core import monitor
        orig = monitor.FreshServer.step
        m.setattr(monitor.FreshServer, "step",
                  lambda self, q, widen=1: fault(orig(self, q, widen)))
    plant.__name__ = fault.__name__
    return plant


@pytest.mark.parametrize("fault", [
    _never_staged, _hits_dropped, _shifted_after_repack,
    _in_step(_altered), _in_step(_half_left_out)],
    ids=lambda f: f.__name__.strip("_"))
def test_a_broken_mixed_path_is_not_correct(tiny, fault, monkeypatch):
    import jax
    with monkeypatch.context() as m:
        fault(m)
        # the jitted mixed step traces the planted code afresh
        jax.clear_caches()
        res, _ = _run_mixed(tiny)
    jax.clear_caches()
    assert not res["correct"] and res["failed"] > 0


def test_mixed_readers():
    r = run.Readings(counters={"queries": 512, "repack_s": 2.0,
                               "lowerings": 0, "probe_tests": 512 * 100,
                               "probe_staged": 100, "probe_rows": 512},
                     trace=_trace([("delta_probe", 0, 1000)], [],
                                  window=(0, 40 * 10**9)),
                     device_kind="TPU v5 lite", entries_per_leaf=200)
    assert run.reader("repack_share")(r) == pytest.approx(5.0)
    assert run.reader("window_lowerings")(r) == 0
    # bytes-bound: 100 staged x 8 B + 512 rows x 16 B over 1,000 ns
    assert run.reader("delta_probe_roofline")(r) == pytest.approx(
        100 * (800 + 8192) / 819e9 / 1e-6)
    none = r._replace(counters={"queries": 512, "probe_tests": 0})
    for m in ("repack_share", "window_lowerings", "delta_probe_roofline"):
        assert run.reader(m)(none) is None
    assert run.reader("delta_probe_roofline")(
        r._replace(trace=_trace([("fusion", 0, 10)], []))) is None
    assert run.reader("repack_share")(r._replace(trace=None)) is None
