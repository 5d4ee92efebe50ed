"""Tests of what the benchmark reads of the program's own instrumentation
(``bench.program`` and the readers that use it), run by hand with the
harness's other tests:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import contextlib
import os
import shutil
import sys
from collections import deque
from typing import NamedTuple

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import program, run, trace  # noqa: E402
from repro.core import schedule, telemetry  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("result_gather_share", "wide_pad_share", "pulled_bytes_per_query")


def _report(n, reserved, batch=512, pulled=0):
    wide = -(-reserved // batch)
    return schedule.ServeReport(
        stats=None, n_queries=n, n_batches=-(-n // batch),
        n_reserved=reserved, wide_batches=wide, sort="hilbert",
        pad_rows=-(-n // batch) * batch - n,
        wide_pad_rows=wide * batch - reserved, pulled_bytes=pulled)


@pytest.fixture
def served(monkeypatch):
    """An empty scheduler log in place of the process's own."""
    log = deque(maxlen=schedule.SERVED.maxlen)
    monkeypatch.setattr(schedule, "SERVED", log)
    return log


def test_phase_of_reads_the_scope_path():
    assert program.phase_of(
        "jit(<unknown>)/jit(hybrid_query)/r/jit(range_query_compact)/"
        "gather_ids/vmap(jit(searchsorted))/vmap()/while/body/gather:"
    ) == "r/gather_ids"
    # a loop XLA merged from both paths carries both scopes
    assert program.phase_of("jit(hybrid_query)/r/ai/jit(range_query_"
                            "compact)/jit(ai_query_compact)/gather_ids/"
                            "gather_ids/while") == "r/ai/gather_ids"
    assert program.phase_of("jit(hybrid_query)/select/select_n:") == \
        "select"
    assert program.phase_of("jit(hybrid_query)/jit(range_query_compact)/"
                            "vmap(jit(searchsorted))/while") == ""
    assert program.phase_of("") == ""


def test_phase_and_program_span_arithmetic():
    tr = trace.Trace(window=(0, 100), spans=[], n_chips=1, ops=[
        trace.Op("while", 10, 40, "while"),
        trace.Op("fusion", 15, 25, "fusion"),
        trace.Op("leaf_refine", 60, 70, "leaf_refine name"),
        trace.Op("copy", 80, 85, "copy")])
    phases = {"while": "r/gather_ids", "fusion": "r/gather_ids",
              "leaf_refine": "ai/refine", "copy": ""}
    got = dict(program.device_phases(tr, phases))
    assert got == pytest.approx({"r/gather_ids": 30e-9,
                                 "ai/refine": 10e-9, "unscoped": 5e-9})
    assert sum(got.values()) == pytest.approx(trace.busy_s(tr))
    spans = (("serve.request", 0, 100, {"request": 3}),
             ("serve.keys", 0, 8, {}),
             ("serve.step", 8, 45, {}),
             ("serve.pull", 45, 58, {}),
             ("serve.wide", 58, 95, {}),
             ("serve.step", 58, 75, {}))
    # gaps: [0,10) keys 8 + step 2; [40,60) step 5 + pull 13 + wide's
    # step 2; [70,80) wide's step 5 + wide 5; [85,100) wide 10 +
    # request 5
    assert dict(program.idle_by_program_span(tr, spans)) == pytest.approx(
        {"serve.keys": 8e-9, "serve.step": 14e-9, "serve.pull": 13e-9,
         "serve.wide": 15e-9, "serve.request": 5e-9})
    assert dict(program.idle_by_program_span(tr, ())) == pytest.approx(
        {"other": 55e-9})
    # the harness's own reductions read the trace as they did
    assert dict(trace.top_ops(tr)) == pytest.approx(
        {"while": 20e-9, "fusion": 10e-9, "leaf_refine": 10e-9,
         "copy": 5e-9})


def test_served_window_is_the_newest_requests(served):
    served.extend([_report(512, 100, pulled=7)] * 2 +      # warm-up
                  [_report(512, 150, pulled=10), _report(512, 160,
                                                         pulled=20)])
    r = run.Readings(counters={"queries": 1024, "wide_rows": 310},
                     trace=None, device_kind="TPU v5 lite",
                     entries_per_leaf=200)
    assert [x.n_reserved for x in program.served_in_window(r.counters)] \
        == [150, 160]
    assert run.reader("wide_pad_share")(r) == pytest.approx(
        100 * (1024 - 310) / 1024)
    assert run.reader("pulled_bytes_per_query")(r) == pytest.approx(
        30 / 1024)
    # a log that does not hold the window reads nothing
    for counters in ({"queries": 1024, "wide_rows": 311},
                     {"queries": 1000, "wide_rows": 310},
                     {"queries": 4096, "wide_rows": 510},
                     {"queries": 0}):
        r = r._replace(counters=counters)
        assert program.served_in_window(counters) is None
        assert run.reader("wide_pad_share")(r) is None
        assert run.reader("pulled_bytes_per_query")(r) is None
    # no wide batch in the window: no share of wide padding
    served.append(_report(512, 0))
    r = r._replace(counters={"queries": 512, "wide_rows": 0})
    assert run.reader("wide_pad_share")(r) is None
    assert run.reader("pulled_bytes_per_query")(r) == 0.0


# what the harness's reductions and readers read on the recorded
# crimes-range trace before the program recorded anything of itself
CRIMES_TOP_OPS = [
    ["fusion.81 s32[2097152]", 6.054620065],
    ["fusion.79 s32[2097152]", 2.3953306540000003],
    ["fusion.7 s32[2097152]", 0.35147641300000004],
    ["fusion.77 s32[262144]", 0.29943546600000004],
    ["fusion.75 s32[262144]", 0.29943418800000005],
    ["leaf_refine", 0.20293054100000002],
    ["fusion.5 s32[2097152]", 0.171094309],
    ["fusion.5 s32[262144]", 0.042784721000000005],
    ["fusion.7 s32[262144]", 0.042374666000000005],
    ["mlp_union", 0.016376158000000002]]
CRIMES_IDLE = [["merge", 0.13485021800000002], ["to_host", 0.13424177],
               ["schedule", 0.12804906400000002],
               ["other", 0.016876991], ["wide_step", 1.05e-07],
               ["narrow_step", 7.200000000000001e-08]]


def _crimes_readings(tr):
    return run.Readings(counters={"queries": 4096, "wide_rows": 127,
                                  "leaf_accesses": 109240, "ai_rows": 25,
                                  "refine_leaves": 50000},
                        trace=tr, device_kind="TPU v5 lite",
                        entries_per_leaf=128)


def _traces_of(tmp_path, name):
    """A trace root holding the recorded trace ``name`` alone."""
    root = tmp_path / "traces"
    (root / "cell").mkdir(parents=True)
    shutil.copy(os.path.join(DATA, name), root / "cell" / name)
    return str(root)


def test_existing_readings_unchanged_on_the_recorded_trace():
    tr = trace.load(os.path.join(DATA, "crimes-range.xplane.pb.gz"))
    assert trace.top_ops(tr) == CRIMES_TOP_OPS
    assert trace.idle_by_span(tr) == CRIMES_IDLE
    assert trace.busy_s(tr) == 9.967876668
    assert trace.kernel_s(tr, "leaf_refine") == 0.20293054100000002
    r = _crimes_readings(tr)
    assert run.reader("idle_share")(r) == 3.987886840181243
    assert run.reader("leaf_refine_roofline")(r) == 0.030806236561140645
    assert run.reader("wide_row_share")(r) == 3.1005859375
    assert run.reader("ai_answer_share")(r) == 0.6103515625
    assert run.reader("leaf_accesses_per_query")(r) == 26.669921875


def test_new_readers_read_nothing_on_the_old_trace(tmp_path, monkeypatch):
    """The crimes-range trace predates the named scopes and the program's
    spans, and its program kept no served log: every new reading is left
    out."""
    monkeypatch.setattr(program, "TRACE_ROOT", _traces_of(
        tmp_path, "crimes-range.xplane.pb.gz"))
    monkeypatch.delattr(schedule, "SERVED")
    tr = trace.load(os.path.join(DATA, "crimes-range.xplane.pb.gz"))
    rec = program.recorded(tr)
    assert rec is not None and rec.spans == ()
    assert program.device_phases(tr, rec.phases) == [
        [program.UNSCOPED, trace.busy_s(tr)]]
    [(name, idle)] = program.idle_by_program_span(tr, rec.spans)
    assert name == "other"
    assert idle == pytest.approx(tr.window_s - trace.busy_s(tr))
    r = _crimes_readings(tr)
    for m in NEW:
        assert run.reader(m)(r) is None
    # nor where the run's trace file cannot be found
    monkeypatch.setattr(program, "TRACE_ROOT", str(tmp_path / "none"))
    assert program.recorded(tr) is None


# the ``# counters:`` line of the run that recorded gaussian-range.xplane
GAUSSIAN_COUNTERS = {"queries": 2048, "wide_rows": 602,
                     "leaf_accesses": 112390, "ai_rows": 15,
                     "refine_leaves": 150918}
GAUSSIAN_PULLED = 37851136


def test_phases_spans_and_counters_on_a_recorded_chip_trace(
        tmp_path, monkeypatch, served):
    """The 5 s window of a gaussian-range run (seed 3000000019, four
    requests) on one TPU v5e, recorded with ``--trace 1`` from a program
    with named scopes and its own spans; only the planes, lines and
    stats the reduction reads are kept (the chip's ``XLA Ops`` with each
    op's ``tf_op``, the host thread's spans with their attributes)."""
    monkeypatch.setattr(program, "TRACE_ROOT", _traces_of(
        tmp_path, "gaussian-range.xplane.pb.gz"))
    tr = trace.load(os.path.join(DATA, "gaussian-range.xplane.pb.gz"))
    rec = program.recorded(tr)
    busy = trace.busy_s(tr)
    phases = dict(program.device_phases(tr, rec.phases, n=None))
    assert sum(phases.values()) == pytest.approx(busy)
    assert busy - phases[program.UNSCOPED] >= 0.95 * busy
    assert {"route", "guard", "select", "ai/predict", "ai/refine",
            "ai/gather_ids", "r/traverse", "r/refine",
            "r/gather_ids"} <= set(phases)
    # the wide tier's R-path result-id gather leads
    assert program.device_phases(tr, rec.phases, n=1)[0][0] == \
        "r/gather_ids"
    # one serve.request span per request, each with its own id, and the
    # wide tier's spans under it
    reqs = [(a["request"], a["rows"]) for n, _, _, a in rec.spans
            if n == "serve.request"]
    assert len(reqs) == len({i for i, _ in reqs}) == 4
    wide = {a["request"]: a["rows"] for n, _, _, a in rec.spans
            if n == "serve.wide"}
    assert all(a["tier"] == "wide" for n, _, _, a in rec.spans
               if n in ("serve.wide", "serve.merge"))
    assert sum(wide.values()) == GAUSSIAN_COUNTERS["wide_rows"]
    idle = program.idle_by_program_span(tr, rec.spans, n=None)
    assert {n for n, _ in idle} <= {n for n, *_ in rec.spans} | {"other"}
    assert sum(s for _, s in idle) + busy == pytest.approx(tr.window_s,
                                                           rel=1e-6)
    # the scheduler's log of the run's requests, as the spans give them
    # and with the pulled bytes of its counters line
    served.extend(_report(n, wide.get(i, 0),
                          pulled=GAUSSIAN_PULLED // len(reqs))
                  for i, n in reqs)
    r = run.Readings(counters=GAUSSIAN_COUNTERS, trace=tr,
                     device_kind="TPU v5 lite", entries_per_leaf=200)
    assert 95.0 <= run.reader("result_gather_share")(r) <= 100.0
    # every request is one batch of 512 and pays one wide batch, so the
    # wide tier's padding is what the re-served rows leave of it
    assert run.reader("wide_pad_share")(r) == pytest.approx(
        100.0 - run.reader("wide_row_share")(r))
    # a narrow row returns 5 bools, 5 i32 and 512 result ids (2,073 B), a
    # wide row 4,096 result ids (16,409 B); one batch of each per request
    assert run.reader("pulled_bytes_per_query")(r) == 2073 + 16409
    # the harness's own spans still read beside the program's
    assert {n for n, _ in trace.idle_by_span(tr, n=50)} <= \
        set(trace.SPANS) | {"other"}
    got = program.breakdown(os.path.join(DATA,
                                         "gaussian-range.xplane.pb.gz"))
    assert got["requests"] == 4
    assert got["program_spans"]["serve.request"] == 4
    assert got["device_phases"] == [[p, s] for p, s in
                                    program.device_phases(
                                        tr, rec.phases, n=None)]


def test_program_span_names_are_apart_from_the_harness_spans(monkeypatch):
    names = set()

    def record(name, **attrs):
        names.add(name)
        return contextlib.nullcontext()
    monkeypatch.setattr(telemetry, "span_factory", record)
    q = np.random.default_rng(0).uniform(0, 1, (20, 4)).astype(np.float32)
    q[:, 2:] += q[:, :2]

    class Stats(NamedTuple):
        truncated: np.ndarray

    def step(qb):       # flags every other row for the wide tier
        return Stats(np.arange(qb.shape[0]) % 2 == 0)
    rep = schedule.serve_workload(step, q, batch=8, wide_fn=step,
                                  trunc_field="truncated")
    assert rep.n_reserved == 10
    assert names == {"serve.request", "serve.keys", "serve.sort",
                     "serve.step", "serve.pull", "serve.unpermute",
                     "serve.wide", "serve.merge"}
    assert all(n.startswith(program.PROGRAM_PREFIX) for n in names)
    assert not names & set(trace.SPANS) and trace.WINDOW not in names
    assert not any(n.startswith(program.PROGRAM_PREFIX)
                   for n in trace.SPANS)
