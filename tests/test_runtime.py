"""Open-loop streaming runtime: offline equivalence + deadline contracts.

The runtime's whole promise is two-sided: (1) when no deadline forces a
degraded row, its results are *bit-identical* to offline
``serve_workload`` over the same admitted queries — batch grouping is
invisible; (2) when a deadline does fire, the affected rows keep their
best-effort narrow results and are flagged (degraded + still truncated),
never silently dropped. Everything here runs with an injected
``service_time`` model, so the simulated clock — and therefore every
dispatch/degrade decision — is deterministic.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import runtime, schedule, traversal
from repro.data import arrivals
from repro.data.synth_tree import synth_levels
from repro.core.device_tree import DeviceTree, Level


def teardown_module(module):
    # This module jits many one-off (batch, k) serve shapes; drop them so
    # later modules' large kernel compiles don't run on top of the pile.
    jax.clear_caches()


def _queries(n, seed=0, big_frac=0.0):
    rng = np.random.default_rng(seed)
    lo = rng.uniform(-1, 1, (n, 2))
    w = rng.uniform(0, 0.1, (n, 2))
    big = rng.uniform(size=n) < big_frac
    w[big] = rng.uniform(0.5, 1.5, (int(big.sum()), 2))
    return np.concatenate([lo, lo + w], 1).astype(np.float32)


def _tree(L=64, fanout=4, seed=0):
    rng = np.random.default_rng(seed)
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    return DeviceTree(
        levels=tuple(Level(mbrs=jnp.asarray(m), parent=jnp.asarray(p))
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, 8)), jnp.float32),
        leaf_entry_ids=jnp.arange(L * 8, dtype=jnp.int32).reshape(L, 8),
        leaf_counts=jnp.full((L,), 8, jnp.int32),
        n_points=L * 8, max_entries=fanout)


def _serve_fn(tree, k=8, max_results=256):
    return lambda q: traversal.range_query_compact(
        tree, q, max_visited=k, max_results=max_results, use_kernel=False)


def _assert_same(a, b):
    for f in type(a)._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


def _const_cost(narrow=0.01, wide=0.03):
    return lambda n_valid, tier: narrow if tier == "narrow" else wide


# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------

def test_poisson_arrivals_rate_and_determinism():
    a = arrivals.poisson_arrivals(20_000, rate=50.0, seed=3)
    assert a.shape == (20_000,) and np.all(np.diff(a) >= 0) and a[0] > 0
    assert abs(20_000 / a[-1] - 50.0) / 50.0 < 0.05
    np.testing.assert_array_equal(
        a, arrivals.poisson_arrivals(20_000, rate=50.0, seed=3))


def test_bursty_arrivals_same_mean_higher_variance():
    p = arrivals.poisson_arrivals(20_000, rate=100.0, seed=0)
    b = arrivals.bursty_arrivals(20_000, rate=100.0, seed=0)
    assert np.all(np.diff(b) >= 0)
    # mean rate normalized to target (sum of gaps is exact; diff drops
    # the lead-in gap, so compare end-to-end)
    assert abs(b[-1] - 20_000 * 0.01) < 1e-6
    # burstiness: gap coefficient of variation strictly above Poisson's
    cv = lambda x: np.diff(x).std() / np.diff(x).mean()
    assert cv(b) > 1.3 * cv(p)


def test_trace_roundtrip(tmp_path):
    src = arrivals.poisson_arrivals(500, rate=10.0, seed=1)
    path = str(tmp_path / "trace.npy")
    arrivals.save_trace(path, src)
    # truncate, tile, and rescale
    t = arrivals.load_trace(path, n=200)
    assert t.shape == (200,) and np.all(np.diff(t) >= 0) and t[0] > 0
    t2 = arrivals.load_trace(path, n=1200, rate=40.0)
    assert t2.shape == (1200,) and np.all(np.diff(t2) >= 0)
    assert abs(1200 / t2[-1] - 40.0) / 40.0 < 0.01
    with pytest.raises(ValueError):
        arrivals.make_arrivals("trace", 10, 1.0)      # no path
    with pytest.raises(ValueError):
        arrivals.make_arrivals("nope", 10, 1.0)


# ---------------------------------------------------------------------------
# offline equivalence: no deadline pressure → bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("formation", ["deadline", "full"])
@pytest.mark.parametrize("rate", [200.0, 2000.0])
def test_runtime_bit_identical_to_offline(formation, rate):
    """Any batch grouping the open loop produces — partial dispatches,
    urgency-centered curve windows, immediate wide re-serves — must be
    invisible in the per-query results when deadlines never bind."""
    tree = _tree()
    q = _queries(150, seed=5, big_frac=0.2)
    arr = arrivals.poisson_arrivals(150, rate=rate, seed=2)
    narrow = _serve_fn(tree, k=4)
    wide = _serve_fn(tree, k=64)
    rep = runtime.run_stream(
        narrow, q, arr, batch=32, deadline_s=1e9, wide_fn=wide,
        trunc_field="truncated", formation=formation,
        service_time=_const_cost())
    off = schedule.serve_workload(narrow, q, batch=32, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    assert rep.n_degraded == 0
    _assert_same(rep.stats, off.stats)
    # zero silent drops: every query completed after it arrived
    assert np.all(rep.done_s > rep.arrival_s)
    assert rep.goodput == 1.0


def test_runtime_no_wide_fn_matches_offline_narrow():
    tree = _tree()
    q = _queries(60, seed=7, big_frac=0.3)
    arr = arrivals.poisson_arrivals(60, rate=500.0, seed=0)
    narrow = _serve_fn(tree, k=4)
    rep = runtime.run_stream(narrow, q, arr, batch=16, deadline_s=1e9,
                             trunc_field="truncated",
                             service_time=_const_cost())
    off = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    _assert_same(rep.stats, off.stats)
    assert rep.n_wide_batches == 0


def test_runtime_single_query_and_tiny_batches():
    tree = _tree()
    q = _queries(1, seed=1)
    arr = arrivals.poisson_arrivals(1, rate=10.0)
    rep = runtime.run_stream(_serve_fn(tree), q, arr, batch=8,
                             deadline_s=1e9, service_time=_const_cost())
    off = schedule.serve_workload(_serve_fn(tree), q, batch=8,
                                  sort="hilbert")
    _assert_same(rep.stats, off.stats)
    assert rep.n_batches == 1 and rep.mean_fill == pytest.approx(1 / 8)


# ---------------------------------------------------------------------------
# deadline behavior
# ---------------------------------------------------------------------------

def test_deadline_formation_dispatches_partial_batches():
    """Sparse arrivals + binding deadlines: the open loop must ship
    partially-full batches on time instead of waiting to fill — the
    fixed-full-batch baseline blows every early deadline instead."""
    tree = _tree()
    q = _queries(40, seed=3)
    arr = arrivals.poisson_arrivals(40, rate=100.0, seed=4)   # ~10ms gaps
    cost = _const_cost(narrow=0.005, wide=0.005)
    dl = runtime.run_stream(_serve_fn(tree), q, arr, batch=32,
                            deadline_s=0.05, formation="deadline",
                            service_time=cost)
    fb = runtime.run_stream(_serve_fn(tree), q, arr, batch=32,
                            deadline_s=0.05, formation="full",
                            service_time=cost)
    assert dl.mean_fill < 1.0
    assert dl.n_missed < fb.n_missed
    assert dl.goodput > fb.goodput
    assert dl.telemetry["latency_s"]["p99"] \
        < fb.telemetry["latency_s"]["p99"]
    # and the underlying answers still agree row-for-row
    _assert_same(dl.stats, fb.stats)


def test_degraded_rows_flagged_never_dropped():
    """Tight deadlines + expensive wide tier: truncated rows whose
    re-serve would blow the deadline keep their narrow best-effort
    answer, stay flagged truncated, and are marked degraded; rows with
    slack still get exact wide answers."""
    tree = _tree()
    q = _queries(80, seed=11, big_frac=0.5)
    arr = arrivals.poisson_arrivals(80, rate=5000.0, seed=1)
    narrow = _serve_fn(tree, k=4)
    wide = _serve_fn(tree, k=64)
    # wide steps cost more than the whole deadline → every truncated
    # row must degrade
    rep = runtime.run_stream(
        narrow, q, arr, batch=16, deadline_s=0.05, wide_fn=wide,
        trunc_field="truncated", formation="deadline",
        service_time=_const_cost(narrow=0.001, wide=10.0))
    off_n = schedule.serve_workload(narrow, q, batch=16, sort="hilbert")
    trunc = np.asarray(off_n.stats.truncated).astype(bool)
    assert trunc.any(), "fixture too weak: nothing overflowed"
    assert rep.n_wide_batches == 0
    assert rep.n_degraded == int(trunc.sum())
    np.testing.assert_array_equal(rep.degraded, trunc)
    # degraded rows: narrow answers, truncation flag intact
    _assert_same(rep.stats, off_n.stats)
    # zero drops: every row has a completion stamp and a result row
    assert np.all(rep.done_s > 0)

    # generous wide cost → the same rows re-serve and match offline
    rep2 = runtime.run_stream(
        narrow, q, arr, batch=16, deadline_s=1e9, wide_fn=wide,
        trunc_field="truncated", formation="deadline",
        service_time=_const_cost())
    off_w = schedule.serve_workload(narrow, q, batch=16, sort="hilbert",
                                    wide_fn=wide, trunc_field="truncated")
    assert rep2.n_degraded == 0
    _assert_same(rep2.stats, off_w.stats)


def test_degrade_is_per_row_not_per_batch():
    """Per-query deadlines: within one narrow batch, only the rows whose
    own slack fails the wide-cost test degrade."""
    tree = _tree()
    q = _queries(30, seed=13, big_frac=1.0)    # everything truncates @k=4
    arr = np.full((30,), 0.001)
    deadlines = np.where(np.arange(30) % 2 == 0, 10.0, 1e-4)
    rep = runtime.run_stream(
        _serve_fn(tree, k=4), q, arr, batch=30, deadline_s=deadlines,
        wide_fn=_serve_fn(tree, k=64), trunc_field="truncated",
        formation="deadline", service_time=_const_cost(0.01, 0.05))
    off_n = schedule.serve_workload(_serve_fn(tree, k=4), q, batch=30,
                                    sort="hilbert")
    trunc = np.asarray(off_n.stats.truncated).astype(bool)
    odd = np.arange(30) % 2 == 1
    assert (trunc & odd).sum() > 5, "fixture too weak"
    np.testing.assert_array_equal(rep.degraded, trunc & odd)
    # even-index rows got exact wide answers
    off_w = schedule.serve_workload(
        _serve_fn(tree, k=4), q, batch=30, sort="hilbert",
        wide_fn=_serve_fn(tree, k=64), trunc_field="truncated")
    sel = ~rep.degraded
    for f in type(rep.stats)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rep.stats, f))[sel],
            np.asarray(getattr(off_w.stats, f))[sel], err_msg=f)


def test_runtime_telemetry_and_validation():
    tree = _tree()
    q = _queries(20, seed=0)
    arr = arrivals.poisson_arrivals(20, rate=100.0)
    rep = runtime.run_stream(_serve_fn(tree), q, arr, batch=8,
                             deadline_s=1.0, service_time=_const_cost())
    t = rep.telemetry
    assert t["latency_s"]["n"] == 20
    assert t["latency_s"]["p50"] <= t["latency_s"]["p99"]
    assert t["ewma_narrow_s"] == pytest.approx(0.01)
    assert t["queue_depth"]["n"] == rep.n_batches
    with pytest.raises(ValueError):
        runtime.run_stream(_serve_fn(tree), q, arr[:-1], batch=8,
                           deadline_s=1.0)
    with pytest.raises(ValueError):
        runtime.run_stream(_serve_fn(tree), q, arr, batch=8,
                           deadline_s=1.0, formation="nope")
    with pytest.raises(ValueError):
        runtime.run_stream(_serve_fn(tree), q, arr[::-1], batch=8,
                           deadline_s=1.0)
