"""Driver of ``range-insert`` traffic: range requests with inserts between
them, through the program's mixed read/write path.

The server is ``repro.launch.serve.make_fresh_server``'s single-device
``FreshServer``: the deployment's tree and bank, a delta buffer of the
traffic's ``delta_cap`` points, kernels on, no maintenance policy and no
``fit_state``, so a repack guards the whole bank. Set-up stages the
stream's first ``prestage`` points. A request is one call of
``repro.core.schedule.serve_mixed_workload`` with the request's queries
and its ``per_request`` inserts: one segment, served through the same
``serve_workload`` as ``range`` traffic, then the inserts are staged
(acknowledged when the call returns), and the buffer repacks once it
holds ``repack_at`` points (``repack_every``).

An insert's id is the tree's point count plus its position in the
stream (``core/delta.py``'s id convention, which holds across a repack).
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np

from bench.drivers import range as range_driver

KEYS = ("delta_cap", "repack_at", "prestage")


class Plan(NamedTuple):
    """What every server of the cell is built from."""
    points: np.ndarray      # [N, 2] f64 the deployment's points
    hybrid: object          # the deployment's tree and bank, on the device
    args: object            # the program's serving options
    bbox: np.ndarray        # the scheduler's fixed key frame
    batch: int
    sort: str
    stream: object          # bench.traffic.Stream of inserted points
    per_request: int
    prestage: int
    repack_at: int

    def repack_count(self) -> int:
        """Points staged when the first repack fires: the pre-staged ones
        plus whole requests' inserts, until ``repack_at`` is reached."""
        k = -(-(self.repack_at - self.prestage) // self.per_request)
        return self.prestage + k * self.per_request


def check_traffic(traffic: dict) -> None:
    range_driver.check_traffic(traffic, range_driver.KEYS + KEYS
                               + ("inserts",))
    at, pre = int(traffic["repack_at"]), int(traffic["prestage"])
    if int(traffic["inserts"].get("per_request", 0)) < 1 or \
            not 0 <= pre < at:
        raise ValueError("range-insert traffic needs per_request >= 1 and "
                         "0 <= prestage < repack_at")


requests = range_driver.requests


def make_server(index, fit, cfg: dict, traffic: dict, cell: dict) -> Plan:
    from bench import traffic as trlib
    from repro.core import schedule
    cap, at, pre = (int(traffic[k]) for k in KEYS)
    per = int(traffic["inserts"]["per_request"])
    plan = Plan(np.asarray(index.points, np.float64), fit.hybrid,
                range_driver.serve_args(cfg, "--delta-cap", str(cap)),
                schedule.workload_bbox(fit.pool), cfg["serve"]["batch"],
                cfg["serve"]["sort"], trlib.inserts(traffic), per, pre, at)
    if plan.repack_count() > cap:
        raise ValueError(f"the buffer of {cap} points cannot hold the "
                         f"{plan.repack_count()} staged at the repack")
    return plan


class Server:
    """The ``FreshServer`` as ``serve_mixed_workload`` drives it, each
    step's output pulled to numpy under the harness's spans, the inserts
    under ``insert`` and the repack under ``repack``, timed into the
    counters with the delta probe's work."""

    def __init__(self, fresh, spans, counters: dict, wide_tier: bool):
        self.fresh = fresh
        self.spans = spans
        self.c = counters
        # without a flag to re-serve, the scheduler runs no wide tier
        self.trunc_field = fresh.trunc_field if wide_tier else None

    def _step(self, fn, span, q):
        import jax
        fill = self.fresh.delta_fill
        rows = q.shape[0]
        self.c["probe_calls"] += 1
        self.c["probe_rows"] += rows
        self.c["probe_staged"] += fill
        self.c["probe_tests"] += rows * fill
        self.spans.phase(span)
        out = fn(q)
        self.spans.phase("to_host")
        out = jax.tree.map(np.asarray, out)
        self.spans.phase("merge")
        return out

    def serve(self, q):
        return self._step(self.fresh.serve, "narrow_step", q)

    def serve_wide(self, q):
        return self._step(self.fresh.serve_wide, "wide_step", q)

    @property
    def delta_fill(self) -> int:
        return self.fresh.delta_fill

    def insert(self, points) -> None:
        self.spans.phase("insert")
        self.fresh.insert(points)

    def repack(self) -> None:
        import jax
        self.spans.phase("repack")
        t0 = time.perf_counter()
        self.fresh.repack()
        jax.block_until_ready(self.fresh.hybrid)
        self.c["repack_s"] += time.perf_counter() - t0
        self.c["repacks"] += 1

    def on_segment(self):
        return self.fresh.on_segment()


class Client:
    """One closed-loop client: a ``FreshServer`` of its own, the stream's
    first ``prestage`` points staged, then one
    ``schedule.serve_mixed_workload`` call per request."""

    def __init__(self, plan: Plan, spans, wide_tier: bool = True):
        import jax
        from repro.launch import serve
        self.p = plan
        self.spans = spans
        self.counters = {"queries": 0, "wide_rows": 0, "leaf_accesses": 0,
                         "ai_rows": 0, "inserts": 0, "delta_hits": 0,
                         "repacks": 0, "repack_s": 0.0, "probe_calls": 0,
                         "probe_rows": 0, "probe_staged": 0,
                         "probe_tests": 0}
        self.latencies = []
        # (inserts staged before the request, n_results, result ids)
        self.answers = []
        self.repacked_after = []    # requests after which a repack ran
        self.fresh = serve.make_fresh_server(plan.points, plan.hybrid,
                                             plan.args, jax.devices()[:1])
        self.fresh.insert(plan.stream.take(0, plan.prestage))
        self.staged = plan.prestage
        self.server = Server(self.fresh, spans, self.counters, wide_tier)

    @property
    def state(self):
        return [self.p.hybrid, self.fresh.hybrid, self.fresh.delta.xy]

    def serve(self, q: np.ndarray):
        """Serve one request and stage its inserts; returns the
        ``MixedReport``."""
        from repro.core import schedule
        ins = self.p.stream.take(self.staged, self.p.per_request)
        self.spans.phase("schedule")
        rep = schedule.serve_mixed_workload(
            self.server, q, ins, batch=self.p.batch, sort=self.p.sort,
            bbox=self.p.bbox, insert_every=1, repack_every=self.p.repack_at)
        self.spans.phase(None)
        return rep

    def record(self, q, rep, latency: float) -> None:
        st = rep.stats
        c = self.counters
        c["queries"] += q.shape[0]
        c["wide_rows"] += rep.n_reserved
        c["leaf_accesses"] += int(np.sum(st.leaf_accesses))
        c["ai_rows"] += int(np.sum(st.used_ai))
        c["inserts"] += rep.n_inserts
        c["delta_hits"] += int(np.sum(st.delta_hits))
        if rep.n_repacks:
            self.repacked_after.append(len(self.latencies))
        self.latencies.append(latency)
        w = max(int(np.max(st.n_results)), 1)
        self.answers.append((self.staged, np.asarray(st.n_results),
                             np.asarray(st.result_ids)[:, :w]))
        self.staged += rep.n_inserts


def warm(client: Client, reqs, spans) -> str:
    """On a throw-away client (its own ``FreshServer``, the same
    pre-staged buffer; the jit cache is the process's): the warm-up
    requests through both tiers, then the stream staged up to the point
    where the window repacks, the repack itself and warm-up requests
    after it, so the window's steps after its repack find their programs
    compiled; then the spatial sort at every count of re-served rows a
    request can plausibly hold before or after the repack."""
    from bench import run as harness
    p = client.p
    t0 = time.perf_counter()
    seen = []       # (rows, re-served rows) before and after the repack

    def requests(first: int, n: int) -> tuple[int, int]:
        rows = wide = 0
        for i in range(first, first + n):
            q = reqs.queries(i)
            rep = client.serve(q)
            client.staged += rep.n_inserts
            rows += q.shape[0]
            wide += rep.n_reserved
        return rows, wide

    seen.append(requests(0, range_driver.WARM_REQUESTS))
    q0 = reqs.queries(0)
    client.server.serve_wide(q0[:p.batch])
    if not client.counters["repacks"]:
        # the window's repack stages exactly ``repack_count`` points
        gap = p.repack_count() - p.per_request - client.staged
        if gap > 0:
            client.fresh.insert(p.stream.take(client.staged, gap))
            client.staged += gap
        seen.append(requests(range_driver.WARM_REQUESTS, 1))
    # what a deployment pays at each new leaf count, and the window
    # does not: the steps retraced after the repack
    retrace = harness.CompileCounter()
    retrace.on = True
    t_r = time.perf_counter()
    rows, wide = requests(range_driver.WARM_REQUESTS + 1, 1)
    first_s = time.perf_counter() - t_r
    more = requests(range_driver.WARM_REQUESTS + 2,
                    range_driver.WARM_REQUESTS - 1)
    seen.append((rows + more[0], wide + more[1]))
    client.server.serve_wide(q0[:p.batch])
    retrace.on = False
    t1 = time.perf_counter()
    spans.phase(None)
    bands = [range_driver.sort_counts(r, w, q0.shape[0]) for r, w in seen]
    counts = sorted(set().union(*(range(lo, hi + 1) for lo, hi in bands)))
    sort_s = range_driver.warm_sort(q0, counts, p.sort, p.bbox)
    shares = ", ".join(f"{(w + 3) / r:.4f}" for r, w in seen)
    warmed = ", ".join(f"{lo}..{hi}" for lo, hi in bands)
    return (f"warm-up: {len(seen)} sets of requests in {t1 - t0:.1f}s, "
            f"{client.counters['repacks']} repack at {p.repack_count()} "
            f"staged in {client.counters['repack_s']:.2f}s, after it "
            f"{retrace.counts['lowerings']} lowerings, "
            f"{retrace.counts['compiles']} compiles (costliest: "
            f"{retrace.costliest()}), the first request {first_s:.2f}s, "
            f"re-serve shares "
            f"{shares}, sort warmed for {warmed} re-served rows "
            f"({len(counts)} counts) in {sort_s:.1f}s")


def check(client: Client, reqs, index, fit) -> tuple[dict, int]:
    """Every row of the window against the pool's answers over the
    deployment's points plus the brute-force answers over every insert
    staged before its request, the pre-staged ones included, count and
    id set, exactly. Returns ``(checks, wrong rows)``."""
    from bench import reference
    p = client.p
    print(f"# window: {len(client.repacked_after)} repacks, after requests "
          f"{[i + 1 for i in client.repacked_after]}", flush=True)
    if not client.answers:
        return {"wrong_rows": (0, 0)}, 0
    last = client.answers[-1][0]
    s_off, s_ids = reference.range_answers(p.stream.take(0, last), fit.pool)
    base = p.points.shape[0]
    bad = 0
    for i, (staged, n_res, got) in enumerate(client.answers):
        src = reqs.src(i)
        n_pool, pool = range_driver.dense_rows(fit.ref_offsets, fit.ref_ids,
                                               src)
        _, ins = range_driver.dense_rows(s_off, s_ids, src)
        seen = ins < staged
        ins[seen] += base
        ins[~seen] = range_driver.BIG
        ref = np.sort(np.concatenate([pool, ins], axis=1), axis=1)
        n_ref = n_pool + seen.sum(axis=1)
        bad += int(range_driver.wrong(n_res, got, n_ref, ref).sum())
    return {"wrong_rows": (bad, 0)}, bad
