"""Spatial serving driver: build an AI+R-tree and stream a full workload.

``python -m repro.launch.serve --points 120000 --queries 4096 [...]``

End-to-end: synthesize (or load) the dataset → dynamic R-tree build →
workload labelling → AI+R training (grid search + router) → **streaming**
hybrid serving of the *entire* query workload through the spatial batch
scheduler (``core.schedule``): queries are Hilbert/Morton-sorted into
fixed-size batches (``--sort none`` keeps arrival order), every query is
served exactly once, results are restored to submission order, and rows
that overflowed the narrow R-path bound are re-served on the wide tier.
Reports aggregate stats over the whole stream plus an oracle check that no
query was dropped. With >1 device, serving dispatches through the
shard_map engine (queries over 'data', tree/experts over 'model').

Open-loop mode (``--arrival poisson|bursty|trace``): instead of draining
the workload closed-loop, queries are stamped with arrival times
(``data.arrivals``) and served by the streaming runtime
(``core.runtime``) under per-query deadlines (``--rate``,
``--deadline-ms``, auto-pinned to the measured capacity when 0):
continuous Hilbert batch formation with deadline-aware partial dispatch
and wide-tier gating (``--formation full`` keeps the fixed-full-batch
baseline). Reports latency p50/p95/p99, goodput, and the degraded-row
accounting, plus the same no-drop oracle.

Mixed read/write mode (``--insert-rate r``): a fraction ``r`` of the
points is held out of the initial build and staged as dynamic inserts
between query segments (``core.schedule.serve_mixed_workload`` over a
``FreshServer``): every batch probes the device-side delta buffer, the
freshness guard demotes stale/under-fit cells to the exact R path, and
``--repack-every N`` triggers the online repack (bulk-reload swap between
batches) once N points are staged. The oracle then checks every query's
result count against brute-force containment over exactly the points
visible to its segment.
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build, device_tree as dt, engine, labels, runtime, \
    schedule
from repro.core import geometry as geo
from repro.core.hybrid import hybrid_query
from repro.core.monitor import DefaultPolicy, EngineFreshServer, FreshServer
from repro.core.rtree import RTree
from repro.launch import mesh as pmesh
from repro.launch.compile_cache import enable_compile_cache
from repro.data import arrivals as arrv, synth


def engine_mesh(devices):
    """The serving mesh over ``devices``: half the devices on ``data``
    (traffic), the rest on ``model`` (leaves and experts). Returns
    ``(mesh, n_model)``."""
    n = len(devices)
    nd = max(1, n // 2)
    return pmesh.make_mesh((nd, n // nd), ("data", "model"),
                           devices=devices), n // nd


def engine_config(hyb, args) -> engine.EngineConfig:
    """The engine's bounds for serving ``hyb``: the AI path keeps the
    tree's own ``max_pred``/``max_cells``/threshold (so the sharded
    engine answers as the single-device path does), the R path the
    ``--max-visited`` narrow bound per shard."""
    return engine.EngineConfig(
        max_visited=args.max_visited, max_pred=hyb.ait.max_pred,
        max_cells=hyb.ait.max_cells, threshold=hyb.ait.threshold,
        use_kernel=args.kernel)


class ServeFns(NamedTuple):
    """The two-tier range serving steps and what they serve.

    ``step``/``wide_step`` are jitted ``(hybrid, queries) → stats`` (the
    narrow and wide bounds); ``hybrid`` is the served tree (padded and
    placed on the mesh when distributed); ``trunc_field`` names the
    overflow flag the scheduler re-serves; ``ai_fused`` reports whether
    the AI path's prediction really dispatches the fused kernel under
    this configuration — asked of the dispatch gate at the shapes it will
    see (per-shard for the engine), because ``REPRO_KERNELS=off`` or the
    VMEM gate route to the dense oracle in interpret mode.

    Serving sets no ambient mesh: the engine's steps carry theirs in the
    ``shard_map`` and in the placed tree, while under ``jax.set_mesh``
    the scheduler's eager key kernel would be partitioned over the mesh,
    which a Mosaic kernel cannot be."""
    step: Callable
    wide_step: Callable
    hybrid: object
    trunc_field: str
    ai_fused: bool

    def narrow(self, q):
        return self.step(self.hybrid, q)

    def wide(self, q):
        return self.wide_step(self.hybrid, q)


def make_serve_fns(hyb, args, devices) -> ServeFns:
    """Range serving steps for the stream loop.

    Distributed (>1 device and ``--distributed``): the shard_map engine's
    two-tier steps (overflow flag ``ServeStats.r_truncated``), with the
    tree's leaves and experts placed over ``model``. Otherwise:
    ``hybrid_query`` with the same narrow/wide bound split (flag
    ``HybridResult.truncated``; the wide tier also widens ``max_results``
    so its result-id gather cannot re-truncate).
    """
    from repro.kernels import ops as kops
    want_fused = args.kernel and args.classifier == "mlp"
    if args.distributed and len(devices) > 1:
        mesh, n_model = engine_mesh(devices)
        nd = len(devices) // n_model
        hyb_s = engine.pad_tree_for_sharding(hyb, n_model)
        hyb_s = jax.device_put(hyb_s, engine.tree_shardings(hyb_s, mesh))
        cfg = engine_config(hyb, args)
        narrow, wide = engine.make_two_tier_steps(
            mesh, cfg, kind=args.classifier, wide_factor=args.wide_factor)
        fused = want_fused and cfg.score_union == "topk" and \
            kops.mlp_fused_active(
                args.batch // nd, hyb_s.ait.bank, cfg.max_cells,
                hyb_s.tree.n_leaves, cfg.max_pred,
                n_cells=hyb_s.ait.bank.w1.shape[0] // n_model)
        # jit once per tier — the stream re-enters the step per batch
        return ServeFns(jax.jit(narrow), jax.jit(wide), hyb_s,
                        "r_truncated", fused)

    mv, mr = args.max_visited, 512
    narrow = jax.jit(functools.partial(
        hybrid_query, max_visited=mv, max_results=mr,
        use_kernel=args.kernel))
    wide = jax.jit(functools.partial(
        hybrid_query, max_visited=mv * args.wide_factor,
        max_results=mr * args.wide_factor, use_kernel=args.kernel))
    fused = want_fused and kops.mlp_fused_active(
        args.batch, hyb.ait.bank, hyb.ait.max_cells,
        hyb.tree.n_leaves, hyb.ait.max_pred)
    return ServeFns(narrow, wide, hyb, "truncated", fused)


def make_fresh_server(base, hyb, args, devices, fit_state=None,
                      policy=None):
    """Build the mixed-stream server: ``FreshServer`` (single-device
    hybrid path) or ``EngineFreshServer`` (shard_map engine, replicated
    delta). ``fit_state``/``policy`` turn on the online
    instance-optimization loop (span-diff repacks + incremental
    ``refit_cells`` chunks between segments)."""
    if args.distributed and len(devices) > 1:
        mesh, n_model = engine_mesh(devices)
        return EngineFreshServer(base, hyb, mesh, engine_config(hyb, args),
                                 kind=args.classifier, n_model=n_model,
                                 delta_cap=args.delta_cap,
                                 wide_factor=args.wide_factor,
                                 fit_state=fit_state, policy=policy)
    return FreshServer(base, hyb, delta_cap=args.delta_cap,
                       max_visited=args.max_visited, max_results=512,
                       wide_factor=args.wide_factor, use_kernel=args.kernel,
                       fit_state=fit_state, policy=policy)


def serve_mixed(base, extra, hyb, wl, args, rep
                ) -> tuple[int, schedule.MixedReport]:
    """Drive the mixed read/write stream and report freshness stats.
    Returns the freshness oracle's mismatch count and the stream
    report."""
    fit_state = policy = None
    if args.policy != "none":
        # repack/demote/promote run regardless; without a per-cell
        # FitState (forest banks) the server skips the refit chunks,
        # prints its one-time notice, and records the skip count on
        # each decision (MaintenanceDecision.refit_skipped)
        policy = DefaultPolicy(refit_chunk=args.refit_chunk,
                               repack_at=args.repack_at)
        if rep.fit_state is not None and args.classifier != "forest":
            fit_state = rep.fit_state
    server = make_fresh_server(base, hyb, args, jax.devices(),
                               fit_state=fit_state, policy=policy)
    bbox = schedule.workload_bbox(wl.queries)
    t0 = time.time()
    mixed = schedule.serve_mixed_workload(
        server, wl.queries, extra, batch=args.batch, sort=args.sort,
        bbox=bbox, insert_every=args.insert_every,
        repack_every=args.repack_every)
    dt_s = time.time() - t0
    st = mixed.stats
    fs = server.stats()
    trunc_field = getattr(server, "trunc_field", "truncated")
    acc = float(np.asarray(st.leaf_accesses).mean())
    ai = float(np.asarray(st.used_ai).mean())
    guarded = float(np.asarray(st.guarded).mean())
    d_hits = int(np.asarray(st.delta_hits).sum())
    resid = int(np.asarray(getattr(st, trunc_field)).sum())
    print(f"# mixed stream: {mixed.n_queries} queries / {mixed.n_inserts} "
          f"inserts in {mixed.n_segments} segments ({mixed.n_batches} "
          f"batches, sort={mixed.sort}), {mixed.n_repacks} repacks, "
          f"{mixed.n_reserved} re-served wide, {resid} still truncated")
    print(f"# serve: {mixed.n_queries/dt_s:.0f} queries/s, "
          f"{acc:.2f} leaf accesses/query, {100*ai:.1f}% AI path, "
          f"{100*guarded:.1f}% guard-demoted, {d_hits} delta hits")
    print(f"# freshness: {fs.ok_cells}/{fs.n_cells} cells serve-eligible "
          f"({fs.fit_cells} exact-fit, {fs.stale_cells} stale, "
          f"{fs.demoted_cells} demoted), delta "
          f"fill {fs.delta_fill}/{args.delta_cap}, "
          f"{fs.n_repacks} repacks")
    if policy is not None:
        n_prep = sum(d.repack for _, d in mixed.maintenance)
        n_ref = sum(r.cells_refit for r in server.refits)
        n_dem = sum(d.demote.size for _, d in mixed.maintenance)
        n_pro = sum(d.promote.size for _, d in mixed.maintenance)
        n_skip = sum(d.refit_skipped for _, d in mixed.maintenance)
        print(f"# policy: {n_prep} repacks, {n_ref} cell refits "
              f"({n_skip} skipped), {n_dem} demotions, {n_pro} promotions "
              f"across {len(mixed.maintenance)} segment decisions")
        # recovery curve: guard/AI rates per segment show the AI path
        # coming back chunk by chunk after each span-diff repack
        g = np.asarray(st.guarded)
        u = np.asarray(st.used_ai)
        curve = "  ".join(
            f"{s}:{g[lo:hi].mean():.2f}/{u[lo:hi].mean():.2f}"
            for s, (lo, hi) in enumerate(mixed.seg_bounds))
        print(f"# recovery (seg:guarded/used_ai): {curve}")
    # freshness oracle: each segment's queries against exactly the points
    # visible to it (schedule.visible_segments — the scheduler's actual
    # staging, never re-derived from the policy)
    mism = 0
    got = np.asarray(st.n_results)
    for (lo, hi), visible in schedule.visible_segments(mixed, base):
        for o in range(lo, hi, 256):
            qs = wl.queries[o:min(o + 256, hi)]
            exp = geo.np_contains_point(
                qs[:, None, :], visible[None, :, :]).sum(axis=1)
            mism += int(np.sum(exp != got[o:min(o + 256, hi)]))
    print(f"# oracle: {mism} / {mixed.n_queries} n_results mismatches vs "
          f"per-segment brute-force containment")
    return mism, mixed


def serve_open_loop(narrow_fn, wide_fn, trunc_field, wl, args) -> None:
    """Open-loop serving: stamp arrivals, drive ``runtime.run_stream``,
    report the latency/goodput/degraded accounting plus the no-drop
    oracle (every non-degraded row exact against the workload labels)."""
    q = wl.queries
    # measured full-pipeline step costs pin the auto rate/deadline to
    # this machine's actual capacity (same convention as latency_bench)
    qb = jnp.asarray(q[: args.batch])
    ts = {}
    for name, fn in (("narrow", narrow_fn), ("wide", wide_fn)):
        jax.block_until_ready(fn(qb))
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(qb))
            reps.append(time.perf_counter() - t0)
        ts[name] = float(np.median(reps))
    cap_qps = args.batch / (ts["narrow"] + ts["wide"])
    rate = args.rate if args.rate > 0 else 1.5 * cap_qps
    deadline_s = (args.deadline_ms / 1e3 if args.deadline_ms > 0
                  else 6.0 * (ts["narrow"] + ts["wide"]))
    arr = arrv.make_arrivals(args.arrival, q.shape[0], rate,
                             trace=args.trace)
    print(f"# open loop: {args.arrival} arrivals at {rate:.0f} qps "
          f"({rate/cap_qps:.2f}x measured capacity {cap_qps:.0f} qps), "
          f"deadline {deadline_s*1e3:.1f} ms, formation={args.formation}")
    rep = runtime.run_stream(
        narrow_fn, q, arr, batch=args.batch, deadline_s=deadline_s,
        sort=args.sort, wide_fn=wide_fn, trunc_field=trunc_field,
        formation=args.formation)
    lat = rep.telemetry["latency_s"]
    depth = rep.telemetry["queue_depth"]
    print(f"# stream: {rep.n_queries} queries in {rep.n_batches} batches "
          f"(+{rep.n_wide_batches} wide), mean fill "
          f"{100*rep.mean_fill:.0f}%, queue depth p95 {depth['p95']:.0f}")
    print(f"# latency: p50 {lat['p50']*1e3:.1f} ms, "
          f"p95 {lat['p95']*1e3:.1f} ms, p99 {lat['p99']*1e3:.1f} ms")
    print(f"# goodput: {100*rep.goodput:.1f}% exact-and-on-time "
          f"({rep.n_missed} missed deadline, {rep.n_degraded} degraded "
          f"to best-effort narrow — flagged, never dropped)")
    # no-drop oracle: every query completed after it arrived, and every
    # non-degraded row's count matches the labelling pass exactly
    assert np.all(rep.done_s > rep.arrival_s)
    got = np.asarray(rep.stats.n_results)
    mism = int(np.sum(got[~rep.degraded] != wl.n_results[~rep.degraded]))
    print(f"# oracle: 0 dropped; {mism} / {int((~rep.degraded).sum())} "
          f"non-degraded n_results mismatches vs workload labels"
          + (f"; {rep.n_degraded} degraded rows carry their truncation "
             f"flag" if rep.n_degraded else ""))


def _timed_stream(narrow_fn, q, args, *, wide_fn=None, trunc_field=None,
                  bbox=None):
    """Warm both tiers, then time ``--reps`` full-stream repetitions."""
    report = schedule.serve_workload(
        narrow_fn, q, batch=args.batch, sort=args.sort, bbox=bbox,
        wide_fn=wide_fn, trunc_field=trunc_field)
    t0 = time.time()
    for _ in range(args.reps):
        report = schedule.serve_workload(
            narrow_fn, q, batch=args.batch, sort=args.sort, bbox=bbox,
            wide_fn=wide_fn, trunc_field=trunc_field)
    return report, (time.time() - t0) / args.reps


def serve_knn(dtree, pts, args) -> int:
    """kNN stream: distance browsing at a density-derived radius, with
    the radius-doubling wide tier re-serving flagged rows; a brute-force
    k-distance oracle checks a sample bit-exactly (prefix property on
    rows still truncated). Returns the oracle's mismatch count."""
    from repro.core import knn as knnlib
    rng = np.random.default_rng(0)
    centers = pts[rng.integers(0, pts.shape[0], args.queries)].astype(
        np.float32)
    q = np.concatenate([centers, centers], axis=1)
    r = knnlib.default_radius(dtree, args.knn_k, margin=args.knn_margin)
    narrow, wide = knnlib.make_knn_steps(
        dtree, k=args.knn_k, radius=r, max_visited=args.max_visited,
        wide_factor=args.wide_factor, use_kernel=args.kernel)
    report, dt_s = _timed_stream(narrow, q, args, wide_fn=wide,
                                 trunc_field="truncated",
                                 bbox=schedule.workload_bbox(q))
    st = report.stats
    resid = int(np.asarray(st.truncated).sum())
    acc = float(np.asarray(st.leaf_accesses).mean())
    print(f"# knn stream: k={args.knn_k}, radius {r:.4g} "
          f"(margin {args.knn_margin}), {report.n_queries} queries in "
          f"{report.n_batches} batches (sort={report.sort}), "
          f"{report.n_reserved} re-served at 2x radius, {resid} still "
          f"truncated (flagged, never approximate)")
    print(f"# serve: {report.n_queries/dt_s:.0f} queries/s, "
          f"{acc:.2f} leaf accesses/query, mean k-distance "
          f"{float(np.sqrt(np.asarray(st.neighbor_d2)[:, -1][~np.asarray(st.truncated)].mean())):.4g}")
    # oracle: sampled rows vs all-pairs brute kNN — d2 must match
    # bit-for-bit (both sides evaluate dx*dx+dy*dy under jit, so XLA's
    # FMA contraction is identical); truncated rows match on the
    # in-radius prefix
    m = min(256, q.shape[0])
    idx = rng.choice(q.shape[0], m, replace=False)
    bd2, _ = knnlib.knn_brute(pts, centers[idx], args.knn_k)
    got = np.asarray(st.neighbor_d2)[idx]
    trunc = np.asarray(st.truncated)[idx]
    nw = np.asarray(st.n_within)[idx]
    mism = 0
    for j in range(m):
        kk = args.knn_k if not trunc[j] else min(int(nw[j]), args.knn_k)
        mism += int(not np.array_equal(got[j, :kk], bd2[j, :kk]))
    print(f"# oracle: {mism} / {m} sampled rows mismatch brute-force "
          f"k-distances (bit-exact)")
    return mism


def serve_join(dtree, pts, args) -> None:
    """Spatial join stream: index-nested-loop over the fused traversal,
    pairs through the shared compaction epilogue; a brute-force pair-set
    oracle checks a sample exactly."""
    from repro.core import joins
    rng = np.random.default_rng(0)
    outer = synth.synth_queries(pts, args.selectivity, args.queries)
    rep = joins.spatial_join(dtree, outer, batch=args.batch,
                             max_pairs=args.join_pairs,
                             max_visited=args.max_visited, sort=args.sort,
                             wide_factor=args.wide_factor,
                             use_kernel=args.kernel)   # warm both tiers
    t0 = time.time()
    for _ in range(args.reps):
        rep = joins.spatial_join(dtree, outer, batch=args.batch,
                                 max_pairs=args.join_pairs,
                                 max_visited=args.max_visited,
                                 sort=args.sort,
                                 wide_factor=args.wide_factor,
                                 use_kernel=args.kernel)
    dt_s = (time.time() - t0) / args.reps
    print(f"# join stream: {rep.n_outer} outer rects x {pts.shape[0]} "
          f"points -> {rep.n_pairs} pairs "
          f"({rep.n_pairs/max(rep.n_outer,1):.1f}/outer) in "
          f"{rep.n_batches} batches (sort={rep.sort}), {rep.n_reserved} "
          f"re-served wide, {rep.residual_truncated} still truncated")
    print(f"# serve: {rep.n_outer/dt_s:.0f} outer rows/s, "
          f"{rep.n_pairs/dt_s:.0f} pairs/s")
    # oracle: sampled outer rows' pair sets vs dense brute containment;
    # rows the wide tier still truncated are excluded (flagged above)
    m = min(256, outer.shape[0])
    idx = rng.choice(outer.shape[0], m, replace=False)
    still = np.asarray(rep.stats.truncated).astype(bool)
    idx = idx[~still[idx]]
    bp = joins.join_brute(pts, outer[idx])
    remap = {int(o): i for i, o in enumerate(idx)}
    sel = np.isin(rep.pairs[:, 0], idx)
    got = {(remap[int(o)], int(pj)) for o, pj in rep.pairs[sel]}
    brute = {(int(o), int(pj)) for o, pj in bp}
    print(f"# oracle: {len(got ^ brute)} pair mismatches vs brute-force "
          f"containment over {idx.size} sampled outer rows")


def serve_point(hyb, base, args, devices) -> None:
    """Point-query stream: degenerate rects at dataset points served
    with single-cell AI routing and narrowed bounds — no wide tier, so
    exactness is *asserted* (zero truncated rows) instead of re-served."""
    from repro.core import hybrid as hybmod
    rng = np.random.default_rng(0)
    ppts = base[rng.integers(0, base.shape[0], args.queries)].astype(
        np.float32)
    q = np.concatenate([ppts, ppts], axis=1)
    if args.distributed and len(devices) > 1:
        mesh, n_model = engine_mesh(devices)
        hyb_s = engine.pad_tree_for_sharding(hyb, n_model)
        step = engine.make_point_serve_step(mesh, engine_config(hyb, args),
                                            kind=args.classifier)
        narrow = jax.jit(lambda qq: step(hyb_s, qq))
        trunc_field = "r_truncated"
    else:
        narrow = jax.jit(lambda qq: hybmod.point_query(
            hyb, qq, use_kernel=args.kernel))
        trunc_field = "truncated"
    report, dt_s = _timed_stream(narrow, q, args,
                                 bbox=schedule.workload_bbox(q))
    st = report.stats
    resid = int(np.asarray(getattr(st, trunc_field)).sum())
    acc = float(np.asarray(st.leaf_accesses).mean())
    ai = float(np.asarray(st.used_ai).mean())
    print(f"# point stream: {report.n_queries} degenerate-rect queries "
          f"in {report.n_batches} batches (sort={report.sort}), "
          f"single-cell AI routing, no wide tier")
    print(f"# serve: {report.n_queries/dt_s:.0f} queries/s, "
          f"{acc:.2f} leaf accesses/query, {100*ai:.1f}% AI path")
    # the narrowed bounds must cover every row — a truncated point query
    # would be silently wrong, so this is an assert, not a re-serve
    assert resid == 0, f"{resid} truncated point queries"
    got = np.asarray(st.n_results)
    # containment in f32 — the serving path (and the tree's leaf
    # entries) is f32 throughout, and a degenerate rect only contains
    # the points that are *bit-equal* at that precision
    bf = base.astype(np.float32)
    mism = 0
    for o in range(0, q.shape[0], 256):
        qs = q[o:o + 256]
        exp = geo.np_contains_point(qs[:, None, :],
                                    bf[None, :, :]).sum(axis=1)
        mism += int(np.sum(exp != got[o:o + 256]))
    print(f"# oracle: 0 truncated (exactness asserted); {mism} / "
          f"{report.n_queries} n_results mismatches vs brute-force "
          f"containment")


def serve_range(fns: ServeFns, wl, args
                ) -> tuple[int, schedule.ServeReport]:
    """Closed-loop range stream: warm both tiers, time ``--reps`` full
    streams through the spatial scheduler, report the stream stats and
    the no-drop oracle (every query's count against the workload
    labels). Returns the oracle's mismatch count and the stream report
    (per-query stats in submission order)."""
    bbox = schedule.workload_bbox(wl.queries)
    # warm / compile both tiers, then time full-stream repetitions
    report = schedule.serve_workload(
        fns.narrow, wl.queries, batch=args.batch, sort=args.sort,
        bbox=bbox, wide_fn=fns.wide, trunc_field=fns.trunc_field)
    t0 = time.time()
    for _ in range(args.reps):
        report = schedule.serve_workload(
            fns.narrow, wl.queries, batch=args.batch, sort=args.sort,
            bbox=bbox, wide_fn=fns.wide, trunc_field=fns.trunc_field)
    dt_s = (time.time() - t0) / args.reps

    st = report.stats
    acc = float(np.asarray(st.leaf_accesses).mean())
    ai = float(np.asarray(st.used_ai).mean())
    resid = int(np.asarray(getattr(st, fns.trunc_field)).sum())
    print(f"# stream: {report.n_queries} queries in {report.n_batches} "
          f"batches (sort={report.sort}), {report.n_reserved} re-served "
          f"wide ({report.wide_batches} batches), {resid} still truncated; "
          f"pad rows {report.pad_rows} narrow, {report.wide_pad_rows} "
          f"wide; {report.pulled_bytes} B pulled to the host; "
          f"{report.gather_chunks} result-id gather chunks")
    print(f"# serve: {report.n_queries/dt_s:.0f} queries/s, "
          f"{acc:.2f} leaf accesses/query, "
          f"{100*ai:.1f}% answered by the AI path")
    # no-drop oracle: the labelling pass already executed every query
    mism = int(np.sum(np.asarray(st.n_results) != wl.n_results))
    print(f"# oracle: {mism} / {report.n_queries} n_results mismatches "
          f"vs workload labels")
    return mism, report


def parse_args(argv=None) -> argparse.Namespace:
    """The command-line options (``argv=None`` reads ``sys.argv``). On a
    TPU the kernel paths always serve, whatever ``--kernel`` says."""
    p = argparse.ArgumentParser()
    p.add_argument("--dataset", default="tweets", choices=("tweets",
                                                           "crimes"))
    p.add_argument("--points", type=int, default=120_000)
    p.add_argument("--queries", type=int, default=4096)
    p.add_argument("--selectivity", type=float, default=5e-5)
    p.add_argument("--node-capacity", type=int, default=128)
    p.add_argument("--classifier", default="knn",
                   choices=("knn", "forest", "mlp"))
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--reps", type=int, default=3,
                   help="timed repetitions of the full stream")
    p.add_argument("--sort", default="hilbert", choices=schedule.SORT_MODES,
                   help="spatial batch scheduling curve (none = arrival "
                        "order)")
    p.add_argument("--max-visited", type=int, default=64,
                   help="narrow-tier R-path bound (overflow re-serves wide)")
    p.add_argument("--wide-factor", type=int, default=8)
    p.add_argument("--kernel", action="store_true",
                   help="serve through the Pallas kernel paths (fused "
                        "traversal/compaction; with --classifier mlp also "
                        "the fused prediction kernel) in interpret mode; "
                        "on a TPU the kernels always serve")
    p.add_argument("--distributed", action="store_true",
                   help="serve through the shard_map engine")
    p.add_argument("--insert-rate", type=float, default=0.0,
                   help="fraction of points held out of the build and "
                        "staged as dynamic inserts during the stream")
    p.add_argument("--insert-every", type=int, default=4,
                   help="query batches per stream segment (inserts land "
                        "between segments)")
    p.add_argument("--repack-every", type=int, default=0,
                   help="online repack once this many inserts are staged "
                        "(0 = never; buffer must then hold them all)")
    p.add_argument("--delta-cap", type=int, default=8192,
                   help="delta store capacity (points)")
    p.add_argument("--arrival", default="closed",
                   choices=("closed", "poisson", "bursty", "trace"),
                   help="closed = drain the workload as fast as it serves "
                        "(the throughput harness); anything else stamps "
                        "arrival times and drives the open-loop runtime "
                        "(core.runtime) under per-query deadlines")
    p.add_argument("--rate", type=float, default=0.0,
                   help="open-loop arrival rate, queries/s (0 = auto: "
                        "1.5x the measured serve capacity)")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-query deadline from arrival (0 = auto: 6x "
                        "the measured narrow+wide batch cost)")
    p.add_argument("--trace", default=None,
                   help="timestamp file for --arrival trace (.npy or one "
                        "float per line)")
    p.add_argument("--formation", default="deadline",
                   choices=("deadline", "full"),
                   help="open-loop batch formation: deadline-aware "
                        "partial dispatch, or fixed-full-batch baseline")
    p.add_argument("--policy", default="none", choices=("none", "default"),
                   help="between-segment maintenance policy: span-diff "
                        "repacks + stats-driven incremental refit chunks "
                        "(needs a per-cell classifier: knn or mlp)")
    p.add_argument("--refit-chunk", type=int, default=4,
                   help="max stale cells retrained per segment decision")
    p.add_argument("--repack-at", type=float, default=0.75,
                   help="policy repacks once the delta buffer passes this "
                        "fill fraction")
    p.add_argument("--query-type", default="range",
                   choices=("range", "point", "knn", "join"),
                   help="serving path: range rects (default), point "
                        "lookups (degenerate rects, single-cell AI "
                        "routing, exactness asserted), kNN (distance "
                        "browsing with a radius-doubling wide tier), or "
                        "spatial join (index-nested-loop, pair-slot "
                        "tables)")
    p.add_argument("--knn-k", type=int, default=8,
                   help="neighbors per query for --query-type knn")
    p.add_argument("--knn-margin", type=float, default=2.0,
                   help="probe radius margin over the density estimate "
                        "(larger = fewer wide-tier re-serves)")
    p.add_argument("--join-pairs", type=int, default=16,
                   help="narrow-tier pair-slot width for --query-type "
                        "join")
    args = p.parse_args(argv)
    if args.query_type != "range" and (args.insert_rate > 0
                                       or args.arrival != "closed"):
        p.error("--query-type point/knn/join drive the closed-loop "
                "read-only stream (no --insert-rate / --arrival)")
    args.kernel = args.kernel or jax.default_backend() == "tpu"
    return args


def main() -> None:
    args = parse_args()
    print(f"# compile cache: {enable_compile_cache()}")

    gen = synth.tweets_like if args.dataset == "tweets" else synth.crimes_like
    pts = gen(args.points)
    n_ins = int(round(args.insert_rate * pts.shape[0]))
    base, extra = (pts[:-n_ins], pts[-n_ins:]) if n_ins else (pts, None)
    print(f"# dataset {args.dataset}: {pts.shape[0]} points"
          + (f" ({n_ins} held out as inserts)" if n_ins else ""))

    t0 = time.time()
    tree = RTree(max_entries=args.node_capacity).insert_all(base)
    dtree = dt.flatten(tree)
    print(f"# R-tree: {dtree.n_leaves} leaves, height {dtree.height}, "
          f"built in {time.time()-t0:.1f}s")

    if args.query_type == "knn":
        serve_knn(dtree, pts, args)
        return
    if args.query_type == "join":
        serve_join(dtree, pts, args)
        return

    qs = synth.synth_queries(pts, args.selectivity, args.queries)
    wl = labels.make_workload(dtree, qs)
    print(f"# workload: mean α {wl.alpha.mean():.3f}, "
          f"mean visited {wl.n_visited.mean():.1f}")

    hyb, rep = build.fit_airtree(dtree, wl, kind=args.classifier,
                                 verbose=True)
    print(f"# AI+R: grid {rep.grid_size}², exact-fit {rep.exact_fit:.3f} "
          f"({int(rep.cell_fit.sum())}/{rep.cell_fit.size} cells exact), "
          f"router test acc {rep.router.test_acc:.3f}, "
          f"models {rep.model_bytes/1e6:.2f} MB")

    if args.query_type == "point":
        serve_point(hyb, base, args, jax.devices())
        return

    if n_ins:
        serve_mixed(base, extra, hyb, wl, args, rep)
        return

    fns = make_serve_fns(hyb, args, jax.devices())
    if args.arrival != "closed":
        serve_open_loop(fns.narrow, fns.wide, fns.trunc_field, wl, args)
        return

    serve_range(fns, wl, args)


if __name__ == "__main__":
    main()
