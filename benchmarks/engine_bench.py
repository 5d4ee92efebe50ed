"""Beyond-paper engine benchmarks: batched serving throughput + kernel µbench.

The paper measures per-query latency under a disk cost model; the TPU engine's
native metric is batched throughput (queries/s) and bytes-touched. This
harness reports both, plus microbenchmarks of the Pallas kernel entry points
(interpret mode on CPU — wall numbers are for relative tracking only; the
roofline analysis in EXPERIMENTS.md covers the TPU target).
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import build, device_tree as dt, labels
from repro.core.hybrid import hybrid_query
from repro.core.rtree import RTree
from repro.data import synth


def _time(fn, reps=5):
    fn()  # warm
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.time() - t0) / reps


def serving_throughput(rows: list, n_points: int = 120_000,
                       batch: int = 512) -> None:
    pts = synth.tweets_like(n_points, seed=0)
    tree = RTree(max_entries=128).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 5e-5, 4000, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, rep = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(8, 12))
    q = jnp.asarray(wl.queries[:batch])
    for force in ("r", "ai", "auto"):
        dtm = _time(lambda: hybrid_query(hyb, q, force_path=force))
        out = hybrid_query(hyb, q, force_path=force)
        acc = float(np.asarray(out.leaf_accesses).mean())
        # bytes touched ≈ leaf accesses × leaf tile bytes
        tile = dtree.leaf_entries.shape[2] * 2 * 4
        rows.append((f"serve_{force}_qps", batch / dtm,
                     f"leaf_acc={acc:.2f},tile_bytes={tile}"))


def query_type_throughput(rows: list, n_points: int = 120_000,
                          batch: int = 512) -> None:
    """Serving throughput of the non-range query types — kNN, point,
    spatial join — on the same slot-table contract as the range path.
    Emits ``_qps`` rows so ``run.py --check`` guards them with the same
    inverted tolerance as ``serve_*_qps``."""
    from repro.core import hybrid as hybmod, joins
    from repro.core import knn as knnlib

    pts = synth.tweets_like(n_points, seed=0)
    dtree = dt.flatten(RTree.str_bulk(pts, max_entries=32))
    rng = np.random.default_rng(7)
    centers = pts[rng.integers(0, n_points, batch)].astype(np.float32)
    pq = jnp.asarray(np.concatenate([centers, centers], axis=1))

    k = 8
    r = knnlib.default_radius(dtree, k)
    knn_fn = jax.jit(lambda q: knnlib.knn_query(dtree, q, k=k, radius=r,
                                                max_visited=64))
    dtm = _time(lambda: knn_fn(pq))
    out = knn_fn(pq)
    acc = float(np.asarray(out.leaf_accesses).mean())
    rows.append(("knn_serve_qps", batch / dtm,
                 f"k={k},r={r:.3g},leaf_acc={acc:.2f},"
                 f"trunc={int(np.asarray(out.truncated).sum())}"))

    outer = jnp.asarray(synth.synth_queries(pts, 1e-4, batch, seed=8))
    join_fn = jax.jit(lambda q: joins.join_step(dtree, q, max_pairs=32,
                                                max_visited=64))
    dtm = _time(lambda: join_fn(outer))
    out = join_fn(outer)
    rows.append(("join_outer_qps", batch / dtm,
                 f"max_pairs=32,pairs={int(np.asarray(out.n_pairs).sum())}"))

    qs = synth.synth_queries(pts, 5e-5, 1500, seed=9)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(8,))
    pt_fn = jax.jit(lambda q: hybmod.point_query(hyb, q))
    dtm = _time(lambda: pt_fn(pq))
    out = pt_fn(pq)
    assert not np.asarray(out.truncated).any(), \
        "point path truncated — narrowed bounds failed to cover"
    acc = float(np.asarray(out.leaf_accesses).mean())
    rows.append(("point_serve_qps", batch / dtm, f"leaf_acc={acc:.2f}"))


def _synth_levels(L: int, fanout: int, rng):
    """STR-packed synthetic hierarchy (spatially tight leaf-ID tiles)."""
    from repro.data.synth_tree import synth_levels
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    return ([jnp.asarray(m) for m in mbrs],
            [jnp.asarray(p) for p in parents])


def _med_time(fn, reps: int = 15) -> float:
    """Median wall time (s) — robust to the noisy shared-CPU container."""
    jax.block_until_ready(fn())  # warm / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _med_time_pair(fa, fb, reps: int = 25) -> tuple[float, float]:
    """Interleaved medians of two competitors — back-to-back sampling
    cancels the container's load drift, which otherwise dwarfs a closely
    matched comparison measured in separate blocks."""
    jax.block_until_ready(fa())
    jax.block_until_ready(fb())
    ta, tb_ = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fa())
        t1 = time.perf_counter()
        jax.block_until_ready(fb())
        ta.append(t1 - t0)
        tb_.append(time.perf_counter() - t1)
    return float(np.median(ta)), float(np.median(tb_))


def traversal_micro(rows: list, B: int = 256, L: int = 2048,
                    fanout: int = 4) -> None:
    """Fused single-pass traversal vs per-level kernel path vs jnp oracle.

    Interpret mode on CPU — wall numbers track relative cost only, but the
    fused/per-level ratio is the perf gate for this subsystem: the fused
    kernel replaces H pallas_calls + H−1 HBM mask round-trips with one
    call, and its tile-level early exit skips dead subtrees outright.
    Three workloads: uniform small queries, a spatially clustered serving
    batch (most leaf tiles dead), and an all-dead batch (frontier dies at
    the root).
    """
    import functools

    from repro.core.device_tree import DeviceTree, Level
    from repro.core import traversal
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    mbrs, parents = _synth_levels(L, fanout, rng)
    tree = DeviceTree(
        levels=tuple(Level(mbrs=m, parent=p)
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.zeros((L, 2, 8), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, 8), jnp.int32),
        leaf_counts=jnp.zeros((L,), jnp.int32),
        n_points=0, max_entries=fanout)

    lo = rng.uniform(-1, 1, (B, 2))
    w = rng.uniform(0, 0.05, (B, 2))
    q_uniform = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    c = rng.uniform(-0.8, 0.6, (1, 2))
    lo = c + rng.uniform(0, 0.15, (B, 2))
    w = rng.uniform(0, 0.02, (B, 2))
    q_cluster = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    q_dead = jnp.asarray(
        np.tile(np.array([[50.0, 50.0, 51.0, 51.0]], np.float32), (B, 1)))

    fused = jax.jit(functools.partial(ops.traverse_fused))
    per_level = jax.jit(functools.partial(
        traversal.visited_leaf_mask_per_level, use_kernel=True))
    oracle = jax.jit(functools.partial(
        traversal.visited_leaf_mask_per_level, use_kernel=False))

    lm = [lv.mbrs for lv in tree.levels]
    lp = [lv.parent for lv in tree.levels]
    shape = f"B{B}xL{L}"
    for wl, q in [("uniform", q_uniform), ("clustered", q_cluster),
                  ("alldead", q_dead)]:
        # sanity: identical masks, or the timing comparison is meaningless
        np.testing.assert_array_equal(np.asarray(fused(q, lm, lp)),
                                      np.asarray(oracle(tree, q)))
        t_fused = _med_time(lambda: fused(q, lm, lp))
        t_level = _med_time(lambda: per_level(tree, q))
        rows.append((f"traversal_fused_{wl}_{shape}_us", t_fused * 1e6,
                     f"speedup_vs_per_level={t_level / t_fused:.2f}x"))
        rows.append((f"traversal_per_level_{wl}_{shape}_us", t_level * 1e6,
                     f"levels={len(lm)}"))
    t_oracle = _med_time(lambda: oracle(tree, q_uniform))
    rows.append((f"traversal_oracle_jnp_{shape}_us", t_oracle * 1e6, ""))


def compaction_micro(rows: list, B: int = 256, L: int = 2048,
                     fanout: int = 4, k: int = 64) -> None:
    """Fused-compact epilogue vs mask+compact hand-off (traversal+refine).

    Both sides end with identical scalar-prefetch ``leaf_refine`` inputs;
    the difference under test is the traversal→compaction hand-off: the
    mask+compact path writes the ``[B, L]`` visited mask to HBM and
    re-scans it with the jnp ``compact_mask``, while the fused-compact path
    emits the ``[B, k]`` slot table and per-row counts straight from the
    kernel's VMEM-resident frontier. Interpret mode on CPU — relative cost
    only, same workloads as ``traversal_micro``.
    """
    from repro.core.device_tree import DeviceTree, Level
    from repro.core import traversal
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    mbrs, parents = _synth_levels(L, fanout, rng)
    tree = DeviceTree(
        levels=tuple(Level(mbrs=m, parent=p)
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, 8)), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, 8), jnp.int32),
        leaf_counts=jnp.full((L,), 8, jnp.int32),
        n_points=0, max_entries=fanout)
    lm = [lv.mbrs for lv in tree.levels]
    lp = [lv.parent for lv in tree.levels]

    lo = rng.uniform(-1, 1, (B, 2))
    w = rng.uniform(0, 0.05, (B, 2))
    q_uniform = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    c = rng.uniform(-0.8, 0.6, (1, 2))
    lo = c + rng.uniform(0, 0.15, (B, 2))
    w = rng.uniform(0, 0.02, (B, 2))
    q_cluster = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    q_dead = jnp.asarray(
        np.tile(np.array([[50.0, 50.0, 51.0, 51.0]], np.float32), (B, 1)))

    @jax.jit
    def fused_compact(q):
        idx, valid, cnt = ops.traverse_compact(q, lm, lp, k)
        ref = traversal.refine_leaves(tree, q, idx, valid, use_kernel=True)
        return ref.counts, cnt

    @jax.jit
    def mask_compact(q):
        mask = ops.traverse_fused(q, lm, lp)
        idx, valid, cnt = traversal.compact_mask_counted(mask, k)
        ref = traversal.refine_leaves(tree, q, idx, valid, use_kernel=True)
        return ref.counts, cnt

    shape = f"B{B}xL{L}k{k}"
    for wl, q in [("uniform", q_uniform), ("clustered", q_cluster),
                  ("alldead", q_dead)]:
        # sanity: identical outputs, or the timing comparison is meaningless
        fc, fcnt = fused_compact(q)
        mc, mcnt = mask_compact(q)
        np.testing.assert_array_equal(np.asarray(fc), np.asarray(mc))
        np.testing.assert_array_equal(np.asarray(fcnt), np.asarray(mcnt))
        t_fused = _med_time(lambda: fused_compact(q))
        t_mask = _med_time(lambda: mask_compact(q))
        rows.append((f"compact_fused_{wl}_{shape}_us", t_fused * 1e6,
                     f"speedup_vs_mask_compact={t_mask / t_fused:.2f}x"))
        rows.append((f"compact_mask_{wl}_{shape}_us", t_mask * 1e6, ""))


def ai_fusion_micro(rows: list, B: int = 256, L: int = 2048, g: int = 4,
                    Cl: int = 32, k: int = 64) -> None:
    """Fused AI-path prediction vs the dense pipeline it replaces.

    ``ai_dense_*`` is the pre-fusion serving form: gathered per-cell MLP
    forward → sigmoid → ``global_scores`` max-union scatter into the
    ``[B, L]`` score table → threshold → ``compact_mask_counted``.
    ``ai_fused_*`` is ``ops.mlp_predict_compact`` — the same semantics in
    one ``pallas_call`` whose only HBM output is the ``[B, k]`` slot
    table + counts (the [B, L] table never materializes; bit-identity is
    asserted before timing). Also rows the query-level pipelines
    (``ai_query`` vs ``ai_query_compact``, refine + gather included).
    Interpret mode on CPU — relative cost only; the derived column
    carries the dense-table bytes the fused form stops moving.
    """
    from repro.core import traversal
    from repro.core.aitree import (ai_query, ai_query_compact, make_aitree,
                                   predict_compact, predict_scores)
    from repro.core.device_tree import DeviceTree, Level
    from benchmarks._synth_ai import synth_mlp_bank, unit_grid

    rng = np.random.default_rng(0)
    bank = synth_mlp_bank(rng, g * g, L, Cl=Cl)
    C = g * g
    grid = unit_grid(g)
    ait = make_aitree(grid, bank, max_cells=4, max_pred=k)
    lo = rng.uniform(-1, 0.9, (B, 2))
    q = jnp.asarray(np.concatenate([lo, lo + 0.05], 1), jnp.float32)

    # both competitors are the full predict pipeline INCLUDING cell
    # routing (timing only one side's cells_of_queries would bias the
    # comparison) — exactly the two rungs predict_compact dispatches
    @jax.jit
    def dense(qq):
        scores, _ = predict_scores(ait, qq, L)
        return traversal.compact_mask_counted(scores > ait.threshold, k)

    @jax.jit
    def fused(qq):
        return predict_compact(ait, qq, L, use_kernel=True)[:3]

    # sanity: identical slots, or the timing comparison is meaningless
    for a, b in zip(fused(q), dense(q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    shape = f"B{B}xL{L}k{k}"
    t_fused, t_dense = _med_time_pair(lambda: fused(q), lambda: dense(q),
                                      reps=40)
    dense_mb = B * L * 4 / 1e6
    rows.append((f"ai_fused_predict_{shape}_us", t_fused * 1e6,
                 f"speedup_vs_dense={t_dense / t_fused:.2f}x,"
                 f"dense_table_mb={dense_mb:.2f}"))
    rows.append((f"ai_dense_predict_{shape}_us", t_dense * 1e6,
                 f"cells={C},Cl={Cl}"))

    # query level: predict + refine + result gather, dense vs compact
    M = 8
    tree = DeviceTree(
        levels=(Level(mbrs=jnp.asarray(
            np.concatenate([lo2 := rng.uniform(-1, 1, (L, 2)),
                            lo2 + 0.2], 1), jnp.float32),
            parent=jnp.zeros((L,), jnp.int32)),),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, M)), jnp.float32),
        leaf_entry_ids=jnp.asarray(np.arange(L * M).reshape(L, M),
                                   jnp.int32),
        leaf_counts=jnp.full((L,), M, jnp.int32), n_points=L * M,
        max_entries=M)
    qd = jax.jit(lambda qq: ai_query(ait, tree, qq, max_results=128))
    qf = jax.jit(lambda qq: ai_query_compact(ait, tree, qq, max_results=128,
                                             use_kernel=True))
    rd, rf = qd(q), qf(q)
    np.testing.assert_array_equal(np.asarray(rd.n_results),
                                  np.asarray(rf.n_results))
    np.testing.assert_array_equal(np.asarray(rd.fallback),
                                  np.asarray(rf.fallback))
    t_f, t_d = _med_time_pair(lambda: qf(q), lambda: qd(q), reps=40)
    rows.append((f"ai_fused_query_{shape}_us", t_f * 1e6,
                 f"speedup_vs_dense={t_d / t_f:.2f}x"))
    rows.append((f"ai_dense_query_{shape}_us", t_d * 1e6, ""))


def _sched_traffic(Q: int, kind: str, rng) -> np.ndarray:
    """Serving traffic in *arrival* order: spatially mixed streams.

    ``clustered``: queries draw from a handful of hotspots but arrive
    interleaved (the realistic worst case the scheduler exists for —
    every unsorted batch touches every hotspot). ``uniform``: small rects
    everywhere.
    """
    if kind == "uniform":
        lo = rng.uniform(-1, 1, (Q, 2))
        w = rng.uniform(0, 0.05, (Q, 2))
    else:
        centers = rng.uniform(-0.9, 0.7, (16, 2))
        which = rng.integers(0, centers.shape[0], Q)
        lo = centers[which] + rng.normal(0, 0.01, (Q, 2))
        w = rng.uniform(0, 0.005, (Q, 2))
    q = np.concatenate([lo, lo + w], 1).astype(np.float32)
    rng.shuffle(q)                      # arrival order ≠ spatial order
    return q


def scheduler_bench(rows: list, Q: int = 2048, batch: int = 256,
                    L: int = 4096, fanout: int = 4, k: int = 64,
                    check: bool = True) -> None:
    """Spatial batch scheduler: full-stream serving, sorted vs unsorted.

    The serve step per batch is the kernel-path compact pipeline
    (``range_query_compact``), pinned to the **leaf-tile grid** form
    (``tile_l = DEF_TL``) — the TPU-shaped graph whose ``pl.when`` tile
    early exit is what batch locality feeds. (The interpret-mode default
    folds the leaf axis into one tile, where only the per-subtile exit
    remains and its savings drown in the replicated internal walk — see
    EXPERIMENTS.md "Scheduler locality".) A Hilbert/Morton-ordered stream
    hands the kernel batches whose queries share a compact region, so
    most leaf tiles of most batches are dead before the intersection
    runs. ``live_sub`` in the derived column is the measured fraction of
    (batch × tile) pairs the early exit cannot skip — the locality the
    sort manufactures. Also rows the scheduler's own admission cost (the
    spatial_key kernel).
    """
    import functools

    from repro.core.device_tree import DeviceTree, Level
    from repro.core import schedule, traversal
    from repro.kernels import ops
    from repro.kernels import traverse_fused as tf

    rng = np.random.default_rng(0)
    mbrs, parents = _synth_levels(L, fanout, rng)
    tree = DeviceTree(
        levels=tuple(Level(mbrs=m, parent=p)
                     for m, p in zip(mbrs, parents)),
        leaf_entries=jnp.asarray(rng.uniform(-1, 1, (L, 2, 8)), jnp.float32),
        leaf_entry_ids=jnp.zeros((L, 8), jnp.int32),
        leaf_counts=jnp.full((L,), 8, jnp.int32),
        n_points=0, max_entries=fanout)

    tile_l = min(tf.DEF_TL, L)
    serve_fn = functools.partial(traversal.range_query_compact, tree,
                                 max_visited=k, max_results=64,
                                 use_kernel=True, tile_l=tile_l)
    leaf_mbrs = np.asarray(mbrs[-1])
    sub = tile_l    # early-exit granularity of the gridded form
    shape = f"Q{Q}B{batch}xL{L}"
    for kind in ("clustered", "uniform"):
        q = _sched_traffic(Q, kind, np.random.default_rng(1))
        bbox = schedule.workload_bbox(q)
        base = None
        results = {}
        for sort in ("none", "morton", "hilbert"):
            run = lambda s=sort: schedule.serve_workload(
                serve_fn, q, batch=batch, sort=s, bbox=bbox)
            results[sort] = run()
            t = _med_time(lambda: run(), reps=5)
            # live subtiles per batch: what the early exit cannot skip
            live = tot = 0
            sched = schedule.make_schedule(q, batch, sort, bbox)
            for chunk, _ in schedule.iter_batches(q, sched):
                hit = np.asarray(ops.mbr_intersect(
                    jnp.asarray(chunk), jnp.asarray(leaf_mbrs)))
                nsub = -(-hit.shape[1] // sub)
                for s in range(nsub):
                    tot += 1
                    live += bool(hit[:, s * sub:(s + 1) * sub].any())
            extra = f"live_sub={live / tot:.2f}"
            if sort == "none":
                base = t
            else:
                extra += f",speedup_vs_none={base / t:.2f}x"
            rows.append((f"sched_{sort}_{kind}_{shape}_us", t * 1e6, extra))
        if check:
            # the scheduler must be invisible in the results (and serve
            # every query): sorted == unsorted, field for field
            for sort in ("morton", "hilbert"):
                for f in type(results["none"].stats)._fields:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(results["none"].stats, f)),
                        np.asarray(getattr(results[sort].stats, f)),
                        err_msg=f"{kind}:{sort}:{f}")

    q = jnp.asarray(_sched_traffic(Q, "uniform", np.random.default_rng(2)))
    bbox = jnp.asarray(schedule.workload_bbox(np.asarray(q)))
    for curve in ("hilbert", "morton"):
        t = _med_time(lambda: ops.spatial_key(q, bbox=bbox, curve=curve))
        rows.append((f"spatial_key_{curve}_Q{Q}_us", t * 1e6,
                     f"{Q / t / 1e6:.2f}Mkeys/s"))


def _fresh_world(n_points: int, n_ins: int, n_queries: int, seed: int = 0):
    """Toy mixed read/write world: STR base tree + held-out inserts."""
    from repro.core import build as buildlib
    pts = synth.tweets_like(n_points + n_ins, seed=seed)
    base, extra = pts[:n_points], pts[n_points:]
    dtree = dt.flatten(RTree.str_bulk(base, max_entries=32))
    qs = synth.synth_queries(pts, 2e-4, n_queries, seed=seed + 1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = buildlib.fit_airtree(dtree, wl, kind="knn", grid_sizes=(6,))
    return base, extra, dtree, wl, hyb


def freshness_bench(rows: list, n_points: int = 30_000, n_ins: int = 2048,
                    batch: int = 256) -> None:
    """Freshness subsystem costs: delta-probe vs buffer fill, staging,
    online repack, and the serving overhead of the delta stage
    (``update_*`` rows; see EXPERIMENTS.md "Freshness")."""
    from repro.core import delta as deltalib
    from repro.core.monitor import FreshServer
    from repro.kernels import ops

    base, extra, dtree, wl, hyb = _fresh_world(n_points, n_ins, 2000)
    q = jnp.asarray(wl.queries[:batch])

    # probe cost vs buffer fill (the [B, cap] mask never leaves VMEM; the
    # cost is capacity-shaped, not fill-shaped — rows document that)
    cap = n_ins
    for fill in (0, cap // 4, cap):
        store = deltalib.make_delta(cap, base=n_points)
        if fill:
            store = deltalib.stage_inserts(store, extra[:fill])
        t = _med_time(lambda s=store: ops.delta_probe(q, s.xy, k=64))
        rows.append((f"update_probe_B{batch}xN{cap}_fill{fill}_us", t * 1e6,
                     f"{batch / t / 1e3:.0f}kprobes/s"))

    # staging throughput (host append + device swap, between batches)
    def stage():
        deltalib.stage_inserts(deltalib.make_delta(cap, base=n_points),
                               extra)
        return jnp.zeros(())
    t = _med_time(stage, reps=7)
    rows.append((f"update_stage_{n_ins}_us", t * 1e6,
                 f"{n_ins / t / 1e3:.0f}kpts/s"))

    # online repack: bulk reload + flatten of base+staged
    store = deltalib.stage_inserts(
        deltalib.make_delta(cap, base=n_points), extra)

    def do_repack():
        deltalib.repack(base, store, max_entries=32)
        return jnp.zeros(())
    t = _med_time(do_repack, reps=3)
    rows.append((f"update_repack_{n_points + n_ins}_us", t * 1e6,
                 f"{(n_points + n_ins) / t / 1e6:.2f}Mpts/s"))

    # serving overhead of the freshness stage: FreshServer (probe + merge
    # + guard) vs the plain read-only hybrid, interleaved timing
    srv = FreshServer(base, hyb, delta_cap=cap, max_visited=128,
                      max_results=512)
    srv.insert(extra[:cap // 2])
    ro = jax.jit(lambda qq: hybrid_query(hyb, qq, max_visited=128))
    tf_, tr = _med_time_pair(lambda: srv.serve(q), lambda: ro(q))
    rows.append((f"update_serve_B{batch}_us", tf_ * 1e6,
                 f"readonly_us={tr * 1e6:.0f},overhead="
                 f"{(tf_ / tr - 1) * 100:.0f}%,qps={batch / tf_:.0f}"))


def freshness_smoke(rows: list) -> None:
    """Toy mixed read/write gate (``make bench-smoke`` / CI): stream
    queries with inserts interleaved and a mid-stream repack, then
    *assert* delta-serving ≡ the from-scratch rebuild oracle — result
    counts per segment against exactly the points visible to it, and the
    post-repack serve bit-identical to a fresh bulk load."""
    import dataclasses

    from repro.core import delta as deltalib, schedule
    from repro.core.monitor import FreshServer

    base, extra, dtree, wl, hyb = _fresh_world(6000, 600, 300)
    srv = FreshServer(base, hyb, delta_cap=1024, max_visited=128,
                      max_results=512)
    t0 = time.time()
    mixed = schedule.serve_mixed_workload(
        srv, wl.queries, extra, batch=64, sort="hilbert", insert_every=1,
        repack_every=400)
    dt_s = time.time() - t0
    assert mixed.n_repacks >= 1, "gate must exercise the online repack"
    # per-segment rebuild oracle: n_results over the visible point set
    # (schedule.visible_segments — the scheduler's actual staging)
    from repro.core import geometry as geo
    got = np.asarray(mixed.stats.n_results)
    for (lo, hi), visible in schedule.visible_segments(mixed, base):
        exp = geo.np_contains_point(
            wl.queries[lo:hi][:, None, :], visible[None, :, :]).sum(axis=1)
        np.testing.assert_array_equal(got[lo:hi], exp,
                                      err_msg=f"segment {lo}:{hi}")
    # repack ≡ rebuild: the swapped tree is bit-identical to a fresh
    # bulk load of the same points, so serving it must be too
    srv.repack()
    rebuilt = dt.flatten(RTree.str_bulk(srv.points, max_entries=32))
    hyb2 = dataclasses.replace(srv.hybrid, tree=rebuilt)
    q = jnp.asarray(wl.queries[:64])
    a = srv.serve(q)
    b = hybrid_query(hyb2, q, max_visited=128, max_results=512)
    for f in type(b)._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"repack vs rebuild: {f}")
    rows.append(("update_smoke_stream_us", dt_s * 1e6,
                 f"{mixed.n_queries}q/{mixed.n_inserts}ins/"
                 f"{mixed.n_repacks}repack,oracle=exact"))


def refit_bench(rows: list, quick: bool = False) -> None:
    """Incremental ``build.refit_cells`` vs a from-scratch fit.

    The instance-optimization loop's cost claim: when a localized change
    dirties ≤ 25% of the grid cells, retraining just those cells (chunk
    relabel + per-cell train + splice + partial recertify) must beat the
    full pipeline (full relabel + all-cell train + full certify) by a
    wide margin — the per-cell training pipeline's bit-determinism makes
    the two *results* identical, so the rows measure pure cost. Both
    sides include their labelling work (refit relabels internally; the
    full side pays ``make_workload``).

    Gate: ≥5x for the knn bank (fit cost scales with the touched query/
    cell set, so the ratio tracks the dirty fraction directly). The mlp
    row is asserted at a lower floor on this CPU harness: the Adam epoch
    loop has a fixed per-step dispatch cost that dominates tiny cell
    batches, flattening the trained-cells ratio (20 vs 100 cells ≈ 3.5x
    wall here); on an accelerator the per-epoch cost is matmul-bound and
    the ratio recovers toward cells_full/cells_chunk."""
    import dataclasses as dc

    from repro.core import build as buildlib

    floor = {"knn": 5.0, "mlp": 2.5}
    for kind in ("knn", "mlp"):
        pts = synth.tweets_like(4000 if quick else 6000, seed=0)
        tree = RTree(max_entries=32).insert_all(pts)
        dtree = dt.flatten(tree)
        qs = synth.synth_queries(pts, 1e-3, 300 if quick else 500, seed=1)
        lkw = {"max_results": 2048}
        wl = labels.make_workload(dtree, qs, **lkw)
        kw = dict(kind=kind, grid_sizes=(10,), label_kwargs=lkw)
        if kind == "mlp":
            kw.update(mlp_hidden=32, mlp_epochs=200 if quick else 400)
        hyb, rep = buildlib.fit_airtree(dtree, wl, **kw)
        state = rep.fit_state

        # localized inserts: one tight cluster in a data corner, through
        # the host tree's dynamic insert path (split cascades included)
        rng = np.random.default_rng(7)
        lo, hi = pts.min(axis=0), pts.max(axis=0)
        corner = lo + 0.02 * (hi - lo)
        newp = (corner + np.abs(rng.normal(0, 0.001, (20, 2)))
                ).astype(np.float32)
        tree.insert_all(newp)
        dtree2 = dt.flatten(tree)
        hyb2 = dc.replace(hyb, tree=dtree2)

        _, s_chk, r_chk = buildlib.refit_cells(hyb2, state)
        frac = r_chk.cells_changed / state.n_cells
        assert frac <= 0.25, \
            f"scenario must stay localized, got {frac:.0%} cells changed"

        def inc():
            buildlib.refit_cells(hyb2, state)
            return jnp.zeros(())

        def full():
            wl2 = labels.make_workload(dtree2, qs, **lkw)
            kwf = dict(kw, max_labels=state.cl, max_queries=state.qp)
            buildlib.fit_airtree(dtree2, wl2, **kwf)
            return jnp.zeros(())

        t_inc = _med_time(inc, reps=3)
        t_full = _med_time(full, reps=3)
        rows.append((f"refit_cells_{kind}_us", t_inc * 1e6,
                     f"cells={r_chk.cells_changed}/{state.n_cells},"
                     f"relabel={r_chk.n_relabeled},"
                     f"speedup_vs_full={t_full / t_inc:.2f}x"))
        rows.append((f"refit_full_{kind}_us", t_full * 1e6,
                     f"queries={qs.shape[0]}"))
        assert t_full / t_inc >= floor[kind], \
            f"incremental refit must be ≥{floor[kind]}x cheaper at " \
            f"≤25% cells changed, got {t_full / t_inc:.2f}x ({kind})"


def refit_recovery_smoke(rows: list) -> None:
    """``make bench-smoke`` gate for the online instance-optimization
    loop: stream queries + localized inserts through a policy-driven
    ``FreshServer`` and *assert* (a) the policy repacked mid-stream,
    (b) the AI path came back within the refit-chunk drain budget after
    the first repack — via incremental ``refit_cells`` alone (a full
    ``fit_airtree`` on the serve path trips the planted raiser), and
    (c) every segment served exactly against its visible points."""
    from repro.core import build as buildlib, schedule
    from repro.core import geometry as geo
    from repro.core.monitor import DefaultPolicy, FreshServer

    pts = synth.tweets_like(3000, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 1e-3, 150, seed=1)
    lkw = {"max_results": 2048}
    wl = labels.make_workload(dtree, qs, **lkw)
    hyb, rep = buildlib.fit_airtree(dtree, wl, kind="knn", grid_sizes=(4,),
                                    label_kwargs=lkw)
    chunk = 4
    srv = FreshServer(pts, hyb, delta_cap=256, max_visited=256,
                      max_results=512, fit_state=rep.fit_state,
                      policy=DefaultPolicy(refit_chunk=chunk,
                                           repack_at=0.1))
    stream = np.tile(qs, (4, 1))
    rng = np.random.default_rng(5)
    lo, hi = pts.min(axis=0), pts.max(axis=0)
    ins = (lo + 0.02 * (hi - lo)
           + np.abs(rng.normal(0, 0.004, (200, 2)))).astype(np.float32)

    real_fit = buildlib.fit_airtree

    def _raiser(*a, **k):
        raise AssertionError("full fit_airtree ran on the serve path")

    t0 = time.time()
    buildlib.fit_airtree = _raiser
    try:
        mixed = schedule.serve_mixed_workload(
            srv, stream, ins, batch=50, sort="hilbert", insert_every=1,
            repack_every=0)
    finally:
        buildlib.fit_airtree = real_fit
    dt_s = time.time() - t0

    n_repacks = sum(d.repack for _, d in mixed.maintenance)
    assert n_repacks >= 1, "gate must exercise a policy repack"
    n_refit = sum(r.cells_refit for r in srv.refits)
    assert n_refit > 0, "recovery must run through refit_cells chunks"
    # recovery budget: with C cells stale and `chunk` per segment, the
    # drain takes ceil(C / chunk) segments — the AI path must be back
    # within that window after the first repack
    first_rp = next(s for s, d in mixed.maintenance if d.repack)
    budget = -(-rep.fit_state.n_cells // chunk)
    u = np.asarray(mixed.stats.used_ai)
    seg_ai = [u[b:e].mean() for b, e in mixed.seg_bounds]
    window = seg_ai[first_rp + 1:first_rp + 1 + budget]
    assert window and max(window) > 0.2, \
        f"AI path did not recover within {budget} segments: {seg_ai}"
    got = np.asarray(mixed.stats.n_results)
    for (b, e), visible in schedule.visible_segments(mixed, pts):
        exp = geo.np_contains_point(
            stream[b:e][:, None, :], visible[None, :, :]).sum(axis=1)
        np.testing.assert_array_equal(got[b:e], exp,
                                      err_msg=f"segment {b}:{e}")
    rows.append(("refit_recovery_smoke_us", dt_s * 1e6,
                 f"repacks={n_repacks},refit_cells={n_refit},"
                 f"recovered<= {budget}seg,oracle=exact"))


def knn_smoke(rows: list) -> None:
    """kNN gate: two-tier distance browsing vs the brute-force
    k-distance oracle. Every row's reported neighbors must be a
    bit-exact prefix of the brute kNN — full length when not truncated,
    the in-radius prefix otherwise — so nothing is ever silently
    dropped; the deliberately tight narrow radius forces the
    radius-doubling wide tier to actually run."""
    from repro.core import knn as knnlib, schedule

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(4000, 2))
    dtree = dt.flatten(RTree.str_bulk(pts, max_entries=16))
    centers = pts[rng.integers(0, 4000, 160)].astype(np.float32)
    centers += rng.normal(scale=1e-3, size=centers.shape).astype(np.float32)
    q = np.concatenate([centers, centers], axis=1)
    k = 16
    r = knnlib.default_radius(dtree, k, margin=1.0)
    narrow, wide = knnlib.make_knn_steps(dtree, k=k, radius=r,
                                         max_visited=64)
    t0 = time.perf_counter()
    rep = schedule.serve_workload(narrow, q, batch=64, sort="hilbert",
                                  wide_fn=wide, trunc_field="truncated")
    dt_s = time.perf_counter() - t0
    assert rep.n_reserved > 0, "knn smoke: wide tier never exercised"
    bd2, _ = knnlib.knn_brute(pts, centers, k)
    got = np.asarray(rep.stats.neighbor_d2)
    tr = np.asarray(rep.stats.truncated)
    nw = np.asarray(rep.stats.n_within)
    for j in range(q.shape[0]):
        kk = k if not tr[j] else min(int(nw[j]), k)
        assert np.array_equal(got[j, :kk], bd2[j, :kk]), \
            f"knn smoke: row {j} diverged from the brute prefix"
    rows.append(("knn_smoke_stream_us", dt_s * 1e6,
                 f"Q=160,k={k},reserved={rep.n_reserved},"
                 f"residual={int(tr.sum())}"))


def join_smoke(rows: list) -> None:
    """Join gate: ``spatial_join`` vs the brute-force pair-set oracle.
    The canonical (outer, point) pair array must equal brute force
    exactly (zero silent drops), with overflow rows re-served on the
    wide tier and zero residual truncation."""
    from repro.core import joins

    rng = np.random.default_rng(1)
    pts = rng.normal(size=(4000, 2))
    dtree = dt.flatten(RTree.str_bulk(pts, max_entries=16))
    lo = pts[rng.integers(0, 4000, 150)].astype(np.float32)
    wd = rng.uniform(0, 0.2, (150, 2)).astype(np.float32)
    outer = np.concatenate([lo - wd, lo + wd], axis=1)
    t0 = time.perf_counter()
    rep = joins.spatial_join(dtree, outer, batch=64, max_pairs=4,
                             max_visited=64, wide_factor=64)
    dt_s = time.perf_counter() - t0
    assert rep.n_reserved > 0, "join smoke: wide tier never exercised"
    assert rep.residual_truncated == 0, \
        f"join smoke: {rep.residual_truncated} rows stayed truncated"
    bp = joins.join_brute(pts, outer)
    assert np.array_equal(rep.pairs, bp), \
        "join smoke: pair set diverged from brute force"
    rows.append(("join_smoke_stream_us", dt_s * 1e6,
                 f"Q=150,pairs={rep.n_pairs},reserved={rep.n_reserved}"))


def kernel_micro(rows: list) -> None:
    from repro.kernels import ops
    rng = np.random.default_rng(0)

    def rects(n):
        lo = rng.uniform(-1, 1, (n, 2))
        w = rng.uniform(0, 0.3, (n, 2))
        return jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)

    q, m = rects(1024), rects(4096)
    dtm = _time(lambda: ops.mbr_intersect(q, m))
    rows.append(("mbr_intersect_1024x4096_us", dtm * 1e6,
                 f"{1024*4096/dtm/1e9:.2f}Gpairs/s"))

    entries = jnp.asarray(rng.uniform(-1, 1, (4096, 2, 256)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 4096, (256, 32)), jnp.int32)
    val = jnp.ones((256, 32), jnp.int32)
    dtm = _time(lambda: ops.leaf_refine(q[:256], entries, idx, val))
    rows.append(("leaf_refine_256x32x256_us", dtm * 1e6,
                 f"{256*32*256/dtm/1e9:.2f}Gtests/s"))

    feats = q[:, :4]
    fidx = jnp.asarray(rng.integers(0, 4, (16, 8)), jnp.int32)
    th = jnp.asarray(rng.uniform(-1, 1, (16, 8)), jnp.float32)
    tb = jnp.asarray(rng.uniform(0, 1, (16, 256, 128)), jnp.float32)
    dtm = _time(lambda: ops.forest_infer(feats, fidx, th, tb))
    rows.append(("forest_infer_1024x16_us", dtm * 1e6, ""))

    BH, T, dk, dv = 8, 512, 64, 64
    r = jnp.asarray(rng.normal(size=(BH, T, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, T, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, T, dv)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.3, 0.999, (BH, T, dk)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(BH, dk)), jnp.float32)
    dtm = _time(lambda: ops.wkv6(r, k, v, w, u), reps=2)
    rows.append(("wkv6_8x512x64_us", dtm * 1e6,
                 f"{BH*T/dtm/1e6:.2f}Mtok/s"))


def scale_bench(rows: list, B: int = 64, quick: bool = False) -> None:
    """Large-tree scaling: per-leaf traversal cost of the three dispatch
    forms — full-VMEM fused, ancestor-sliced, per-level fallback — over a
    leaf-count sweep, so the crossover the VMEM gate encodes is measured,
    not assumed.

    Interpret mode on CPU: absolute walls track *relative* cost only.
    Each form is invoked directly (the full form under a raised budget,
    the sliced form through ``ops._sliced_call``) — the ladder would
    otherwise need a different budget override per (form, L) pair. The
    slice granularity is coarse (tl=4096) to bound interpret-mode grid
    unrolling; autotune owns the per-shape choice.
    """
    import functools

    from repro.core.device_tree import build_ancestor_table
    from repro.kernels import ops
    from repro.kernels import traverse_fused as tf

    fanout = 4
    rng = np.random.default_rng(2)
    Ls = (2048, 8192, 32768) if quick else (2048, 8192, 32768, 65536)
    for L in Ls:
        lm, lp = _synth_levels(L, fanout, rng)
        sl = build_ancestor_table([np.asarray(p) for p in lp], tl=4096)
        lo = rng.uniform(-1, 1, (B, 2))
        w = rng.uniform(0, 0.05, (B, 2))
        q = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
        L128 = (L + 127) // 128 * 128

        orig = tf.VMEM_BUDGET
        try:
            tf.VMEM_BUDGET = 1 << 40           # decide forms at trace time
            full = jax.jit(functools.partial(ops.traverse_fused,
                                             tb=B, tl=L128))
            t_full = _med_time(lambda: full(q, lm, lp), reps=7)
            sliced = jax.jit(lambda q_, lm_, lp_: ops._sliced_call(
                q_, lm_, lp_, sl, B, True))
            t_sliced = _med_time(lambda: sliced(q, lm, lp), reps=7)
        finally:
            tf.VMEM_BUDGET = orig
        per_level = jax.jit(ops._per_level_kernel_mask)
        t_pl = _med_time(lambda: per_level(q, lm, lp), reps=7)

        extra = f"B={B},fanout={fanout},w_last={sl.widths[-1]}"
        rows.append((f"scale_fused_full_L{L}_perleaf_ns",
                     t_full / L * 1e9, extra))
        rows.append((f"scale_sliced_L{L}_perleaf_ns",
                     t_sliced / L * 1e9, extra))
        rows.append((f"scale_per_level_L{L}_perleaf_ns",
                     t_pl / L * 1e9, extra))


def main(quick: bool = False) -> list:
    rows: list = []
    serving_throughput(rows, n_points=30_000 if quick else 120_000,
                       batch=256 if quick else 512)
    query_type_throughput(rows, n_points=20_000 if quick else 120_000,
                          batch=256 if quick else 512)
    traversal_micro(rows)
    compaction_micro(rows)
    ai_fusion_micro(rows)
    scale_bench(rows, quick=quick)
    freshness_bench(rows, n_points=10_000 if quick else 30_000,
                    n_ins=1024 if quick else 2048)
    refit_bench(rows, quick=quick)
    if not quick:
        # the quick (CI fast-job) run skips this section: the same job
        # already runs it via the dedicated `make bench-smoke` gate
        scheduler_bench(rows)
    kernel_micro(rows)
    for name, val, extra in rows:
        print(f"{name},{val:.2f},{extra}")
    return rows


def smoke() -> list:
    """Toy-scale gates only (the ``make bench-smoke`` / CI fast-job):
    the scheduler streaming loop (asserts sorted ≡ unsorted, so the
    serving loop cannot silently rot) and the mixed read/write freshness
    gate (asserts delta-serving ≡ the from-scratch rebuild oracle and
    repack ≡ rebuild) and the online-refit recovery gate (asserts the
    AI path recovers within ceil(C/chunk) segments after a policy
    repack with full `fit_airtree` hard-disabled, results exact
    throughout) and the query-type gates (kNN brute-prefix oracle and
    join pair-set oracle — zero silent drops on either path)."""
    rows: list = []
    # Q deliberately not a multiple of batch: the gate must exercise the
    # ragged tail's pad-and-drop path, not just full batches
    scheduler_bench(rows, Q=400, batch=128, L=2048, check=True)
    freshness_smoke(rows)
    refit_recovery_smoke(rows)
    knn_smoke(rows)
    join_smoke(rows)
    for name, val, extra in rows:
        print(f"{name},{val:.2f},{extra}")
    return rows


if __name__ == "__main__":
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--smoke", action="store_true",
                   help="scheduler streaming benchmark only, toy scale")
    a = p.parse_args()
    smoke() if a.smoke else main(quick=a.quick)
