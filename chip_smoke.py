"""Chip smoke: serve the AI+R-tree on a TPU through the repo's entry points.

    python chip_smoke.py               # one chip: range, kNN, mixed phases
    python chip_smoke.py --four-chip   # four chips: the sharded engine
    #                                    against the single-device path

One process, no subprocesses. It refuses to run anywhere but on a TPU.
The one-chip run builds the Chicago-Crimes-like index at the paper's size
(872,000 points, node capacity 128) once, fits the MLP bank on 4,096
range queries at selectivity 5e-5, and serves three phases through the
kernels with the same functions ``python -m repro.launch.serve`` runs:

* range — closed loop, batch 512, Hilbert-sorted, two-tier wide re-serve;
* kNN — k = 8, probe-radius margin 2.0;
* mixed read/write — 5 % of the index size arrives as inserts between
  query segments, with one online repack.

Each phase must end with 0 oracle mismatches, and each phase's jitted
narrow step must compile to Mosaic calls of the kernels that phase runs.
The fused MLP kernel's slot tables must equal the jnp oracle's on every
range query. ``--four-chip`` runs only the range phase, through the
``shard_map`` engine on a 2×2 (data × model) mesh with the bank fitted on
the 20² grid, and the single-device path on the same queries; per-query
results, leaf accesses and truncation flags must be identical, and some
rows must reach the engine's wide tier.

Lines starting with ``#`` report progress; the last line is one JSON
object naming the device. Any failure exits nonzero before that line.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core import device_tree as dt  # noqa: E402
from repro.core import build, knn as knnlib, labels, schedule  # noqa: E402
from repro.core.grid import cells_of_queries  # noqa: E402
from repro.core.rtree import RTree  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.kernels import ops as kops, ref as kref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

POINTS = 872_000          # Chicago Crimes, the paper's second dataset
NODE_CAPACITY = 128
QUERIES = 4096
SELECTIVITY = 5e-5
BATCH = 512
INSERT_RATE = 0.05
SEGMENTS = QUERIES // BATCH   # mixed phase: one segment per batch
FOUR_CHIP_GRID = 20

# kernels each phase's narrow step must call ("a|b": either)
RANGE_KERNELS = ("forest_infer", "traverse_compact|traverse_compact_sliced",
                 "mlp_infer", "mlp_union", "leaf_refine")
KEY_KERNELS = ("spatial_key",)
KNN_KERNELS = ("traverse_compact|traverse_compact_sliced", "knn_browse")
MIXED_KERNELS = RANGE_KERNELS + ("delta_probe",)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: {msg}")


def require_kernels(phase: str, want: tuple, lower) -> None:
    """Compile ``lower()``, a served step lowered at its serving
    arguments; every kernel of ``want`` (``"a|b"``: either) must be a
    Mosaic call of the compiled program."""
    t0 = time.perf_counter()
    got = kops.mosaic_kernels(lower().compile().as_text())
    print(f"# {phase}: compiled in {time.perf_counter() - t0:.1f}s; "
          f"Mosaic kernels {sorted(got)}")
    missing = [w for w in want if not set(w.split("|")) & got]
    check(not missing, f"{phase}: no Mosaic call of {missing}")


def serve_args(n_points: int, *extra: str) -> argparse.Namespace:
    """``repro.launch.serve``'s options for this run (kernels on)."""
    n_ins = int(round(INSERT_RATE * n_points))
    # inserts land in SEGMENTS - 1 = 7 chunks; the buffer repacks once it
    # holds half of them — after the 4th chunk, and never again
    cap = 1 << int(np.ceil(np.log2(n_ins * 4 / 7 + BATCH)))
    return serve.parse_args([
        "--dataset", "crimes", "--points", str(n_points),
        "--queries", str(QUERIES), "--selectivity", str(SELECTIVITY),
        "--node-capacity", str(NODE_CAPACITY), "--classifier", "mlp",
        "--batch", str(BATCH), "--sort", "hilbert", "--reps", "1",
        "--knn-k", "8", "--knn-margin", "2.0", "--kernel",
        "--insert-every", "1", "--repack-every", str(n_ins // 2),
        "--delta-cap", str(cap), *extra])


def build_index(n_points: int):
    pts = synth.crimes_like(n_points)
    t0 = time.perf_counter()
    tree = RTree(max_entries=NODE_CAPACITY).insert_all(pts)
    dtree = dt.flatten(tree)
    print(f"# index: {pts.shape[0]} points, {dtree.n_leaves} leaves, "
          f"height {dtree.height}, host build "
          f"{time.perf_counter() - t0:.1f}s")
    return pts, dtree


def fit(pts, dtree, grid_sizes=None):
    """Fit the MLP bank, searching ``grid_sizes`` (default: the build's
    own search)."""
    t0 = time.perf_counter()
    qs = synth.synth_queries(pts, SELECTIVITY, QUERIES)
    wl = labels.make_workload(dtree, qs)
    kw = {} if grid_sizes is None else {"grid_sizes": grid_sizes}
    hyb, rep = build.fit_airtree(dtree, wl, kind="mlp", verbose=True, **kw)
    print(f"# fit: {QUERIES} range queries at selectivity {SELECTIVITY}, "
          f"grid {rep.grid_size}², exact-fit {rep.exact_fit:.3f}, bank "
          f"{tuple(hyb.ait.bank.w2.shape)}, {time.perf_counter() - t0:.1f}s")
    return wl, hyb, rep


def range_phase(hyb, wl, args, devices):
    fns = serve.make_serve_fns(hyb, args, devices)
    check(fns.ai_fused, "range: the fused MLP kernel would not dispatch")
    qb = jnp.asarray(wl.queries[:BATCH])
    require_kernels("range step", RANGE_KERNELS,
                    lambda: fns.step.lower(fns.hybrid, qb))
    bbox = jnp.asarray(schedule.workload_bbox(wl.queries))
    key_step = jax.jit(functools.partial(kops.spatial_key, curve=args.sort))
    require_kernels("range key step", KEY_KERNELS,
                    lambda: key_step.lower(qb, bbox))
    mism, report = serve.serve_range(fns, wl, args)
    check(mism == 0, f"range: {mism} oracle mismatches")
    return fns, report


@functools.partial(jax.jit, static_argnames="n_leaves")
def _mlp_both(ait, queries, n_leaves: int):
    """The fused kernel's and the dense jnp oracle's AI-path slot tables
    ``(leaf_idx, valid, count)`` for one batch."""
    bank = ait.bank
    cid, ok, _ = cells_of_queries(ait.grid, queries, ait.max_cells)
    got = kops.mlp_predict_compact(queries, bank, cid, ok, n_leaves=n_leaves,
                                   k=ait.max_pred, threshold=ait.threshold)
    want = kref.mlp_predict_compact(
        (queries - bank.mu) / bank.sd, jnp.clip(cid, 0, bank.w1.shape[0] - 1),
        ok, bank.w1, bank.b1, bank.w2, bank.b2, bank.label_map, bank.lmask,
        n_leaves=n_leaves, k=ait.max_pred, threshold=ait.threshold)
    return got, want


def mlp_oracle_check(hyb, wl) -> None:
    """The fused MLP kernel against ``kernels.ref`` on every workload
    query: training and the cells' exact-fit verdicts use the oracle's
    arithmetic, so a label the kernel thresholds differently could drop a
    leaf from an AI-path answer. Slot tables and counts must be equal."""
    rows = slots = 0
    for i in range(0, wl.queries.shape[0], BATCH):
        got, want = _mlp_both(hyb.ait, jnp.asarray(wl.queries[i:i + BATCH]),
                              n_leaves=hyb.tree.n_leaves)
        diff = np.zeros(got[2].shape[0], bool)
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            diff |= (g != w).reshape(g.shape[0], -1).any(axis=1)
        rows += int(diff.sum())
        slots += int(np.asarray(want[2]).sum())
    print(f"# mlp kernel vs oracle: {rows} / {wl.queries.shape[0]} queries "
          f"differ in slot tables or counts ({slots} predicted leaves)")
    check(rows == 0, f"mlp kernel: {rows} queries differ from the oracle")


def knn_phase(pts, dtree, args) -> None:
    r = knnlib.default_radius(dtree, args.knn_k, margin=args.knn_margin)
    narrow, _ = knnlib.make_knn_steps(
        dtree, k=args.knn_k, radius=r, max_visited=args.max_visited,
        wide_factor=args.wide_factor, use_kernel=args.kernel)
    c = jnp.asarray(pts[:BATCH], jnp.float32)
    require_kernels("knn step", KNN_KERNELS,
                    lambda: narrow.lower(jnp.concatenate([c, c], axis=1)))
    mism = serve.serve_knn(dtree, pts, args)
    check(mism == 0, f"knn: {mism} oracle mismatches")


def mixed_phase(pts, hyb, wl, rep, args) -> None:
    # the jitted step serve_mixed's server runs, at its first batch
    srv = serve.make_fresh_server(pts, hyb, args, jax.devices()[:1])
    require_kernels("mixed step", MIXED_KERNELS,
                    lambda: srv.lower(wl.queries[:BATCH]))
    # new arrivals from the same city: 5 % of the index size
    extra = synth.crimes_like(int(round(INSERT_RATE * pts.shape[0])),
                              seed=2)
    print(f"# mixed: {extra.shape[0]} inserts over {SEGMENTS} segments, "
          f"repack at {args.repack_every} staged, delta cap "
          f"{args.delta_cap}")
    mism, mixed = serve.serve_mixed(pts, extra, hyb, wl, args, rep)
    check(mixed.n_repacks == 1, f"mixed: {mixed.n_repacks} repacks, not 1")
    check(mism == 0, f"mixed: {mism} oracle mismatches")


def one_chip(n_points: int) -> None:
    args = serve_args(n_points)
    pts, dtree = build_index(n_points)
    wl, hyb, rep = fit(pts, dtree)
    range_phase(hyb, wl, args, jax.devices()[:1])
    mlp_oracle_check(hyb, wl)
    knn_phase(pts, dtree, args)
    mixed_phase(pts, hyb, wl, rep, args)


def four_chip(n_points: int) -> None:
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--four-chip needs 4 chips, found "
                             f"{len(jax.devices())}")
    pts, dtree = build_index(n_points)
    # the grid the one-chip search settles on at 872k: the comparison
    # needs one fitted bank, not the search, and the host-bound search
    # would hold four chips for minutes
    wl, hyb, _ = fit(pts, dtree, grid_sizes=(FOUR_CHIP_GRID,))
    args_e = serve_args(n_points, "--distributed")
    fns_e, rep_e = range_phase(hyb, wl, args_e, devices)
    check(rep_e.n_reserved > 0, "engine: no row reached the wide tier")
    leaves = fns_e.hybrid.tree.leaf_entries
    mesh = dict(leaves.sharding.mesh.shape)
    check(mesh == {"data": 2, "model": 2},
          f"engine mesh {mesh}, not 2x2 data x model")
    rows = {(s.device.id, s.index[0].start or 0, s.data.shape[0])
            for s in leaves.addressable_shards}
    n_loc = leaves.shape[0] // 2
    check(len({d for d, _, _ in rows}) == 4
          and {(lo, n) for _, lo, n in rows} == {(0, n_loc), (n_loc, n_loc)},
          f"leaves are not split over 'model': {sorted(rows)}")
    print(f"# engine: leaves [{leaves.shape[0]}] split over 'model' as "
          f"{sorted((lo, n) for _, lo, n in rows)[::2]} on 4 devices")
    _, rep_1 = range_phase(hyb, wl, serve_args(n_points), devices[:1])
    e, o = rep_e.stats, rep_1.stats
    diffs = {
        "n_results": int(np.sum(np.asarray(e.n_results)
                                != np.asarray(o.n_results))),
        "leaf_accesses": int(np.sum(np.asarray(e.leaf_accesses)
                                    != np.asarray(o.leaf_accesses))),
        "truncated": int(np.sum(np.asarray(e.r_truncated)
                                != np.asarray(o.truncated))),
    }
    # the tiers may split rows differently (the engine flags R-path
    # truncation per shard): the final answers must agree
    print(f"# engine vs single device: per-query differences {diffs} over "
          f"{rep_e.n_queries} queries; re-served wide: engine "
          f"{rep_e.n_reserved}, single device {rep_1.n_reserved}")
    check(not any(diffs.values()), f"engine differs: {diffs}")
    mlp_oracle_check(hyb, wl)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chip", action="store_true",
                   help="run only the range phase on the 2x2 shard_map "
                        "engine and on one device, and compare them")
    p.add_argument("--points", type=int, default=POINTS,
                   help="index size (the paper's Chicago Crimes: 872,000)")
    args = p.parse_args()
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"needs a TPU, found {dev.platform}")
    print(f"# device: {dev.device_kind} x{len(jax.devices())}")
    print(f"# compile cache: {enable_compile_cache()}")
    if args.points != POINTS:
        print(f"# index cut to {args.points} points (paper: {POINTS})")
    t0 = time.perf_counter()
    (four_chip if args.four_chip else one_chip)(args.points)
    print(f"# total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
