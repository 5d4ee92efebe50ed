"""End-to-end serving driver: batched request stream against the AI+R-tree.

    PYTHONPATH=src python examples/spatial_serve.py [--distributed]

This is the deployment-shaped example (the paper's kind is a serving
system): a stream of mixed-α query batches flows through the router-
dispatched hybrid engine; the loop reports running throughput, per-path
traffic split and leaf-I/O savings vs the classical R-tree.
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import build, device_tree, engine, labels
from repro.core.hybrid import hybrid_query
from repro.core.rtree import RTree
from repro.launch import mesh as pmesh
from repro.launch.compile_cache import enable_compile_cache
from repro.data import synth

parser = argparse.ArgumentParser()
parser.add_argument("--points", type=int, default=100_000)
parser.add_argument("--batches", type=int, default=20)
parser.add_argument("--batch-size", type=int, default=512)
parser.add_argument("--train-queries", type=int, default=3000,
                    help="training queries per selectivity bucket")
parser.add_argument("--insert-rate", type=float, default=0.0,
                    help="fraction of points arriving as dynamic inserts "
                         "during the stream (freshness subsystem demo)")
parser.add_argument("--repack-every", type=int, default=0,
                    help="online repack once this many inserts are staged")
parser.add_argument("--distributed", action="store_true")
args = parser.parse_args()
print(f"# compile cache: {enable_compile_cache()}")
# the kernels serve on a TPU; elsewhere the jnp reference path does
on_tpu = jax.default_backend() == "tpu"

all_points = synth.tweets_like(args.points, seed=0)
n_ins = int(round(args.insert_rate * args.points))
points = all_points[:-n_ins] if n_ins else all_points
inserts = all_points[-n_ins:] if n_ins else None
tree = RTree(max_entries=128).insert_all(points)
dtree = device_tree.flatten(tree)

# training workload: mixture of selectivities (mixed α population)
train_q = np.concatenate([
    synth.synth_queries(points, s, args.train_queries, seed=i)
    for i, s in enumerate((2e-5, 5e-5, 2e-4))])
workload = labels.make_workload(dtree, train_q)
hybrid, report = build.fit_airtree(dtree, workload, kind="knn")
print(f"# fitted: grid {report.grid_size}², fit {report.exact_fit:.3f}, "
      f"router acc {report.router.test_acc:.2f}")

# serving stream: same workload distribution, shuffled into batches
rng = np.random.default_rng(1)
order = rng.permutation(workload.n_queries)

if inserts is not None:
    # Freshness demo: a mixed read/write stream through the scheduler.
    # Inserts land in the device-side delta buffer between query
    # segments (every query probes it), the guard demotes stale cells to
    # the exact R path, and the online repack folds the buffer into a
    # fresh bulk-loaded tree mid-stream.
    from repro.core import schedule
    from repro.core.monitor import FreshServer
    server = FreshServer(points, hybrid, delta_cap=max(64, n_ins),
                         max_visited=256, max_results=1024,
                         use_kernel=on_tpu)
    stream = workload.queries[
        np.resize(order, args.batches * args.batch_size)]
    t0 = time.time()
    mixed = schedule.serve_mixed_workload(
        server, stream, inserts, batch=args.batch_size, sort="none",
        insert_every=1, repack_every=args.repack_every)
    dt = time.time() - t0
    fs = server.stats()
    print(f"# stream: {mixed.n_queries/dt:8.0f} q/s | "
          f"{int(np.asarray(mixed.stats.delta_hits).sum())} delta hits | "
          f"{100*np.asarray(mixed.stats.guarded).mean():.1f}% "
          f"guard-demoted | delta fill {fs.delta_fill} | "
          f"{fs.ok_cells}/{fs.n_cells} cells eligible")
    print(f"# total: {mixed.n_queries} queries served fresh over "
          f"{mixed.n_inserts} dynamic inserts, {mixed.n_repacks} online "
          f"repacks")
    raise SystemExit(0)

step = None
if args.distributed and len(jax.devices()) > 1:
    n = len(jax.devices())
    mesh = pmesh.make_mesh((max(1, n // 2), 2), ("data", "model"))
    hybrid_s = engine.pad_tree_for_sharding(hybrid, 2)
    step = engine.make_serve_step(
        mesh, engine.EngineConfig(use_kernel=on_tpu), kind="knn")

served = 0
accesses = 0.0
baseline = 0.0
ai_hits = 0
t0 = time.time()
for b in range(args.batches):
    take = order[(b * args.batch_size) % workload.n_queries:][
        :args.batch_size]
    if take.size < args.batch_size:
        take = np.concatenate([take, order[:args.batch_size - take.size]])
    q = jnp.asarray(workload.queries[take])
    if step is not None:
        with jax.set_mesh(mesh):
            out = step(hybrid_s, q)
        acc = np.asarray(out.leaf_accesses)
        ai = np.asarray(out.used_ai)
    else:
        out = hybrid_query(hybrid, q, use_kernel=on_tpu)
        acc = np.asarray(out.leaf_accesses)
        ai = np.asarray(out.used_ai)
    base = np.asarray(hybrid_query(hybrid, q, force_path="r",
                                   use_kernel=on_tpu).leaf_accesses)
    served += args.batch_size
    accesses += acc.sum()
    baseline += base.sum()
    ai_hits += int(ai.sum())
    if (b + 1) % 5 == 0:
        dt = time.time() - t0
        print(f"# batch {b+1:3d}: {served/dt:8.0f} q/s | "
              f"leaf I/O saved {100*(1-accesses/baseline):5.1f}% | "
              f"AI-path share {100*ai_hits/served:5.1f}%")
print(f"# total: {served} queries, "
      f"{100*(1-accesses/baseline):.1f}% leaf accesses saved vs R-tree")
