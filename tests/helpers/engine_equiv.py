"""Subprocess helper: distributed engine vs single-device hybrid equivalence.

Run with 8 fake host devices; prints EQUIVALENT on success.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import build, device_tree as dt, engine, labels  # noqa: E402
from repro.core.hybrid import hybrid_query  # noqa: E402
from repro.core.rtree import RTree  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.launch import mesh as pmesh  # noqa: E402


def main() -> int:
    pts = synth.tweets_like(25_000, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 1e-4, 1000, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(8,))

    mesh = pmesh.make_mesh((2, 2, 2), ("pod", "data", "model"))
    hyb_p = engine.pad_tree_for_sharding(hyb, 2)
    B = 64
    q = jnp.asarray(wl.queries[:B])
    ref = hybrid_query(hyb, q, max_visited=128)
    ok = True
    for union in ("pmax", "topk"):
        step = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=64, max_pred=32, score_union=union), kind="knn")
        with jax.set_mesh(mesh):
            stats = step(hyb_p, q)
        checks = {
            "n_results": np.array_equal(np.asarray(stats.n_results),
                                        np.asarray(ref.n_results)),
            "used_ai": np.array_equal(np.asarray(stats.used_ai),
                                      np.asarray(ref.used_ai)),
            "leaf_accesses": np.array_equal(
                np.asarray(stats.leaf_accesses),
                np.asarray(ref.leaf_accesses)),
        }
        if not all(checks.values()):
            print(f"MISMATCH ({union}):", checks)
            ok = False
    if ok:
        print("EQUIVALENT")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
