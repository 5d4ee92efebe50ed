"""Subprocess helper: distributed engine freshness equivalence.

Run with 8 fake host devices at a 2×2×2 mesh; prints EQUIVALENT when
serving with a populated delta buffer matches a from-scratch ``str_bulk``
rebuild over the same points (result counts — the structural stats
legitimately differ between the two trees), and the post-repack store
serves bit-identically to the rebuild on every ServeStats field.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import build, device_tree as dt, engine, labels  # noqa: E402
from repro.core import delta as deltalib  # noqa: E402
from repro.core.rtree import RTree  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.launch import mesh as pmesh  # noqa: E402


def main() -> int:
    pts = synth.tweets_like(22_000, seed=0)
    base, extra = pts[:20_000], pts[20_000:]
    dtree = dt.flatten(RTree.str_bulk(base, max_entries=32))
    qs = synth.synth_queries(pts, 1e-4, 800, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(8,))

    store = deltalib.stage_inserts(
        deltalib.make_delta(4096, base=base.shape[0]), extra)
    tree2, dtree2, allp, empty = deltalib.repack(base, store,
                                                 max_entries=32)
    # guard every cell on the rebuilt side: the bank's labels refer to
    # the old tree (the monitor would do the same to a served repack)
    hyb2 = dataclasses.replace(
        hyb, tree=dtree2,
        ait=dataclasses.replace(hyb.ait,
                                cell_ok=jnp.zeros_like(hyb.ait.cell_ok)))

    mesh = pmesh.make_mesh((2, 2, 2), ("pod", "data", "model"))
    hyb_p = engine.pad_tree_for_sharding(hyb, 2)
    hyb2_p = engine.pad_tree_for_sharding(hyb2, 2)
    q = jnp.asarray(wl.queries[:64])
    cfg = engine.EngineConfig(max_visited=256, max_pred=32)
    step = engine.make_serve_step(mesh, cfg, kind="knn")
    ok = True
    with jax.set_mesh(mesh):
        with_delta = step(hyb_p, q, store.xy)
        rebuilt = step(hyb2_p, q)
        repacked = step(hyb2_p, q, empty.xy)
    if not np.array_equal(np.asarray(with_delta.n_results),
                          np.asarray(rebuilt.n_results)):
        print("MISMATCH: delta-serving n_results != rebuild")
        ok = False
    if not int(np.asarray(with_delta.delta_hits).sum()) > 0:
        print("DEGENERATE: no delta hits — fixture exercises nothing")
        ok = False
    # post-repack (empty buffer) must be bit-identical to the rebuild on
    # every field: the swapped tree IS a fresh bulk load
    for f in type(rebuilt)._fields:
        if not np.array_equal(np.asarray(getattr(repacked, f)),
                              np.asarray(getattr(rebuilt, f))):
            print(f"MISMATCH: repack vs rebuild field {f}")
            ok = False
    if ok:
        print("EQUIVALENT")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
