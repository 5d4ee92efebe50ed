"""Shard-scaling benchmark: AI-path score union ``pmax`` vs ``topk``.

``python -m benchmarks.union_scaling [--shards 1,2,4,8] [--json FILE]``

The pending ROADMAP question behind ``EngineConfig.score_union``: the
paper-faithful ``pmax`` union reduces a dense ``[B, L_glob]`` per-leaf
score table across expert shards, while the beyond-paper ``topk`` union
all-gathers per-shard ``[B, k]`` candidate lists — O(B·L_glob) vs
O(B·shards·k) collective payload, so ``topk`` should win once the model
axis is wide enough. This harness measures both at increasing model-shard
counts and reports the crossover.

Each shard count runs in a **subprocess** with
``xla_force_host_platform_device_count`` (the flag must be set before jax
initializes, and each count needs a fresh backend). Host "devices" share
the CPU, so absolute wall times are emulation artifacts; the pmax/topk
*ratio* at equal shard count is the trackable signal (collective payload
is real traffic even in emulation). Per-query outputs of the two unions
are asserted identical before timing, sweep after sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def _child(n_shards: int, reps: int) -> None:
    """One shard count: build, serve with both unions, print a JSON line."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import build, device_tree as dt, engine, labels
    from repro.core.rtree import RTree
    from repro.data import synth
    from repro.launch import mesh as pmesh

    pts = synth.tweets_like(20_000, seed=0)
    tree = RTree(max_entries=32).insert_all(pts)
    dtree = dt.flatten(tree)
    qs = synth.synth_queries(pts, 1e-4, 600, seed=1)
    wl = labels.make_workload(dtree, qs)
    hyb, _ = build.fit_airtree(dtree, wl, kind="knn", grid_sizes=(8,))

    mesh = pmesh.make_mesh((1, n_shards), ("data", "model"))
    hyb_p = engine.pad_tree_for_sharding(hyb, n_shards)
    B = 256
    q = jnp.asarray(wl.queries[:B])
    out = {"shards": n_shards}
    stats = {}
    for union in ("pmax", "topk"):
        step = engine.make_serve_step(mesh, engine.EngineConfig(
            max_visited=64, max_pred=32, score_union=union), kind="knn")
        fn = jax.jit(lambda q, step=step: step(hyb_p, q))
        with jax.set_mesh(mesh):
            stats[union] = fn(q)
            jax.block_until_ready(stats[union])   # compile + warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q))
                ts.append(time.perf_counter() - t0)
        out[union + "_us"] = float(np.median(ts)) * 1e6
    for f in stats["pmax"]._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(stats["pmax"], f)),
            np.asarray(getattr(stats["topk"], f)), err_msg=f)
    out["speedup_topk"] = out["pmax_us"] / out["topk_us"]
    print("UNION_ROW " + json.dumps(out))


def main(argv=None) -> list:
    p = argparse.ArgumentParser()
    p.add_argument("--shards", default="1,2,4,8")
    p.add_argument("--reps", type=int, default=9)
    p.add_argument("--json", default=None, metavar="FILE",
                   help="merge rows into this benchmark JSON")
    p.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child is not None:
        _child(args.child, args.reps)
        return []

    rows: list = []
    for n in (int(s) for s in args.shards.split(",")):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={n}")
        env.setdefault("PYTHONPATH", "src")
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.union_scaling",
             "--child", str(n), "--reps", str(args.reps)],
            capture_output=True, text=True, env=env)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("UNION_ROW ")), None)
        if line is None:
            print(f"shards={n} FAILED:\n{proc.stdout}\n{proc.stderr}",
                  file=sys.stderr)
            continue
        r = json.loads(line[len("UNION_ROW "):])
        for union in ("pmax", "topk"):
            extra = (f"speedup_topk={r['speedup_topk']:.2f}x"
                     if union == "topk" else "")
            rows.append((f"union_{union}_shards{r['shards']}_us",
                         r[union + "_us"], extra))
        print(f"shards={r['shards']}: pmax {r['pmax_us']:.0f}us "
              f"topk {r['topk_us']:.0f}us "
              f"(topk speedup {r['speedup_topk']:.2f}x)")

    if args.json:
        try:
            with open(args.json) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        doc["union_scaling"] = {
            name: {"value": val, "derived": extra}
            for name, val, extra in rows}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, default=str)
        print(f"wrote {args.json}")
    return rows


if __name__ == "__main__":
    main()
