"""A configuration's deployment: built once per checkout, then loaded.

A deployment is what a server loads at start: the configuration's points,
the flattened R-tree, and for range serving the training pool of range
queries, the fitted AI+R bank and router, and the brute-force answers to
every pool query. The configuration file fixes all of it; ``--seed`` only
draws traffic from it.

The first run of a configuration in a checkout builds the deployment
through the program's own build path (``RTree.insert_all``,
``device_tree.flatten``, ``labels.make_workload``,
``build.fit_airtree`` at the pinned grid) and writes it under
``bench/.cache/deploy``; every later run loads those arrays and places
them on the device. The directory's key hashes the configuration file,
every file under ``src/repro`` and this benchmark's generator, build and
reference code, so a change to any of them builds afresh.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import shutil
import time
from typing import NamedTuple

import jax
import numpy as np

from bench import gen, reference

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
# the benchmark's own code whose change alters what a deployment holds
KEYED_BENCH_FILES = ("gen.py", "deploy.py", "reference.py")


def cache_key(config_path: str, src_dir: str | None = None) -> str:
    """sha256 over the configuration file, every file under ``src_dir``
    (default ``src/repro``; byte code excluded) and the benchmark's
    generator, build and reference code."""
    src_dir = src_dir or os.path.join(REPO_DIR, "src", "repro")
    h = hashlib.sha256()

    def add(label: str, path: str) -> None:
        h.update(label.encode() + b"\0")
        with open(path, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())

    add("config", config_path)
    for root, dirs, files in os.walk(src_dir):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if not name.endswith((".pyc", ".pyo")):
                path = os.path.join(root, name)
                add(os.path.relpath(path, src_dir), path)
    for name in KEYED_BENCH_FILES:
        add(name, os.path.join(BENCH_DIR, name))
    return h.hexdigest()


def _save(path: str, **parts) -> None:
    """Write pytrees ``parts`` under ``path`` atomically: leaves in one
    ``.npz``, tree structures pickled beside it."""
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    arrays, defs = {}, {}
    for name, tree in parts.items():
        leaves, defs[name] = jax.tree.flatten(tree)
        for i, leaf in enumerate(leaves):
            arrays[f"{name}/{i}"] = np.asarray(leaf)
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    with open(os.path.join(tmp, "treedefs.pkl"), "wb") as f:
        pickle.dump(defs, f)
    shutil.rmtree(path, ignore_errors=True)
    os.rename(tmp, path)


def _load(path: str, place: set) -> dict:
    """Read what ``_save`` wrote; parts named in ``place`` go to the
    default device, the rest stay numpy."""
    # the pickle is this benchmark's own output, read from its own cache
    with open(os.path.join(path, "treedefs.pkl"), "rb") as f:
        defs = pickle.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        out = {}
        for name, td in defs.items():
            leaves = [z[f"{name}/{i}"] for i in range(td.num_leaves)]
            if name in place:
                leaves = jax.device_put(leaves)
            out[name] = jax.tree.unflatten(td, leaves)
    return out


class Index(NamedTuple):
    points: np.ndarray    # [N, 2] f64, the configuration's data
    tree: object          # repro DeviceTree, on the device


class Fit(NamedTuple):
    hybrid: object        # repro HybridTree, on the device
    pool: np.ndarray      # [P, 4] f32 training pool of range queries
    ref_offsets: np.ndarray   # [P + 1] CSR offsets of the pool's answers
    ref_ids: np.ndarray       # [sum] point ids, ascending per query
    grid: int
    exact_fit: float
    reference_s: float    # seconds this process spent on the reference


def _log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def _dir(cfg: dict, config_path: str, cache_dir: str) -> str:
    return os.path.join(cache_dir, "deploy",
                        f"{cfg['name']}-{cache_key(config_path)[:20]}")


def load_index(cfg: dict, config_path: str,
               cache_dir: str = CACHE_DIR) -> Index:
    """The configuration's points and placed R-tree, built on a miss."""
    path = os.path.join(_dir(cfg, config_path, cache_dir), "index")
    if not os.path.isdir(path):
        from repro.core import device_tree as dt
        from repro.core.rtree import RTree
        _log(f"deployment {cfg['name']}: index cache miss, building")
        t0 = time.perf_counter()
        ds = cfg["dataset"]
        points = getattr(gen, ds["generator"])(
            ds["points"], seed=ds["seed"], **ds.get("params", {}))
        t1 = time.perf_counter()
        tree = RTree(max_entries=cfg["rtree"]["node_capacity"]) \
            .insert_all(points)
        tree = dt.flatten(tree)
        _log(f"cold build: {points.shape[0]} points generated in "
             f"{t1 - t0:.1f}s, R-tree of {tree.n_leaves} leaves, height "
             f"{tree.height}, built in {time.perf_counter() - t1:.1f}s")
        _save(path, points=points, tree=tree)
    else:
        _log(f"deployment {cfg['name']}: index cache hit")
    got = _load(path, place={"tree"})
    return Index(points=got["points"], tree=got["tree"])


def load_fit(cfg: dict, config_path: str, index: Index,
             cache_dir: str = CACHE_DIR) -> Fit:
    """The pool, the fitted hybrid and the pool's reference answers,
    built on a miss."""
    path = os.path.join(_dir(cfg, config_path, cache_dir), "fit")
    reference_s = 0.0
    if not os.path.isdir(path):
        from repro.core import build, labels
        _log(f"deployment {cfg['name']}: fit cache miss, building")
        pc, bc = cfg["pool"], cfg["bank"]
        t0 = time.perf_counter()
        pool = gen.synth_queries(index.points, pc["selectivity"],
                                 pc["queries"], seed=pc["seed"])
        wl = labels.make_workload(index.tree, pool)
        t1 = time.perf_counter()
        hyb, rep = build.fit_airtree(
            index.tree, wl, kind=bc["classifier"], tau=bc["tau"],
            grid_sizes=(bc["grid"],), max_cells=bc["max_cells"],
            max_pred=bc["max_pred"], mlp_hidden=bc["hidden"])
        t2 = time.perf_counter()
        offsets, ids = reference.range_answers(index.points, pool)
        reference_s = time.perf_counter() - t2
        _log(f"cold build: pool of {pool.shape[0]} labelled in "
             f"{t1 - t0:.1f}s, bank fitted on the {bc['grid']}² grid in "
             f"{t2 - t1:.1f}s (exact-fit {rep.exact_fit:.4f}, w2 "
             f"{tuple(hyb.ait.bank.w2.shape)}), reference answers in "
             f"{reference_s:.1f}s")
        meta = np.array([rep.grid_size, rep.exact_fit], np.float64)
        _save(path, model=(hyb.ait, hyb.router), pool=pool,
              ref=(offsets, ids), meta=meta)
    else:
        _log(f"deployment {cfg['name']}: fit cache hit")
    got = _load(path, place={"model"})
    from repro.core.hybrid import HybridTree
    ait, router = got["model"]
    offsets, ids = got["ref"]
    return Fit(hybrid=HybridTree(tree=index.tree, ait=ait, router=router),
               pool=got["pool"], ref_offsets=offsets, ref_ids=ids,
               grid=int(got["meta"][0]), exact_fit=float(got["meta"][1]),
               reference_s=reference_s)
