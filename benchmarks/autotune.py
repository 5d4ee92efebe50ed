"""Tile-size autotune sweep for the fused traversal kernels.

``python -m benchmarks.autotune [--quick] [--out PATH] [--shapes B,L,F;...]``

The hand-picked ``DEF_TB / DEF_TL / SUB_TL / COMPACT_KC`` constants in
``kernels/traverse_fused.py`` are one point in a per-tree-shape trade
space (ROADMAP "Autotuned tile sizes"). This harness sweeps the knobs that
matter for the *current backend's* kernel form on synthetic STR-packed
trees, scores each candidate on a uniform + clustered serving mix (the
two workloads whose balance the tiles actually shift), and writes the
winners to a JSON cache keyed by ``(form, B, L, height)``.
``kernels/ops.py`` consults that cache on every fused dispatch — explicit
caller overrides still win, untuned shapes fall back to the defaults, and
a stale cache can only cost time, never correctness (every candidate is
asserted bit-identical to the default-tile output before it is timed).

Forms: in interpret mode (CPU container) the swept knobs are ``tb`` and
``sub_tl`` (the leaf axis is folded into one tile, so ``tl`` is fixed and
``kc`` unused); on real TPU they are ``tb``/``tl``/``kc``. Cache entries
from one form never leak into the other — the form is part of the key.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels import traverse_fused as tf


DEF_SHAPES = ((256, 2048, 4), (256, 4096, 8), (512, 2048, 4))


def _med_time(fn, reps: int = 7) -> float:
    jax.block_until_ready(fn())  # warm / compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _workloads(B: int, rng) -> list[jnp.ndarray]:
    """Uniform + clustered query batches (engine_bench's serving mix)."""
    lo = rng.uniform(-1, 1, (B, 2))
    w = rng.uniform(0, 0.05, (B, 2))
    uniform = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    c = rng.uniform(-0.8, 0.6, (1, 2))
    lo = c + rng.uniform(0, 0.15, (B, 2))
    w = rng.uniform(0, 0.02, (B, 2))
    clustered = jnp.asarray(np.concatenate([lo, lo + w], 1), jnp.float32)
    return [uniform, clustered]


def _candidates(B: int, L: int, interp: bool, quick: bool):
    """Knob grid for the current form; the default point is always included."""
    L128 = (max(128, L) + 127) // 128 * 128
    if interp:
        tbs = [min(1024, (max(8, B) + 7) // 8 * 8)] + \
            ([256] if not quick else [])
        tls = [L128] if L128 <= 8192 else [min(tf.DEF_TL, L128)]
        sub_tls = [128, 256, 512] if not quick else [256, 512]
        kcs = [tf.COMPACT_KC]       # unused by the interpret epilogue
    else:
        tbs = [128, 256, 512]
        tls = sorted({min(t, L128) for t in (256, 512, 1024)})
        sub_tls = [tf.SUB_TL]       # unused by the TPU form
        kcs = [4, 8, 16]
    for tb, tl, sub_tl, kc in itertools.product(tbs, tls, sub_tls, kcs):
        yield {"tb": tb, "tl": tl, "sub_tl": sub_tl, "kc": kc}


def sweep_shape(B: int, L: int, fanout: int, k: int, quick: bool,
                rows: list) -> tuple[str, dict]:
    from repro.data.synth_tree import synth_levels

    rng = np.random.default_rng(0)
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    lm = [jnp.asarray(m) for m in mbrs]
    lp = [jnp.asarray(p) for p in parents]
    n_levels = len(lm)
    interp = jax.default_backend() != "tpu"
    qs = _workloads(B, rng)

    def run(cand, q):
        qp, int_m, int_p, leaf_m, leaf_p = ops._fused_operands(
            q, lm, lp, cand["tb"], cand["tl"])
        return tf.traverse_compact_t(
            qp.T, int_m, int_p, leaf_m, leaf_p, k=k,
            tb=cand["tb"], tl=cand["tl"], sub_tl=cand["sub_tl"],
            kc=cand["kc"], interpret=interp)

    default = {"tb": None, "tl": None, "sub_tl": tf.SUB_TL,
               "kc": tf.COMPACT_KC}
    dtb, dtl, _, _ = ops._fused_tiles(B, L, None, None)
    default["tb"], default["tl"] = dtb, dtl
    ref_out = [jax.tree.map(np.asarray, run(default, q)) for q in qs]

    best, best_t, default_t = None, np.inf, None
    for cand in _candidates(B, L, interp, quick):
        # correctness gate: slots agree wherever valid, counts exactly
        for q, (ri, rc) in zip(qs, ref_out):
            ci, cc = jax.tree.map(np.asarray, run(cand, q))
            np.testing.assert_array_equal(cc, rc)
            np.testing.assert_array_equal(ci[:, :k], ri[:, :k])
        t = sum(_med_time(lambda q=q: run(cand, q)) for q in qs)
        if cand == default:
            default_t = t
        if t < best_t:
            best, best_t = dict(cand), t
    if default_t is None:
        default_t = sum(_med_time(lambda q=q: run(default, q)) for q in qs)
    key = tf.tune_key(B, L, n_levels, interp)
    entry = dict(best, us=best_t * 1e6, default_us=default_t * 1e6)
    rows.append((f"autotune_{key}_us", best_t * 1e6,
                 f"default_us={default_t * 1e6:.0f},"
                 f"tiles=tb{best['tb']}tl{best['tl']}"
                 f"s{best['sub_tl']}kc{best['kc']}"))
    return key, entry


def sweep_mlp_shape(B: int, L: int, g: int, Cl: int, k: int, quick: bool,
                    rows: list) -> tuple[str, dict]:
    """Knob sweep for the fused AI-path prediction kernel (``mlp_infer``).

    Same protocol as the traversal sweep: every candidate is gated
    bit-identical to the default-tile output on the serving mix before it
    is timed; winners land under the ``mlp-`` form keys the
    ``ops.mlp_predict_compact`` dispatch consults.
    """
    from repro.core.grid import cells_of_queries
    from repro.kernels import mlp_infer as mi
    from benchmarks._synth_ai import synth_mlp_bank, unit_grid

    rng = np.random.default_rng(0)
    C = g * g
    bank = synth_mlp_bank(rng, C, L, Cl=Cl)
    grid = unit_grid(g)
    interp = jax.default_backend() != "tpu"
    qs = _workloads(B, rng)
    routed = [jax.jit(cells_of_queries, static_argnames="max_cells")(
        grid, q, max_cells=4)[:2] for q in qs]

    def run(cand, q, cid, ok):
        return ops.mlp_predict_compact(
            q, bank, cid, ok, n_leaves=L, k=k, threshold=0.5,
            tb=cand["tb"], tl=cand["tl"])

    Lp = (max(128, L) + 127) // 128 * 128
    # the baseline must be what ops.mlp_predict_compact would actually
    # dispatch today (same resolution path, like sweep_shape's use of
    # _fused_tiles), not an arbitrary grid point — default_us documents
    # the win over the current dispatch
    dtb, dtl, _, _ = ops._mlp_tiles(B, L, bank, 4, k, interp)
    default = {"tb": dtb, "tl": dtl}
    if interp:
        cands = [{"tb": tb, "tl": Lp}
                 for tb in ([min(1024, B), 128] if not quick
                            else [min(1024, B)])]
    else:
        cands = [{"tb": tb, "tl": tl}
                 for tb in (128, 256, 512)
                 for tl in sorted({min(t, Lp) for t in (256, 512, 1024)})]
    if default not in cands:
        cands.insert(0, default)
    ref_out = [jax.tree.map(np.asarray, run(default, q, cid, ok))
               for q, (cid, ok) in zip(qs, routed)]

    best, best_t, default_t = None, np.inf, None
    for cand in cands:
        for (q, (cid, ok)), ro in zip(zip(qs, routed), ref_out):
            co = jax.tree.map(np.asarray, run(cand, q, cid, ok))
            for c, r in zip(co, ro):
                np.testing.assert_array_equal(c, r)
        t = sum(_med_time(lambda q=q, cid=cid, ok=ok: run(cand, q, cid, ok))
                for q, (cid, ok) in zip(qs, routed))
        if cand == default:
            default_t = t
        if t < best_t:
            best, best_t = dict(cand), t
    key = mi.tune_key_mlp(B, L, C, Cl, interp)
    entry = dict(best, us=best_t * 1e6, default_us=default_t * 1e6)
    rows.append((f"autotune_{key}_us", best_t * 1e6,
                 f"default_us={default_t * 1e6:.0f},"
                 f"tiles=tb{best['tb']}tl{best['tl']}"))
    return key, entry


def sweep_delta_shape(B: int, cap: int, k: int, quick: bool,
                      rows: list) -> tuple[str, dict]:
    """Knob sweep for the delta-probe kernel (``delta_probe``).

    Same protocol as the other sweeps: every candidate is gated
    bit-identical to the current-dispatch output before it is timed;
    winners land under the ``delta-`` form keys ``ops.delta_probe``
    consults. The buffer is probed half-full — the kernel cost is
    capacity-shaped, not fill-shaped, and half-full exercises both live
    and all-padding tiles.
    """
    from repro.kernels import delta_probe as dpk

    rng = np.random.default_rng(0)
    interp = jax.default_backend() != "tpu"
    qs = _workloads(B, rng)
    pts = np.full((cap, 2), np.inf, np.float32)
    pts[:cap // 2] = rng.uniform(-1, 1, (cap // 2, 2))
    pts = jnp.asarray(pts)

    def run(cand, q):
        return ops.delta_probe(q, pts, k=k, tb=cand["tb"], tn=cand["tn"])

    Np = (max(128, cap) + 127) // 128 * 128
    dtb, dtn, _ = ops._delta_tiles(B, cap, interp)
    default = {"tb": dtb, "tn": dtn}
    if interp:
        cands = [{"tb": tb, "tn": Np}
                 for tb in ([min(1024, B), 128] if not quick
                            else [min(1024, B)])]
    else:
        cands = [{"tb": tb, "tn": tn}
                 for tb in (128, 256, 512)
                 for tn in sorted({min(t, Np) for t in (256, 512, 1024)})]
    if default not in cands:
        cands.insert(0, default)
    ref_out = [jax.tree.map(np.asarray, run(default, q)) for q in qs]

    best, best_t, default_t = None, np.inf, None
    for cand in cands:
        for q, ro in zip(qs, ref_out):
            co = jax.tree.map(np.asarray, run(cand, q))
            for c, r in zip(co, ro):
                np.testing.assert_array_equal(c, r)
        t = sum(_med_time(lambda q=q: run(cand, q)) for q in qs)
        if cand == default:
            default_t = t
        if t < best_t:
            best, best_t = dict(cand), t
    key = dpk.tune_key_delta(B, cap, interp)
    # the cache's lane-axis knob is named ``tl`` across kernel families
    entry = {"tb": best["tb"], "tl": best["tn"], "us": best_t * 1e6,
             "default_us": default_t * 1e6}
    rows.append((f"autotune_{key}_us", best_t * 1e6,
                 f"default_us={default_t * 1e6:.0f},"
                 f"tiles=tb{best['tb']}tn{best['tn']}"))
    return key, entry


def sweep_sliced_shape(B: int, L: int, fanout: int, k: int, quick: bool,
                       rows: list) -> tuple[str, dict]:
    """Knob sweep for the ancestor-sliced traversal form (``sliced-*``
    keys).

    Unlike the other sweeps, the swept ``tl`` is the slice granularity
    baked into the ancestor table — every candidate **rebuilds the
    table** (changing tl changes the windows, hence the whole operand
    layout), and the bit-identity gate runs against the jnp oracle's
    compacted output rather than a default candidate, since no single
    default layout spans all granularities. ``ops._sliced_call`` and the
    on-the-fly table build (``_build_slices_if_concrete``) consult the
    winning entry; tables attached at ``flatten`` time keep their own
    granularity and only pick up the ``tb``/``sub_tl``/``kc`` knobs.
    """
    from repro.core.device_tree import build_ancestor_table
    from repro.core.traversal import compact_mask_counted
    from repro.data.synth_tree import synth_levels
    from repro.kernels import ref

    rng = np.random.default_rng(0)
    mbrs, parents = synth_levels(L, fanout, rng, str_pack=True)
    lm = [jnp.asarray(m) for m in mbrs]
    lp = [jnp.asarray(p) for p in parents]
    n_levels = len(lm)
    interp = jax.default_backend() != "tpu"
    qs = _workloads(B, rng)
    oracle = [jax.tree.map(np.asarray, compact_mask_counted(
        jnp.asarray(ref.traverse_fused(q, lm, lp)), k)) for q in qs]

    tables: dict = {}

    def table(tl):
        if tl not in tables:
            tables[tl] = build_ancestor_table(
                [np.asarray(p) for p in parents], tl=tl)
        return tables[tl]

    def run(cand, q):
        sl = table(cand["tl"])
        qp, im, ip, lmt, lpt = ops._sliced_operands(q, lm, lp, sl,
                                                    cand["tb"])
        return tf.traverse_compact_sliced_t(
            sl.starts, qp.T, im, ip, lmt, lpt, k=k, widths=sl.widths,
            tb=cand["tb"], tl=sl.tl, sub_tl=cand["sub_tl"],
            kc=cand["kc"], interpret=interp)

    if interp:
        # coarse granularities only: interpret unrolls the leaf-tile grid
        # at trace time, so fine slices pay a compile-time cliff
        tbs = [min(1024, (max(8, B) + 7) // 8 * 8)] + \
            ([128] if not quick else [])
        tls = [2048, 4096] if not quick else [4096]
        sub_tls = [256, 512]
        kcs = [tf.COMPACT_KC]       # unused by the interpret epilogue
    else:
        tbs = [128, 256]
        tls = [512, 1024, 2048]
        sub_tls = [tf.SUB_TL]       # unused by the TPU form
        kcs = [4, 8, 16]
    default = {"tb": tbs[0], "tl": tls[-1] if interp else tf.DEF_TL,
               "sub_tl": tf.SUB_TL, "kc": tf.COMPACT_KC}
    cands = [{"tb": tb, "tl": tl, "sub_tl": s, "kc": kc}
             for tb, tl, s, kc in itertools.product(tbs, tls, sub_tls,
                                                    kcs)]
    if default not in cands:
        cands.insert(0, default)

    best, best_t, default_t = None, np.inf, None
    for cand in cands:
        # correctness gate: counts exactly, slots agree wherever valid
        for q, (ri, rv, rc) in zip(qs, oracle):
            ci, cc = jax.tree.map(np.asarray, run(cand, q))
            np.testing.assert_array_equal(cc[:B, 0], rc)
            np.testing.assert_array_equal(np.where(rv, ci[:B, :k], 0),
                                          np.where(rv, ri, 0))
        t = sum(_med_time(lambda q=q: run(cand, q)) for q in qs)
        if cand == default:
            default_t = t
        if t < best_t:
            best, best_t = dict(cand), t
    if default_t is None:
        default_t = sum(_med_time(lambda q=q: run(default, q)) for q in qs)
    key = tf.tune_key_sliced(B, L, n_levels, interp)
    entry = dict(best, us=best_t * 1e6, default_us=default_t * 1e6)
    rows.append((f"autotune_{key}_us", best_t * 1e6,
                 f"default_us={default_t * 1e6:.0f},"
                 f"tiles=tb{best['tb']}tl{best['tl']}"
                 f"s{best['sub_tl']}kc{best['kc']}"))
    return key, entry


def main(argv=None) -> list:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=tf.autotune_cache_path(),
                   help="JSON cache path (merged, not overwritten)")
    p.add_argument("--quick", action="store_true",
                   help="smaller grid + first shape only")
    p.add_argument("--shapes", default=None,
                   help="semicolon list of B,L,fanout triples")
    p.add_argument("--k", type=int, default=64,
                   help="compaction bound used for timing")
    args = p.parse_args(argv)

    shapes = DEF_SHAPES[:1] if args.quick else DEF_SHAPES
    if args.shapes:
        shapes = tuple(tuple(int(x) for x in s.split(","))
                       for s in args.shapes.split(";"))

    rows: list = []
    cache = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            cache = json.load(f)
    for (B, L, fanout) in shapes:
        key, entry = sweep_shape(B, L, fanout, args.k, args.quick, rows)
        cache[key] = entry
        print(f"{key}: {entry}")
    key, entry = sweep_mlp_shape(256, 2048, 4, 32, args.k, args.quick, rows)
    cache[key] = entry
    print(f"{key}: {entry}")
    key, entry = sweep_delta_shape(256, 4096, args.k, args.quick, rows)
    cache[key] = entry
    print(f"{key}: {entry}")
    # sliced form: swept at a shape past the VMEM budget (the only place
    # the ladder picks it)
    key, entry = sweep_sliced_shape(256, 32768, 4, args.k, args.quick,
                                    rows)
    cache[key] = entry
    print(f"{key}: {entry}")
    with open(args.out, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} ({len(cache)} shapes)")
    return rows


if __name__ == "__main__":
    main()
