import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

(note: no ``from __future__`` here — the XLA_FLAGS env line must stay the
very first statement of this module.)

For each cell this produces, with zero real allocation (ShapeDtypeStruct
inputs, eval_shape'd states):

  * ``compiled.memory_analysis()``  — proves the cell fits per-device HBM
  * ``compiled.cost_analysis()``    — per-device FLOPs/bytes for §Roofline
  * collective wire bytes           — parsed from the post-SPMD HLO

Results land in ``benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json``;
``benchmarks/roofline.py`` turns them into the §Roofline table.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (ACCUM, SHAPE_DEFS, cell_supported,
                                decode_specs, input_specs, state_specs)
from repro.models import transformer as tf
from repro.models.config import ModelConfig

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> Dict[str, Any]:
    """Per-device wire bytes by collective kind (ring formulas).

    all-gather: out·(n-1)/n ; reduce-scatter: out·(n-1) ;
    all-reduce: out·2(n-1)/n ; all-to-all: out·(n-1)/n ;
    collective-permute: out.
    """
    by_kind: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out_shape, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(out_shape)
        g = _GROUPS_RE.search(line)
        if g:
            n = len([x for x in g.group(1).split(",") if x.strip() != ""])
        else:
            g2 = _GROUPS_LIST_RE.search(line)
            n = int(g2.group(2)) if g2 else 2
        n = max(n, 2)
        if kind == "all-gather":
            wire = nbytes * (n - 1) / n
        elif kind == "reduce-scatter":
            wire = nbytes * (n - 1)
        elif kind == "all-reduce":
            wire = nbytes * 2 * (n - 1) / n
        elif kind == "all-to-all":
            wire = nbytes * (n - 1) / n
        else:  # collective-permute
            wire = nbytes
        by_kind[kind] = by_kind.get(kind, 0.0) + wire
        counts[kind] = counts.get(kind, 0) + 1
    return {"wire_bytes_by_kind": by_kind, "counts": counts,
            "wire_bytes_total": sum(by_kind.values())}


def _mem_dict(mem) -> Dict[str, float]:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        try:
            out[attr] = float(getattr(mem, attr))
        except Exception:
            pass
    if not out and mem is not None:
        out["repr"] = str(mem)[:2000]
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        cost = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    keep = {}
    for k, v in dict(cost).items():
        if k in ("flops", "bytes accessed", "transcendentals",
                 "optimal_seconds") or k.startswith("bytes accessed"):
            keep[k] = float(v)
    return keep


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def _airtree_cell(shape: str, multi_pod: bool):
    """The paper's engine on the production mesh: batched AI+R serving.

    Fabricated tweets-2M-scale tree (16k leaves × 256 entries), 20×20 grid
    of kNN cell models, 64k queries per batch — all ShapeDtypeStructs.
    """
    import numpy as np
    from repro.core import engine as eng
    from repro.core.device_tree import DeviceTree, Level
    from repro.core.grid import Grid
    from repro.core.aitree import AITree
    from repro.core.hybrid import HybridTree
    from repro.core.classifiers.knn import KNNBank
    from repro.core.classifiers.router import Router
    from repro.launch.specs import f32, i32

    mesh = make_production_mesh(multi_pod=multi_pod)
    union = "topk" if shape.endswith("_topk") else "pmax"
    base_shape = shape.replace("_topk", "")
    B = {"serve_64k": 65536, "serve_8k": 8192}[base_shape]
    L, M, C, Qp, Cl = 16384, 256, 400, 256, 128
    levels = (Level(mbrs=f32(1, 4), parent=i32(1)),
              Level(mbrs=f32(128, 4), parent=i32(128)),
              Level(mbrs=f32(L, 4), parent=i32(L)))
    tree = DeviceTree(levels=levels, leaf_entries=f32(L, 2, M),
                      leaf_entry_ids=i32(L, M), leaf_counts=i32(L),
                      n_points=2_000_000, max_entries=M)
    bank = KNNBank(feats=f32(C, Qp, 4), labels=f32(C, Qp, Cl),
                   label_map=i32(C, Cl), lmask=jax.ShapeDtypeStruct(
                       (C, Cl), jnp.bool_), eps=1e-6)
    ait = AITree(grid=Grid(bbox=f32(4), g=20), bank=bank,
                 cell_ok=jax.ShapeDtypeStruct((C,), jnp.bool_), kind="knn",
                 max_cells=4, max_pred=16, threshold=0.5)
    router = Router(feat_idx=i32(16, 6), thresh=f32(16, 6),
                    tables=f32(16, 2 ** 6, 1), tau=0.75)
    h = HybridTree(tree=tree, ait=ait, router=router)
    # topk variant also runs the tuned per-shard refine bound (32 vs 64):
    # per-shard visited is ~visited_total/16, so 32 is ≥5× headroom; the
    # r_truncated guard re-serves any overflow on a wide-bound tier.
    cfg = eng.EngineConfig(max_visited=64 if union == "pmax" else 32,
                           max_pred=16, score_union=union)
    step = eng.make_serve_step(mesh, cfg, kind="knn")
    q_spec = f32(B, 4)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step).lower(h, q_spec)
    meta = dict(arch="airtree", shape=shape,
                mesh="2x16x16" if multi_pod else "16x16", kind="serve",
                seq_len=0, global_batch=B)
    return lowered, mesh, meta


def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               remat_policy: str = "dots"):
    """Build (lowered, mesh, meta) for one dry-run cell."""
    if arch == "airtree":
        return _airtree_cell(shape, multi_pod)
    cfg = configs.get_config(arch)
    ok, why = cell_supported(cfg, shape)
    if not ok:
        raise ValueError(f"unsupported cell: {why}")
    mesh = make_production_mesh(multi_pod=multi_pod)
    sd = SHAPE_DEFS[shape]
    meta: Dict[str, Any] = dict(arch=arch, shape=shape,
                                mesh="2x16x16" if multi_pod else "16x16",
                                kind=sd["kind"],
                                seq_len=sd["seq_len"],
                                global_batch=sd["global_batch"])

    if sd["kind"] == "train":
        from repro.training import train_loop
        state_spec, ocfg = state_specs(cfg)
        accum = ACCUM.get(cfg.name, 1)
        meta["accum_steps"] = accum
        step = train_loop.make_train_step(cfg, opt_cfg=ocfg,
                                          accum_steps=accum,
                                          remat_policy=remat_policy)
        batch_spec = input_specs(cfg, shape)
        in_sh = (shd.params_shardings(state_spec, mesh),
                 shd.batch_shardings(batch_spec, mesh))
        with jax.set_mesh(mesh):
            lowered = jax.jit(step, in_shardings=in_sh).lower(
                state_spec, batch_spec)
        return lowered, mesh, meta

    params_spec = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0),
                               dtype=jnp.bfloat16))
    if sd["kind"] == "prefill":
        batch_spec = input_specs(cfg, shape)

        def prefill(params, batch):
            return tf.forward(cfg, params, batch, remat_policy=None)

        in_sh = (shd.params_shardings(params_spec, mesh),
                 shd.batch_shardings(batch_spec, mesh))
        with jax.set_mesh(mesh):
            lowered = jax.jit(prefill, in_shardings=in_sh).lower(
                params_spec, batch_spec)
        return lowered, mesh, meta

    # decode
    from repro.serving import decode as dec
    tok_spec, cache_spec = decode_specs(cfg, shape)

    def serve_step(params, cache, tokens):
        return dec.decode_step(cfg, params, cache, tokens)

    in_sh = (shd.params_shardings(params_spec, mesh),
             shd.cache_shardings(cache_spec, mesh),
             shd.batch_shardings(tok_spec, mesh)["tokens"])
    with jax.set_mesh(mesh):
        lowered = jax.jit(serve_step, in_shardings=in_sh).lower(
            params_spec, cache_spec, tok_spec["tokens"])
    meta["cache_bytes_global"] = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(cache_spec)
        if hasattr(x, "size"))
    return lowered, mesh, meta


# ---------------------------------------------------------------------------
# differential cost accounting
#
# XLA's cost_analysis counts a lax.scan body ONCE regardless of trip count,
# so full-depth scanned lowerings under-report FLOPs/bytes/collectives by
# ~L×. True totals are recovered from two small *unrolled* lowerings:
#     body  = f(L=2 units) − f(L=1 unit)          (per metric)
#     total = f(1 unit) + body × (units_full − 1)
# The unit is one scanned step: a layer, a local/global pair (gemma2), or an
# (enc, dec) layer pair (whisper). Known residual undercounts (documented in
# EXPERIMENTS.md): inner time scans (mamba ~<1%) and Pallas custom calls
# (wkv6 state math, ~3% for rwkv6).
# ---------------------------------------------------------------------------

def _cost_variants(cfg: ModelConfig):
    import dataclasses as dc
    if cfg.layer_pattern == "alt_local_global":
        a = dc.replace(cfg, n_layers=2, unroll_layers=True)
        b = dc.replace(cfg, n_layers=4, unroll_layers=True)
        units = cfg.n_layers // 2
    elif cfg.family == "moe":
        nd = cfg.n_dense_layers
        a = dc.replace(cfg, n_layers=nd + 1, unroll_layers=True)
        b = dc.replace(cfg, n_layers=nd + 2, unroll_layers=True)
        units = cfg.n_layers - nd
    elif cfg.family == "encdec":
        a = dc.replace(cfg, n_layers=1, n_enc_layers=1, unroll_layers=True)
        b = dc.replace(cfg, n_layers=2, n_enc_layers=2, unroll_layers=True)
        units = cfg.n_layers   # enc and dec depths are equal (12/12)
    else:
        a = dc.replace(cfg, n_layers=1, unroll_layers=True)
        b = dc.replace(cfg, n_layers=2, unroll_layers=True)
        units = cfg.n_layers
    return a, b, units


def _lower_for_cost(cfg: ModelConfig, shape: str, mesh):
    """Small unrolled lowering for one cost variant (accum forced to 1)."""
    sd = SHAPE_DEFS[shape]
    if sd["kind"] == "train":
        from repro.training import optimizer as opt, train_loop
        ocfg = opt.AdamWConfig()
        state_spec = jax.eval_shape(
            lambda: train_loop.init_train_state(
                cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16,
                opt_cfg=ocfg))
        step = train_loop.make_train_step(cfg, opt_cfg=ocfg, accum_steps=1,
                                          remat_policy="dots")
        batch_spec = input_specs(cfg, shape)
        in_sh = (shd.params_shardings(state_spec, mesh),
                 shd.batch_shardings(batch_spec, mesh))
        with jax.set_mesh(mesh):
            return jax.jit(step, in_shardings=in_sh).lower(state_spec,
                                                           batch_spec)
    params_spec = jax.eval_shape(
        lambda: tf.init_params(cfg, jax.random.PRNGKey(0),
                               dtype=jnp.bfloat16))
    if sd["kind"] == "prefill":
        batch_spec = input_specs(cfg, shape)
        fn = lambda p, b: tf.forward(cfg, p, b, remat_policy=None)  # noqa
        in_sh = (shd.params_shardings(params_spec, mesh),
                 shd.batch_shardings(batch_spec, mesh))
        with jax.set_mesh(mesh):
            return jax.jit(fn, in_shardings=in_sh).lower(params_spec,
                                                         batch_spec)
    from repro.serving import decode as dec
    tok_spec, cache_spec = decode_specs(cfg, shape)
    fn = lambda p, c, t: dec.decode_step(cfg, p, c, t)  # noqa
    in_sh = (shd.params_shardings(params_spec, mesh),
             shd.cache_shardings(cache_spec, mesh),
             shd.batch_shardings(tok_spec, mesh)["tokens"])
    with jax.set_mesh(mesh):
        return jax.jit(fn, in_shardings=in_sh).lower(
            params_spec, cache_spec, tok_spec["tokens"])


def _cost_metrics(lowered) -> Dict[str, float]:
    compiled = lowered.compile()
    cost = _cost_dict(compiled)
    coll = collective_stats(compiled.as_text())
    out = {"flops": cost.get("flops", 0.0),
           "bytes_accessed": cost.get("bytes accessed", 0.0),
           "transcendentals": cost.get("transcendentals", 0.0),
           "wire_bytes_total": coll["wire_bytes_total"]}
    for k, v in coll["wire_bytes_by_kind"].items():
        out[f"wire_{k}"] = v
    return out


def cost_scaled(arch: str, shape: str, *, multi_pod: bool = False
                ) -> Dict[str, Any]:
    """Scaled per-device cost metrics for one cell (see block comment)."""
    cfg = configs.get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    a, b, units = _cost_variants(cfg)
    ma = _cost_metrics(_lower_for_cost(a, shape, mesh))
    mb = _cost_metrics(_lower_for_cost(b, shape, mesh))
    scaled: Dict[str, Any] = {"units": units}
    for k in ma:
        body = mb[k] - ma[k]
        scaled[k] = ma[k] + body * (units - 1)
        scaled[f"{k}_per_unit"] = body
    return scaled


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             out_dir: str = RESULTS_DIR) -> Dict[str, Any]:
    t0 = time.time()
    rec: Dict[str, Any]
    try:
        lowered, mesh, rec = lower_cell(arch, shape, multi_pod=multi_pod)
        rec["lower_seconds"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_seconds"] = round(time.time() - t1, 1)
        rec["n_devices"] = int(mesh.devices.size)
        rec["memory"] = _mem_dict(compiled.memory_analysis())
        rec["cost"] = _cost_dict(compiled)
        rec["collectives"] = collective_stats(compiled.as_text())
        if arch != "airtree":
            cfg = configs.get_config(arch)
            rec["model_params"] = cfg.n_params()
            rec["model_params_active"] = cfg.n_active_params()
        else:
            rec["model_params"] = rec["model_params_active"] = 0
        rec["status"] = "ok"
    except Exception as e:
        rec = dict(arch=arch, shape=shape,
                   mesh="2x16x16" if multi_pod else "16x16",
                   status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    rec["total_seconds"] = round(time.time() - t0, 1)
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape}__{rec.get('mesh', 'x')}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--cost-pass", action="store_true",
                   help="add differential cost_scaled metrics to existing "
                        "cell JSONs (no full-depth recompile)")
    p.add_argument("--out", default=RESULTS_DIR)
    args = p.parse_args()

    cells = []
    if args.all:
        for arch in configs.ARCHS:
            alias = configs.get_config(arch).name
            for shape in SHAPE_DEFS:
                ok, why = cell_supported(configs.get_config(arch), shape)
                if ok:
                    cells.append((alias, shape))
                else:
                    print(f"SKIP {alias} {shape}: {why}")
        cells.append(("airtree", "serve_64k"))
    else:
        cells = [(args.arch, args.shape)]

    if args.cost_pass:
        mesh_tag = "2x16x16" if args.multi_pod else "16x16"
        for arch, shape in cells:
            if arch == "airtree":
                continue  # no layer scan — raw cost is already exact
            out_file = os.path.join(args.out,
                                    f"{arch}__{shape}__{mesh_tag}.json")
            if not os.path.exists(out_file):
                continue
            with open(out_file) as f:
                rec = json.load(f)
            if rec.get("status") != "ok":
                continue
            if args.skip_existing and "cost_scaled" in rec:
                print(f"SKIP (cost cached) {arch} {shape}")
                continue
            print(f"COST {arch} {shape} {mesh_tag} ...", flush=True)
            t0 = time.time()
            try:
                rec["cost_scaled"] = cost_scaled(arch, shape,
                                                 multi_pod=args.multi_pod)
                rec["cost_scaled"]["seconds"] = round(time.time() - t0, 1)
                print(f"  flops/dev={rec['cost_scaled']['flops']:.3e} "
                      f"coll={rec['cost_scaled']['wire_bytes_total']:.3e}B "
                      f"({rec['cost_scaled']['seconds']}s)", flush=True)
            except Exception as e:
                rec["cost_scaled"] = {"error": f"{type(e).__name__}: {e}"}
                print(f"  ERROR: {e}", flush=True)
            with open(out_file, "w") as f:
                json.dump(rec, f, indent=1, default=str)
        return

    for arch, shape in cells:
        mesh_tag = "2x16x16" if args.multi_pod else "16x16"
        out_file = os.path.join(args.out,
                                f"{arch}__{shape}__{mesh_tag}.json")
        if args.skip_existing and os.path.exists(out_file):
            with open(out_file) as f:
                if json.load(f).get("status") == "ok":
                    print(f"SKIP (cached) {arch} {shape} {mesh_tag}")
                    continue
        print(f"RUN  {arch} {shape} {mesh_tag} ...", flush=True)
        rec = run_cell(arch, shape, multi_pod=args.multi_pod, out_dir=args.out)
        if rec["status"] == "ok":
            fl = rec["cost"].get("flops", 0)
            print(f"  ok in {rec['total_seconds']}s  "
                  f"flops/dev={fl:.3e}  "
                  f"coll={rec['collectives']['wire_bytes_total']:.3e}B",
                  flush=True)
        else:
            print(f"  ERROR: {rec['error']}", flush=True)


if __name__ == "__main__":
    main()
