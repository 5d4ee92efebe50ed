"""Readings that set the limits of ``correct``: the program on many seeds
and the control on a few, in one process so the deployment loads once.

    python -m bench.readings --workload gaussian-range --seconds 10 \\
        --seeds 1,2,3 --control-seeds 101,102,103

The control is the program with its wide re-serve switched off (the
driver's ``Client(..., wide_tier=False)``: the scheduler gets no wide
step, or no flag to re-serve by): rows that overflowed the narrow bound
keep their narrow answers, which breaks the configurations' guarantee
that truncated rows are re-served, never approximated or dropped. Each run prints its compared numbers as one
``reading`` line. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from bench import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    a = p.parse_args(argv)
    root = os.getcwd()
    spec = run.load_spec(root)
    cell, cfg, cfg_path, traffic = run.find_cell(spec, a.workload, root)
    run.require_chips(cell["chips"])
    sys.path.insert(0, os.path.join(root, "src"))
    from bench import deploy
    run.enable_compile_cache(deploy.CACHE_DIR)
    e2e, _ = run.cell_metrics(spec, cell["name"])
    plan = [(int(s), True) for s in a.seeds.split(",") if s] + \
        [(int(s), False) for s in a.control_seeds.split(",") if s]
    for seed, wide in plan:
        res, _ = run.run_cell(
            cell, cfg, cfg_path, traffic, seed=seed, seconds=a.seconds,
            trace_on=False, t_start=time.time(), e2e=e2e, per_layer=[],
            cache_dir=deploy.CACHE_DIR, wide_tier=wide)
        print("reading " + json.dumps({
            "workload": cell["name"], "seed": seed,
            "side": "program" if wide else "control",
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"],
            "checks": {k: v["value"] for k, v in res["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
