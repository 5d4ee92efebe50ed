"""Driver of ``range`` traffic: read-only range requests through the
program's two-tier range steps.

One closed-loop client sends a request and waits for all its answers:
spatial sort, narrow step, wide re-serve of truncated rows, rows back in
submission order, through ``repro.core.schedule.serve_workload`` over
``repro.launch.serve.make_serve_fns``'s single-device steps.
"""
from __future__ import annotations

import math
import time
from typing import NamedTuple

import numpy as np

# fixed warm-up traffic: the same set-up work for every seed
WARM_REQUESTS = 4
BIG = np.iinfo(np.int64).max
KEYS = ("query", "request", "draw")
DRAWS = ("pool",)


def check_traffic(traffic: dict, keys=KEYS) -> None:
    """Refuse a traffic file that lacks one of ``keys`` or draws from a
    population this driver does not offer."""
    missing = [k for k in keys if k not in traffic]
    if missing:
        raise ValueError(f"{traffic.get('query')} traffic lacks {missing}")
    if traffic["draw"] not in DRAWS:
        raise ValueError(f"traffic draw must be one of {DRAWS}")


def requests(traffic: dict, seed: int, index, fit):
    """The cell's requests: ``traffic["request"]`` queries each, drawn
    with replacement from the configuration's query pool."""
    from bench import traffic as trlib
    return trlib.draw(traffic, seed,
                      {"pool": np.asarray(fit.pool, np.float32)})


class Server(NamedTuple):
    """What a cell's client drives: the program's two-tier steps and the
    arithmetic of the leaves each row's answer needs."""
    narrow: object          # jitted [batch, 4] → stats
    wide: object
    trunc_field: str
    bbox: np.ndarray        # the scheduler's fixed key frame
    batch: int
    sort: str
    needed: object          # (stats, tier) → [rows] leaves the answer needs
    state: object           # device arrays to free before the reference


def serve_args(cfg: dict, *extra: str):
    """The program's serving options for the configuration's ``serve``
    block (on a TPU the kernels always serve)."""
    from repro.launch import serve
    sv = cfg["serve"]
    return serve.parse_args([
        "--classifier", cfg["bank"]["classifier"],
        "--batch", str(sv["batch"]), "--sort", sv["sort"],
        "--max-visited", str(sv["max_visited"]),
        "--wide-factor", str(sv["wide_factor"]), *extra])


def range_server(fit, cfg: dict):
    from repro.core import schedule
    from repro.launch import serve
    sv = cfg["serve"]
    args = serve_args(cfg)
    import jax
    fns = serve.make_serve_fns(fit.hybrid, args, jax.devices()[:1])
    bounds = {"narrow": sv["max_visited"],
              "wide": sv["max_visited"] * sv["wide_factor"]}

    def needed(st, tier):
        # AI-path answers need their predicted leaves; R-path answers
        # the visited leaves, as far as the step's bound reaches
        return np.where(st.used_ai, st.leaf_accesses,
                        np.minimum(st.n_visited_r, bounds[tier]))

    print(f"# range steps: fused MLP kernel "
          f"{'on' if fns.ai_fused else 'off'}, kernels "
          f"{'on' if args.kernel else 'off'}", flush=True)
    return Server(fns.narrow, fns.wide, fns.trunc_field,
                  schedule.workload_bbox(fit.pool), sv["batch"], sv["sort"],
                  needed, fit.hybrid)


def make_server(index, fit, cfg: dict, traffic: dict, cell: dict) -> Server:
    return range_server(fit, cfg)


class Client:
    """One closed-loop client of a ``Server``: serves a request through
    ``repro.core.schedule.serve_workload`` and keeps the window's
    counters and answers."""

    def __init__(self, server: Server, spans, wide_tier: bool = True):
        self.s = server
        self.spans = spans
        self.wide_tier = wide_tier
        self.counters = {"queries": 0, "wide_rows": 0, "leaf_accesses": 0,
                         "ai_rows": 0, "refine_leaves": 0}
        self.latencies = []
        self.answers = []

    @property
    def state(self):
        return self.s.state

    def _step(self, fn, span, calls):
        import jax

        def call(qb):
            self.spans.phase(span)
            out = fn(qb)
            self.spans.phase("to_host")
            out = jax.tree.map(np.asarray, out)
            self.spans.phase("merge")
            calls.append(out)
            return out
        return call

    def serve(self, q: np.ndarray):
        """Serve one request; returns ``(report, narrow_outs, wide_outs)``."""
        from repro.core import schedule
        narrow_outs, wide_outs = [], []
        self.spans.phase("schedule")
        rep = schedule.serve_workload(
            self._step(self.s.narrow, "narrow_step", narrow_outs), q,
            batch=self.s.batch, sort=self.s.sort, bbox=self.s.bbox,
            wide_fn=(self._step(self.s.wide, "wide_step", wide_outs)
                     if self.wide_tier else None),
            trunc_field=self.s.trunc_field)
        self.spans.phase(None)
        return rep, narrow_outs, wide_outs

    def record(self, q, out, latency: float) -> None:
        rep, narrow_outs, wide_outs = out
        st = rep.stats
        c = self.counters
        c["queries"] += q.shape[0]
        c["wide_rows"] += rep.n_reserved
        c["leaf_accesses"] += int(np.sum(st.leaf_accesses))
        c["ai_rows"] += int(np.sum(st.used_ai))
        for outs, tier, n in ((narrow_outs, "narrow", q.shape[0]),
                              (wide_outs, "wide", rep.n_reserved)):
            if outs:
                cat = type(outs[0])(*(np.concatenate(f) for f in zip(*outs)))
                c["refine_leaves"] += int(
                    np.sum(self.s.needed(cat, tier)[:n]))
        self.latencies.append(latency)
        w = max(int(np.max(st.n_results)), 1)
        self.answers.append((np.asarray(st.n_results),
                             np.asarray(st.result_ids)[:, :w]))


def sort_counts(rows: int, wide: int, n: int) -> tuple[int, int]:
    """The counts of re-served rows a request of ``n`` rows can plausibly
    hold, after ``wide`` of ``rows`` warm-up rows were re-served: binomial
    over an upper estimate of the share, ±5σ."""
    # an upper estimate, so that a share the warm-up barely saw still
    # warms a few counts
    p = (wide + 3) / rows
    mu, sd = n * p, math.sqrt(n * p * (1 - p))
    return max(1, int(mu - 5 * sd)), min(n, int(math.ceil(mu + 5 * sd)) + 1)


def warm_sort(q0: np.ndarray, counts, sort: str, bbox: np.ndarray
              ) -> float:
    """Run the spatial sort at each of ``counts`` re-served rows; returns
    the seconds it took."""
    from repro.core import schedule
    t1 = time.perf_counter()
    for j, k in enumerate(counts):
        schedule.spatial_keys(q0[:k], sort, bbox)
        if j % 32 == 31:
            print(f"# warm-up: sort at {k} re-served rows, "
                  f"{time.perf_counter() - t1:.1f}s", flush=True)
    return time.perf_counter() - t1


def warm(client: Client, reqs, spans) -> str:
    """Compile and run every program and shape the window will drive:
    the fixed warm-up requests through both tiers, then the spatial sort
    at every count of re-served rows a request can plausibly hold."""
    s = client.s
    rows = wide = 0
    t0 = time.perf_counter()
    for i in range(WARM_REQUESTS):
        q = reqs.queries(i)
        rep, _, _ = client.serve(q)
        rows += q.shape[0]
        wide += rep.n_reserved
    q0 = reqs.queries(0)
    client._step(s.wide, "wide_step", [])(q0[:s.batch])
    lo, hi = sort_counts(rows, wide, q0.shape[0])
    t1 = time.perf_counter()
    sort_s = warm_sort(q0, range(lo, hi + 1), s.sort, s.bbox)
    spans.phase(None)
    return (f"warm-up: {WARM_REQUESTS} requests in {t1 - t0:.1f}s, re-serve "
            f"share {(wide + 3) / rows:.4f}, sort warmed for {lo}..{hi} "
            f"re-served rows in {sort_s:.1f}s")


# -------------------------------------------------------------- checks

def dense_rows(offsets: np.ndarray, ids: np.ndarray, src: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """Rows ``src`` of CSR answers as ``(counts [R], ids [R, w])``, each
    row's ids in CSR order and padded with ``BIG``."""
    n = offsets[src + 1] - offsets[src]
    w = int(n.max(initial=0))
    col = np.arange(w)[None, :]
    out = np.full((src.size, w), BIG, np.int64)
    take = col < n[:, None]
    out[take] = ids[(offsets[src][:, None] + col)[take]]
    return n, out


def wrong(n_res: np.ndarray, got: np.ndarray, n_ref: np.ndarray,
          ref: np.ndarray) -> np.ndarray:
    """[R] bool: rows whose served count or id set differs from the
    reference's (``ref`` ascending per row, padded with ``BIG``)."""
    w = max(ref.shape[1], got.shape[1])
    col = np.arange(w)[None, :]
    r = np.full((ref.shape[0], w), BIG, np.int64)
    r[:, :ref.shape[1]] = ref
    g = np.full((got.shape[0], w), BIG, np.int64)
    g[:, :got.shape[1]] = got
    g[col >= n_res[:, None]] = BIG
    g.sort(axis=1)
    return (n_res != n_ref) | np.any(g != r, axis=1)


def check(client: Client, reqs, index, fit) -> tuple[dict, int]:
    """Every row of the window: the served count and id set against the
    reference's, exactly. Returns ``(checks, wrong rows)``."""
    bad = 0
    for i, (n_res, got) in enumerate(client.answers):
        n_ref, ref = dense_rows(fit.ref_offsets, fit.ref_ids, reqs.src(i))
        bad += int(wrong(n_res, got, n_ref, ref).sum())
    return {"wrong_rows": (bad, 0)}, bad
