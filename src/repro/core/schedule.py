"""Spatial batch scheduler: Hilbert/Morton-ordered serving batches.

The fused traversal kernel's tile-level early exit (and the compaction
epilogue that inherits it) only pays off when a batch's queries are
spatially clustered — real traffic arrives interleaved. This layer
manufactures the locality: incoming queries are keyed on a space-filling
curve (``kernels.ops.spatial_key``), sorted, cut into fixed-size batches
(each batch then covers a compact region, so most leaf tiles are dead for
the whole batch), and served; the inverse permutation restores submission
order, so the caller sees results **bit-identical** to unsorted serving —
the serve step is per-query (every ServeStats row depends only on its own
query), so permuting the batch composition cannot change any row.

The scheduler is also where the engine's two-tier contract lives:
``ServeStats.r_truncated`` rows (R-path ``max_visited`` overflow — their
``n_results`` undercounts) are collected across the whole stream and
re-served on a wide-bound tier, instead of being the caller's problem.

Everything here is host-side orchestration (numpy permutations around
jit'd serve steps); the device-side work stays in the serve step itself.

``serve_workload`` writes host spans (``telemetry.span``) into the
profiler's trace, each tagged with the call's ``request`` id and its
``tier`` (``narrow``/``wide``): ``serve.request`` around the call,
``serve.keys`` (curve keys, a device round trip), ``serve.sort``
(argsort, inverse, batch padding), ``serve.step`` and ``serve.pull`` per
batch, ``serve.unpermute``, ``serve.wide`` around the wide tier's pass
and ``serve.merge``. ``ServeReport`` counts the pad rows of both tiers,
the bytes the steps return and the chunks their result-id gathers
searched; ``SERVED`` keeps the newest calls' reports, without their
stats, for readers that total a window of them.
"""
from __future__ import annotations

import itertools
from collections import deque
from typing import Callable, Iterator, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.telemetry import span
from repro.core.traversal import GATHER_CHUNK


SORT_MODES = ("none", "morton", "hilbert")


def workload_bbox(queries: np.ndarray) -> np.ndarray:
    """[Q, 4] rects → [4] bounding box of the rect *centers*.

    Keys must be computed against one shared frame or they are not
    comparable across batches; the scheduler pins the workload's own
    center extent. Degenerate extents (a single query, or every center
    coincident along an axis) are widened to a unit span around the
    collapsed value: the key normalization divides by the extent, and
    clamping a zero span to an epsilon downstream would amplify f32
    rounding in ``center − lo`` into arbitrary key orderings — a valid
    frame must always have positive area.
    """
    c = (np.asarray(queries)[:, :2] + np.asarray(queries)[:, 2:]) / 2.0
    lo, hi = c.min(axis=0), c.max(axis=0)
    flat = hi - lo <= 0
    lo = np.where(flat, lo - 0.5, lo)
    hi = np.where(flat, hi + 0.5, hi)
    return np.concatenate([lo, hi]).astype(np.float32)


def point_query_mask(queries: np.ndarray) -> np.ndarray:
    """[Q, 4] → [Q] bool: degenerate rects (zero extent on both axes).

    The scheduler-side twin of ``hybrid.is_point_query`` — the detection
    that routes a stream (or the point rows of a mixed stream) onto the
    point-query fast path: single-cell AI routing plus a narrowed
    traversal, no wide tier (a point visits exactly the leaves whose
    MBRs contain it, a set the narrow bound must cover — exactness is
    asserted, not re-served).
    """
    q = np.asarray(queries, np.float32)
    return (q[:, 0] == q[:, 2]) & (q[:, 1] == q[:, 3])


def spatial_keys(queries: np.ndarray, sort: str,
                 bbox: Optional[np.ndarray] = None) -> np.ndarray:
    """[Q, 4] → [Q] i32 curve keys (zeros for ``sort="none"``).

    A caller-supplied ``bbox`` gets the same degenerate-extent guard as
    ``workload_bbox``: zero-extent axes are widened to a unit span so
    the keys stay well-defined (coincident centers all land in one
    curve cell) instead of leaning on the epsilon clamp downstream.
    """
    if sort not in SORT_MODES:
        raise ValueError(f"sort must be one of {SORT_MODES}, got {sort!r}")
    q = np.asarray(queries, np.float32)
    if sort == "none":
        return np.zeros((q.shape[0],), np.int32)
    from repro.kernels import ops
    if bbox is None:
        bbox = workload_bbox(q)
    else:
        bbox = np.asarray(bbox, np.float32).copy()
        flat = bbox[2:] - bbox[:2] <= 0
        bbox[:2] = np.where(flat, bbox[:2] - 0.5, bbox[:2])
        bbox[2:] = np.where(flat, bbox[2:] + 0.5, bbox[2:])
    return np.asarray(ops.spatial_key(jnp.asarray(q),
                                      bbox=jnp.asarray(bbox), curve=sort))


class Schedule(NamedTuple):
    """A batching plan over one query stream."""
    order: np.ndarray    # [Q] i32 — stream position → submission index
    inv: np.ndarray      # [Q] i32 — submission index → stream position
    n_queries: int
    batch: int
    n_batches: int       # ceil(Q / batch); the tail batch is padded
    sort: str


def _check_stream(n: int, batch: int) -> None:
    if n == 0 or batch <= 0:
        raise ValueError(f"need n_queries > 0 and batch > 0, got {n}/{batch}")


def make_schedule(queries: np.ndarray, batch: int, sort: str = "hilbert",
                  bbox: Optional[np.ndarray] = None) -> Schedule:
    """Key-sorted batch formation. ``sort="none"`` keeps submission order.

    The sort is stable, so equal keys (and the ``none`` mode) preserve
    submission order — scheduling is always a pure permutation.
    """
    q = np.asarray(queries, np.float32)
    _check_stream(q.shape[0], batch)
    return _schedule_from_keys(spatial_keys(q, sort, bbox), batch, sort)


def _schedule_from_keys(keys: np.ndarray, batch: int, sort: str
                        ) -> Schedule:
    n = keys.shape[0]
    order = np.argsort(keys, kind="stable").astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(n, dtype=np.int32)
    return Schedule(order=order, inv=inv, n_queries=n, batch=int(batch),
                    n_batches=-(-n // int(batch)), sort=sort)


def iter_batches(queries: np.ndarray, sched: Schedule
                 ) -> Iterator[tuple[np.ndarray, int]]:
    """Yield ``(q [batch, 4] f32, n_valid)`` per stream batch.

    Every batch has the full static shape (one jit trace); the ragged tail
    is padded by repeating its last valid query — a real rect, so the
    padded rows are well-formed work whose stats are simply dropped.
    """
    q = np.asarray(queries, np.float32)[sched.order]
    for b in range(sched.n_batches):
        lo = b * sched.batch
        chunk = q[lo:lo + sched.batch]
        n_valid = chunk.shape[0]
        if n_valid < sched.batch:
            pad = np.repeat(chunk[-1:], sched.batch - n_valid, axis=0)
            chunk = np.concatenate([chunk, pad], axis=0)
        yield chunk, n_valid


def _rows(tree, sel) -> "jax.tree":
    """Apply a leading-axis selection to every array in a stats pytree."""
    return jax.tree.map(lambda a: np.asarray(a)[sel], tree)


def _merge_rows(narrow, wide, idx: np.ndarray):
    """Replace ``narrow``'s rows at ``idx`` with ``wide``'s, field-wise.

    The wide tier's static bounds are larger, so its slot-table fields
    (compacted leaf ids, result ids, ...) can be wider than the narrow
    tier's. Those are rank-prefix tables — the narrow width is a prefix
    view of the wide one — so wide rows are sliced to the narrow field
    shape: scalar stats (counts, flags) arrive corrected, payload tables
    keep the narrow tier's static width.
    """
    merged = {}
    for f in type(narrow)._fields:
        a = np.asarray(getattr(narrow, f)).copy()
        w = np.asarray(getattr(wide, f))
        if w.shape[1:] != a.shape[1:]:
            if any(ws < ns for ws, ns in zip(w.shape[1:], a.shape[1:])):
                raise ValueError(
                    f"wide tier field {f!r} narrower than narrow tier's: "
                    f"{w.shape} vs {a.shape}")
            w = w[(slice(None),) + tuple(slice(0, n) for n in a.shape[1:])]
        a[idx] = w
        merged[f] = a
    return type(narrow)(**merged)


class ServeReport(NamedTuple):
    """Aggregate result of one scheduled stream."""
    stats: object           # per-query stats pytree, submission order
    n_queries: int
    n_batches: int
    n_reserved: int         # rows re-served on the wide tier
    wide_batches: int
    sort: str
    pad_rows: int = 0       # n_batches·batch − n_queries: padding served
    wide_pad_rows: int = 0  # wide_batches·batch − n_reserved
    pulled_bytes: int = 0   # summed nbytes of every stats array the
    #                         steps of both tiers returned
    gather_chunks: int = 0  # chunks of GATHER_CHUNK slots the answering
    #                         path's result-id gather searched, summed
    #                         over the steps of both tiers


# one id per top-level ``serve_workload`` call in the process, carried by
# every span of the call (both tiers)
_request_ids = itertools.count()
# the reports of the newest top-level ``serve_workload`` calls in the
# process, oldest first, each with ``stats=None``
SERVED: deque = deque(maxlen=4096)


def _gather_chunks(stats) -> int:
    """Chunks of ``GATHER_CHUNK`` result slots that one step's answering
    path searched in ``traversal.gather_result_ids``, reckoned from the
    step's pulled stats: ceil(min(max hits, max_results) / C), the hits
    being ``n_results`` less any delta-buffer hits folded into it. It
    counts the answering path only: where the dispatch is masked
    (``hybrid_query``), the other path's gather runs too, on its own hit
    counts. 0 for stats without a result-id table."""
    ids = getattr(stats, "result_ids", None)
    if ids is None:
        return 0
    hits = np.asarray(stats.n_results) - np.asarray(
        getattr(stats, "delta_hits", 0))
    top = min(int(np.max(hits, initial=0)), ids.shape[1])
    return -(-top // GATHER_CHUNK)


def _serve_tier(serve_fn: Callable, q: np.ndarray, batch: int, sort: str,
                bbox: Optional[np.ndarray], request: int, tier: str
                ) -> tuple:
    """One tier's pass over a stream of [Q, 4] f32 queries: keys, sort
    and pad, one step per batch, pull, back to submission order. Returns
    ``(stats, schedule, pulled bytes, gather chunks)``."""
    n = q.shape[0]
    _check_stream(n, batch)
    tag = {"request": request, "tier": tier}
    with span("serve.keys", rows=n, **tag):
        keys = spatial_keys(q, sort, bbox)
    with span("serve.sort", rows=n, **tag):
        sched = _schedule_from_keys(keys, batch, sort)
        chunks = list(iter_batches(q, sched))
    outs, pulled, chunked = [], 0, 0
    for b, (chunk, n_valid) in enumerate(chunks):
        with span("serve.step", batch=b, rows=n_valid, **tag):
            stats = serve_fn(jnp.asarray(chunk))
        with span("serve.pull", batch=b, **tag):
            stats = jax.tree.map(np.asarray, stats)
        pulled += sum(a.nbytes for a in jax.tree.leaves(stats))
        chunked += _gather_chunks(stats)
        outs.append(_rows(stats, np.s_[:n_valid]))
    with span("serve.unpermute", rows=n, **tag):
        stream = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)
        stats = _rows(stream, sched.inv)    # back to submission order
    return stats, sched, pulled, chunked


def serve_workload(serve_fn: Callable, queries: np.ndarray, *, batch: int,
                   sort: str = "hilbert",
                   bbox: Optional[np.ndarray] = None,
                   wide_fn: Optional[Callable] = None,
                   trunc_field: str = "r_truncated") -> ServeReport:
    """Serve a full query stream through the spatial scheduler.

    ``serve_fn``: ``[batch, 4] jnp → stats`` pytree of per-query arrays
    (leading axis ``batch``) — e.g. an ``engine.make_serve_step`` closure
    or a jit'd ``hybrid_query`` wrapper. Every query of ``queries`` is
    served exactly once (ragged tails are padded, pad rows dropped) and
    the returned stats are in submission order, bit-identical to serving
    the same stream unsorted.

    Two-tier re-serve: with ``wide_fn`` (same signature, wider bounds),
    rows whose ``trunc_field`` is set are collected across the stream and
    re-served through ``wide_fn``; their stats rows are replaced by the
    wide tier's (slot-table fields sliced to the narrow tier's static
    width — see ``_merge_rows``). ``trunc_field=None`` (or absent from
    the stats) disables the second tier.
    """
    q = np.asarray(queries, np.float32)
    request = next(_request_ids)
    with span("serve.request", request=request, tier="narrow",
              rows=q.shape[0]):
        result, sched, pulled, chunked = _serve_tier(
            serve_fn, q, batch, sort, bbox, request, "narrow")
        n_reserved = wide_batches = 0
        if wide_fn is not None and trunc_field is not None \
                and hasattr(result, trunc_field):
            trunc = np.asarray(getattr(result, trunc_field)).astype(bool)
            idx = np.flatnonzero(trunc)
            n_reserved = int(idx.size)
            if n_reserved:
                tag = {"request": request, "tier": "wide",
                       "rows": n_reserved}
                with span("serve.wide", **tag):
                    wide, wsched, wpulled, wchunked = _serve_tier(
                        wide_fn, q[idx], batch, sort, bbox, request, "wide")
                wide_batches = wsched.n_batches
                pulled += wpulled
                chunked += wchunked
                with span("serve.merge", **tag):
                    result = _merge_rows(result, wide, idx)
    report = ServeReport(stats=None, n_queries=sched.n_queries,
                         n_batches=sched.n_batches, n_reserved=n_reserved,
                         wide_batches=wide_batches, sort=sort,
                         pad_rows=sched.n_batches * sched.batch
                         - sched.n_queries,
                         wide_pad_rows=wide_batches * sched.batch
                         - n_reserved,
                         pulled_bytes=pulled, gather_chunks=chunked)
    SERVED.append(report)
    return report._replace(stats=result)


def visible_segments(report: "MixedReport", base_points: np.ndarray):
    """Yield ``((lo, hi), visible)`` per segment of a mixed stream.

    ``visible`` is the [N, 2] f32 point set the segment's queries could
    see: ``base_points`` plus every chunk the scheduler reports it
    actually staged before that segment (``report.staged``). The one
    place the segment-visibility convention lives — the launch driver's
    oracle, the CI freshness gate and the tests all consume this instead
    of re-deriving the staging policy.
    """
    visible = np.asarray(base_points, np.float32)
    for s, (lo, hi) in enumerate(report.seg_bounds):
        if report.staged[s] is not None:
            visible = np.concatenate([visible, report.staged[s]])
        yield (lo, hi), visible


class MixedReport(NamedTuple):
    """Aggregate result of one mixed read/write stream."""
    stats: object           # per-query stats pytree, submission order
    n_queries: int
    n_batches: int
    n_reserved: int         # rows re-served on the wide tier
    n_inserts: int          # points staged into the delta store
    n_repacks: int          # online repacks performed mid-stream
    #                         (scheduler-initiated via repack_every;
    #                         policy repacks live in ``maintenance``)
    n_segments: int         # insert-delimited spans of the query stream
    seg_bounds: tuple       # per-segment (start, end) submission indices
    staged: tuple           # per-segment insert chunk ([m, 2] f32 or
    #                         None) ACTUALLY staged before segment s,
    #                         plus one trailing after-stream entry —
    #                         oracles derive each segment's visible point
    #                         set from this, never by re-deriving the
    #                         chunking policy
    sort: str
    maintenance: tuple = ()  # per-segment (segment_index, decision)
    #                         entries from the server's ``on_segment``
    #                         hook (maintenance-policy servers only);
    #                         segments with no decision are absent


def serve_mixed_workload(server, queries: np.ndarray,
                         inserts: Optional[np.ndarray], *, batch: int,
                         sort: str = "hilbert",
                         bbox: Optional[np.ndarray] = None,
                         insert_every: int = 1,
                         repack_every: int = 0) -> MixedReport:
    """Serve a query stream with insert batches interleaved.

    ``server`` owns the live serving state (``core.monitor.FreshServer``
    or anything duck-typed like it): ``serve(q)``/``serve_wide(q)``
    answer batches, ``insert(points)`` stages writes, ``repack()`` swaps
    in a rebuilt tree, ``delta_fill`` reports the buffer level and
    ``trunc_field`` names the wide-tier flag.

    The stream is cut into *segments* of ``insert_every`` query batches;
    before each segment after the first, the next chunk of ``inserts``
    is staged (so segment ``s`` sees exactly the first ``s`` chunks —
    deterministic visibility), and a repack fires whenever the buffer
    holds ≥ ``repack_every`` points (0 = never). Inserts with no later
    segment to precede — all of them when the stream fits in one segment
    — are staged after the final segment, so every insert always lands
    in the server (visible to subsequent streams) even though no query
    of *this* stream sees them. Within a segment the delta store is
    frozen, so each segment runs through the ordinary spatial scheduler
    (``serve_workload``) — sorted serving stays bit-identical to
    unsorted *within* the segment, and the two-tier wide re-serve also
    happens per segment (a later re-serve would see a different buffer).
    Stats come back in submission order.
    """
    q = np.asarray(queries, np.float32)
    n = q.shape[0]
    ins = None if inserts is None else np.asarray(inserts, np.float32)
    if bbox is None:
        bbox = workload_bbox(q)
    seg = max(1, int(insert_every)) * int(batch)
    n_segments = -(-n // seg)
    chunks = [None] * (n_segments + 1)
    if ins is not None and ins.shape[0]:
        if n_segments > 1:
            chunks[1:-1] = np.array_split(ins, n_segments - 1)
        else:
            chunks[-1] = ins    # no later segment: stage after the stream

    def _stage(chunk):
        count = 0
        if chunk is not None and chunk.shape[0]:
            server.insert(chunk)
            count = int(chunk.shape[0])
            if repack_every and server.delta_fill >= repack_every:
                server.repack()
                return count, 1
        return count, 0

    outs, bounds, maint = [], [], []
    n_batches = n_reserved = n_inserts = n_repacks = 0
    on_segment = getattr(server, "on_segment", None)
    for s in range(n_segments):
        ni, nr = _stage(chunks[s])
        n_inserts += ni
        n_repacks += nr
        lo, hi = s * seg, min((s + 1) * seg, n)
        rep = serve_workload(server.serve, q[lo:hi], batch=batch, sort=sort,
                             bbox=bbox, wide_fn=server.serve_wide,
                             trunc_field=getattr(server, "trunc_field",
                                                 "truncated"))
        outs.append(rep.stats)
        bounds.append((lo, hi))
        n_batches += rep.n_batches
        n_reserved += rep.n_reserved
        # between-segments maintenance window: the server rolls its
        # signal window and (policy servers) repacks/refits/demotes —
        # never under a running segment, so each segment still serves
        # against frozen state and stays bit-identical under sorting
        if on_segment is not None:
            decision = on_segment()
            if decision is not None:
                maint.append((s, decision))
    ni, nr = _stage(chunks[n_segments])
    n_inserts += ni
    n_repacks += nr
    stats = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)
    return MixedReport(stats=stats, n_queries=n, n_batches=n_batches,
                       n_reserved=n_reserved, n_inserts=n_inserts,
                       n_repacks=n_repacks, n_segments=n_segments,
                       seg_bounds=tuple(bounds), staged=tuple(chunks),
                       sort=sort, maintenance=tuple(maint))
