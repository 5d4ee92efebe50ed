"""The benchmark's own data and query generators.

``gaussian`` is the Gaussian distribution of the Spider spatial data
generator (Katiyar et al., "SpiderWeb: A Spatial Data Generator on the
Web", SIGSPATIAL 2020; spider.cs.ucr.edu): each coordinate drawn from a
normal distribution of mean 0.5 and standard deviation 0.1. A point that
falls outside the unit square is drawn again (about one in a million at
these parameters). ``synth_queries`` is a copy of the program's
fixed-selectivity query synthesis (arXiv:2207.00550 §V-B2). Both live
here so that no change to the program can move the data a configuration
is measured on.
"""
from __future__ import annotations

import numpy as np


def gaussian(n: int, seed: int, mean: float = 0.5,
             sd: float = 0.1) -> np.ndarray:
    """[n, 2] f64 points, each coordinate ~ N(mean, sd), inside the unit
    square, in the order they were drawn; exact duplicates dropped (the
    paper's preprocessing)."""
    rng = np.random.default_rng(seed)
    pts = rng.normal(mean, sd, (n, 2))
    out = np.any((pts < 0) | (pts > 1), axis=1)
    while out.any():
        pts[out] = rng.normal(mean, sd, (int(out.sum()), 2))
        out = np.any((pts < 0) | (pts > 1), axis=1)
    _, first = np.unique(pts, axis=0, return_index=True)
    return pts[np.sort(first)]


class _GridBuckets:
    """Point buckets on a uniform grid for fast local neighbourhood queries."""

    def __init__(self, points: np.ndarray, bins: int = 256):
        self.pts = points
        self.lo = points.min(axis=0)
        span = np.maximum(points.max(axis=0) - self.lo, 1e-12)
        self.scale = bins / span
        self.bins = bins
        ij = np.clip(((points - self.lo) * self.scale).astype(int),
                     0, bins - 1)
        key = ij[:, 0] * bins + ij[:, 1]
        order = np.argsort(key, kind="stable")
        self.sorted_idx = order
        self.key_sorted = key[order]
        self.starts = np.searchsorted(self.key_sorted,
                                      np.arange(bins * bins))
        self.ends = np.searchsorted(self.key_sorted,
                                    np.arange(bins * bins) + 1)

    def ring(self, cx: int, cy: int, r: int) -> np.ndarray:
        """Point indices in the square ring of cell-radius r around (cx,cy)."""
        b = self.bins
        cells = []
        x0, x1 = max(cx - r, 0), min(cx + r, b - 1)
        y0, y1 = max(cy - r, 0), min(cy + r, b - 1)
        for x in range(x0, x1 + 1):
            for y in range(y0, y1 + 1):
                if r == 0 or x in (cx - r, cx + r) or y in (cy - r, cy + r):
                    k = x * b + y
                    s, e = self.starts[k], self.ends[k]
                    if e > s:
                        cells.append(self.sorted_idx[s:e])
        return np.concatenate(cells) if cells else np.empty(0, np.int64)


def synth_queries(points: np.ndarray, selectivity: float, n_queries: int,
                  seed: int = 0, aspect_jitter: float = 2.0) -> np.ndarray:
    """Fixed-selectivity rectangles centered on random data points.

    Exact calibration: the rectangle half-width is set to the k-th smallest
    anisotropic L∞ distance from the center, so each query returns exactly
    ≈ ``selectivity · N`` points (paper §V-B2: 0.00001 → ~20 of 2M, etc.).
    """
    rng = np.random.default_rng(seed)
    n = points.shape[0]
    k = max(1, int(round(selectivity * n)))
    gb = _GridBuckets(points)
    out = np.empty((n_queries, 4), np.float64)
    centers = points[rng.integers(0, n, n_queries)]
    aspects = np.exp(rng.uniform(-np.log(aspect_jitter),
                                 np.log(aspect_jitter), n_queries))
    span = (points.max(axis=0) - points.min(axis=0))
    ar_base = span[1] / span[0]
    for i, c in enumerate(centers):
        ar = aspects[i] * ar_base
        cx = int(np.clip((c[0] - gb.lo[0]) * gb.scale[0], 0, gb.bins - 1))
        cy = int(np.clip((c[1] - gb.lo[1]) * gb.scale[1], 0, gb.bins - 1))
        got: list[np.ndarray] = []
        total = 0
        r = 0
        # expand rings until we certainly contain the k-th neighbour
        while r < gb.bins:
            ring = gb.ring(cx, cy, r)
            if ring.size:
                got.append(ring)
                total += ring.size
            if total >= k + 1 and r >= 1:
                break
            r += 1
        idx = np.concatenate(got) if got else np.arange(n)
        p = points[idx]
        m = np.maximum(np.abs(p[:, 0] - c[0]), np.abs(p[:, 1] - c[1]) / ar)
        m.sort()
        w = m[min(k - 1, m.size - 1)] * 1.0000001 + 1e-12
        out[i] = (c[0] - w, c[1] - ar * w, c[0] + w, c[1] + ar * w)
    return out.astype(np.float32)
