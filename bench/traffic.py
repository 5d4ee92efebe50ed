"""The one traffic generator: a traffic file's parameters plus ``--seed``
give the cell's sequence of requests.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``query``: ``"range"`` (rectangles);
* ``request``: queries per request (one closed-loop client sends a
  request and waits for all its answers);
* ``draw``: where each query comes from, drawn with replacement —
  ``"pool"`` picks queries of the configuration's training pool (the
  paper's protocol serves the workload the index was fitted on).

Every seed draws requests of the same size from the same population;
only which queries and their order change.
"""
from __future__ import annotations

import numpy as np

QUERY_TYPES = ("range",)
DRAWS = ("pool",)


class Requests:
    """A cell's requests, drawn one after another from the seed: request
    ``i`` holds the queries ``rects[src(i)]``."""

    def __init__(self, rects: np.ndarray, size: int, seed: int):
        self.rects = rects      # [P, 4] f32 population (the pool)
        self.size = size
        self._rng = np.random.default_rng(seed)
        self._src = []

    def src(self, i: int) -> np.ndarray:
        """[size] i64 row numbers of request ``i`` into ``rects``."""
        while len(self._src) <= i:
            self._src.append(self._rng.integers(0, self.rects.shape[0],
                                                self.size))
        return self._src[i]

    def queries(self, i: int) -> np.ndarray:
        return self.rects[self.src(i)]


def check(traffic: dict) -> None:
    """Refuse a traffic file the generator cannot honour."""
    if traffic.get("query") not in QUERY_TYPES:
        raise ValueError(f"traffic query must be one of {QUERY_TYPES}")
    if traffic.get("draw") not in DRAWS:
        raise ValueError(f"traffic draw must be one of {DRAWS}")
    if int(traffic.get("request", 0)) < 1:
        raise ValueError("traffic request must be a positive size")


def draw(traffic: dict, seed: int, pool: np.ndarray) -> Requests:
    """The requests of ``traffic["request"]`` queries each."""
    check(traffic)
    return Requests(np.asarray(pool, np.float32), int(traffic["request"]),
                    seed)
