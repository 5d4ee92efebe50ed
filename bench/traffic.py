"""The one traffic generator: a traffic file's parameters plus ``--seed``
give the cell's sequence of requests, and its stream of writes.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``query``: the kind of traffic, which names its client driver
  ``bench/drivers/<query>.py`` (``-`` read as ``_``); the driver checks
  the keys of its own kind;
* ``request``: queries per request (one closed-loop client sends a
  request and waits for all its answers);
* ``draw``: where each query comes from, drawn with replacement: the
  name of a population the driver offers (the range drivers' ``"pool"``
  picks queries of the configuration's training pool, the paper's
  protocol serving the workload the index was fitted on);
* ``inserts`` (traffic that writes): the generator of ``bench/gen.py``
  that makes the stream of inserted points, its ``params``, ``points``
  and ``seed``. The stream is the same for every ``--seed``.

Every seed draws requests of the same size from the same population;
only which queries and their order change.
"""
from __future__ import annotations

import os

import numpy as np

DRIVER_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "drivers")


def driver_name(traffic: dict) -> str:
    """The module name of the traffic's driver in ``bench/drivers``."""
    return str(traffic.get("query", "")).replace("-", "_")


class Requests:
    """A cell's requests, drawn one after another from the seed: request
    ``i`` holds the queries ``rects[src(i)]``."""

    def __init__(self, rects: np.ndarray, size: int, seed: int):
        self.rects = rects      # [P, ...] the population (range: [P, 4])
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


class Stream:
    """The traffic's inserted points in the order they arrive."""

    def __init__(self, points: np.ndarray):
        self.points = np.asarray(points, np.float64)

    def take(self, start: int, n: int) -> np.ndarray:
        """[n, 2] f64: the points at positions ``start .. start + n - 1``;
        a run that outruns the stream stops rather than replay it."""
        if start + n > self.points.shape[0]:
            raise ValueError(
                f"the insert stream holds {self.points.shape[0]} points; "
                f"the run needs {start + n}: lengthen the traffic's stream")
        return self.points[start:start + n]


def check(traffic: dict) -> None:
    """Refuse a traffic file that no driver serves (the driver checks
    the keys of its own kind)."""
    name = driver_name(traffic)
    have = sorted(f[:-3] for f in os.listdir(DRIVER_DIR)
                  if f.endswith(".py") and not f.startswith("_"))
    if name not in have:
        raise ValueError(
            f"traffic query {traffic.get('query')!r} has no driver "
            f"bench/drivers/{name}.py; drivers: {have}")


def draw(traffic: dict, seed: int, populations: dict) -> Requests:
    """The requests of ``traffic["request"]`` queries each, drawn from
    the population that ``traffic["draw"]`` names among ``populations``
    (``{name: [P, ...] array}``, what the driver offers)."""
    if traffic.get("draw") not in populations:
        raise ValueError(
            f"traffic draw must be one of {sorted(populations)}")
    if int(traffic.get("request", 0)) < 1:
        raise ValueError("traffic request must be a positive size")
    return Requests(populations[traffic["draw"]], int(traffic["request"]),
                    seed)


def inserts(traffic: dict) -> Stream:
    """The traffic's stream of inserted points, from its ``inserts``
    block: ``bench.gen.<generator>(points, seed=seed, **params)``."""
    from bench import gen
    spec = traffic["inserts"]
    make = getattr(gen, spec["generator"], None)
    if not callable(make):
        raise ValueError(f"no generator {spec['generator']!r} in bench/gen.py")
    if int(spec["points"]) < 1:
        raise ValueError("traffic inserts must hold at least one point")
    return Stream(make(int(spec["points"]), seed=int(spec["seed"]),
                       **spec.get("params", {})))
