"""Hybrid layer (``core/hybrid.py``): mean of the
``leaf_accesses`` the served rows return, the paper's cost unit. Moves
``qps``."""


def read(r):
    n = r.counters.get("queries", 0)
    return r.counters["leaf_accesses"] / n if n else None
