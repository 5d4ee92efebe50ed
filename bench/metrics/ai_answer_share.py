"""Hybrid layer (``core/hybrid.py``): share of the window's range queries
answered by the AI path (``HybridResult.used_ai``). Moves ``qps``, though
today the masked single dispatch runs both paths for every row."""


def read(r):
    n = r.counters.get("queries", 0)
    if "ai_rows" not in r.counters or not n:
        return None
    return 100.0 * r.counters["ai_rows"] / n
