"""Scheduler layer (``core/schedule.py``): share of the window's queries
that overflowed the narrow bound and were re-served on the wide tier
(``ServeReport.n_reserved``). Moves ``p50_ms``."""


def read(r):
    n = r.counters.get("queries", 0)
    return 100.0 * r.counters["wide_rows"] / n if n else None
