"""Scheduler layer (``core/schedule.py``): bytes the serving steps of
both tiers returned to the host (``ServeReport.pulled_bytes``, from the
scheduler's log ``schedule.SERVED``) per query of the window. None where
the program keeps no such log. Moves ``p50_ms``."""
from bench import program


def read(r):
    reps = program.served_in_window(r.counters)
    if not reps:
        return None
    return sum(x.pulled_bytes for x in reps) / r.counters["queries"]
