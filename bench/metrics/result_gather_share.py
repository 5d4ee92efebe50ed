"""Hybrid layer (``core/hybrid.py``): share of the chip's busy time
spent in operations under a ``gather_ids`` named scope (the result-id
gather of the AI and R paths, ``traversal.gather_result_ids``), by device
self time. The scopes are read from the trace file the run left
(``bench.program``). None where no operation carries a named scope (a
program without them). Moves ``qps``."""
from bench import program, trace


def read(r):
    if r.trace is None:
        return None
    rec = program.recorded(r.trace)
    if rec is None:
        return None
    phases = dict(program.device_phases(r.trace, rec.phases, n=None))
    if set(phases) <= {program.UNSCOPED}:
        return None
    gather = sum(s for p, s in phases.items()
                 if "gather_ids" in p.split("/"))
    return 100.0 * gather / trace.busy_s(r.trace)
