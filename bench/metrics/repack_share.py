"""Maintenance layer (``core/delta.py``, ``core/monitor.py``): share of
the traced window spent in the online repack, ``FreshServer.repack``
timed on the host clock up to its new tree's arrival on the device
(counter ``repack_s``). Moves ``qps``."""


def read(r):
    if r.trace is None or "repack_s" not in r.counters:
        return None
    return 100.0 * r.counters["repack_s"] / r.trace.window_s
