"""Maintenance layer (``core/delta.py``, ``core/monitor.py``): programs
lowered inside the measured window (``jax.monitoring``'s jaxpr-to-MLIR
events, counter ``lowerings``), such as a serve step retraced for the
leaf count a repack left. The target is 0. Moves ``qps``."""


def read(r):
    return r.counters.get("lowerings")
