"""Kernel layer (``kernels/leaf_refine.py``): the ``leaf_refine`` kernel's
share of its roofline. Work = the leaves the served answers need (AI-path
rows: their predicted leaves; R-path rows: their visited leaves up to the
step's bound), each a tile of ``entries_per_leaf`` (x, y) f32 pairs read
once (8 B per entry) and tested by four compares. Time = the summed
device time of the ``leaf_refine`` operations. Moves ``qps``."""
from bench import peaks, trace


def read(r):
    leaves = r.counters.get("refine_leaves")
    if r.trace is None or leaves is None:
        return None
    t = trace.kernel_s(r.trace, "leaf_refine")
    if t <= 0:
        return None
    entries = leaves * r.entries_per_leaf
    return peaks.roofline_share(r.device_kind, flops=4.0 * entries,
                                bytes_moved=8.0 * entries, kernel_s=t)
