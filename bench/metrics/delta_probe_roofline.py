"""Kernel layer (``kernels/delta_probe.py``): the ``delta_probe``
kernel's share of its roofline. Work, the same whatever implements the
probe: per probe call, each of the batch's padded rows tested against
each staged point (4 compares), the staged points read once (x, y f32,
8 B each) and the rows read once (4 f32, 16 B each); the driver sums
rows × staged (``probe_tests``), staged (``probe_staged``) and rows
(``probe_rows``) over every narrow and wide step. Time = the summed
device time of the ``delta_probe`` operations. Moves ``qps``."""
from bench import peaks, trace


def read(r):
    tests = r.counters.get("probe_tests")
    if r.trace is None or not tests:
        return None
    t = trace.kernel_s(r.trace, "delta_probe")
    if t <= 0:
        return None
    return peaks.roofline_share(
        r.device_kind, flops=4.0 * tests,
        bytes_moved=8.0 * r.counters["probe_staged"]
        + 16.0 * r.counters["probe_rows"], kernel_s=t)
