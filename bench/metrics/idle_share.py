"""Device layer (TPU): share of the traced window in which no operation
ran on the chip. Moves ``qps``."""
from bench import trace


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(r.trace) / r.trace.window_s)
