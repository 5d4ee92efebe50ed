"""Scheduler layer (``core/schedule.py``): share of the wide tier's
batch rows that are padding, over the window's requests
(``ServeReport.wide_pad_rows`` over ``wide_pad_rows + n_reserved``, from
the scheduler's log ``schedule.SERVED``). None where the program keeps
no such log, or no wide batch ran. Moves ``qps``."""
from bench import program


def read(r):
    reps = program.served_in_window(r.counters)
    if not reps:
        return None
    pad = sum(x.wide_pad_rows for x in reps)
    slots = pad + sum(x.n_reserved for x in reps)
    return 100.0 * pad / slots if slots else None
