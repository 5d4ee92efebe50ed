"""What the program under test records of itself, read for the benchmark.

* device phases: each chip operation's ``jax.named_scope`` path among
  ``PHASES`` (``core/hybrid.py``, ``core/aitree.py``,
  ``core/traversal.py``), from the ``tf_op`` stat (the op's name stack)
  of its event metadata in the serialized XSpace, which
  ``jax.profiler.ProfileData`` does not expose: ``op_phases`` walks the
  protobuf itself;
* program spans: the host spans ``repro.core.telemetry.span`` writes
  (``PROGRAM_PREFIX``, with their attributes), and the device-idle time
  attributed to the innermost one covering it;
* served requests: ``repro.core.schedule.SERVED``, the scheduler's log
  of its newest reports, taken over the window's requests.

A program that records none of it reads as a trace with every operation
``UNSCOPED``, no program spans and no served log, and the readers in
``bench/metrics`` that use this module leave their metric out.

    python3 -m bench.program bench/.cache/traces/<cell>/.../<host>.xplane.pb

prints the phase and program-span breakdowns of a trace that
``bench.run --trace 1`` left, as one JSON object.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
from typing import NamedTuple

from bench import trace

HOST_PLANE = "/host:CPU"
# the serving path's ``jax.named_scope`` names
PHASES = ("route", "guard", "ai", "r", "select", "predict", "traverse",
          "refine", "gather_ids")
UNSCOPED = "unscoped"
# host spans the program writes (``repro.core.telemetry.span``)
PROGRAM_PREFIX = "serve."
# where ``bench.run --trace 1`` leaves each cell's traces
TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".cache", "traces")


def phase_of(op_name: str) -> str:
    """The named-scope path in an op's name stack: its components that
    are ``PHASES``, in order, a repeat merged
    (``jit(hybrid_query)/r/jit(range_query_compact)/gather_ids/while``
    → ``r/gather_ids``); "" where it has none."""
    path = []
    for c in op_name.split("/"):
        c = c.rstrip(":")
        if c in PHASES and (not path or path[-1] != c):
            path.append(c)
    return "/".join(path)


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """Yield ``(field number, value)`` of the protobuf message
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` for a
    length-delimited field; fixed-width fields are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield key >> 3, v


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_phases(raw: bytes) -> dict:
    """{event name: phase} for the chip planes' operations, from the
    ``tf_op`` stat of each event's metadata in a serialized XSpace
    (``XSpace.planes`` → ``XPlane.event_metadata``/``stat_metadata``).
    A name that two metadata entries give different phases maps to ""."""
    out: dict = {}
    for f, plane in _fields(raw):
        if f != 1:                              # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for g, v in _fields(raw, *plane):
            if g == 2:                          # XPlane.name
                name = _text(raw, v)
            elif g == 4:                        # event_metadata entry
                events.append(v)
            elif g == 5:                        # stat_metadata entry
                for k, e in _fields(raw, *v):
                    if k == 2:                  # XStatMetadata
                        sid = sname = None
                        for h, w in _fields(raw, *e):
                            if h == 1:
                                sid = w
                            elif h == 2:
                                sname = _text(raw, w)
                        stats[sid] = sname
        if not trace._CHIP_PLANE.match(name):
            continue
        tf_op = {k for k, n in stats.items() if n == "tf_op"}
        for entry in events:
            for k, e in _fields(raw, *entry):
                if k != 2:                      # the XEventMetadata
                    continue
                ev_name, op = "", ""
                for h, w in _fields(raw, *e):
                    if h == 2:
                        ev_name = _text(raw, w)
                    elif h == 5:                # XStat
                        sid, val = None, ""
                        for j, x in _fields(raw, *w):
                            if j == 1:
                                sid = x
                            elif j == 5:        # str_value
                                val = _text(raw, x)
                            elif j == 7:        # ref_value
                                val = stats.get(x, "")
                        if sid in tf_op:
                            op = val
                ph = phase_of(op)
                out[ev_name] = ph if out.get(ev_name, ph) == ph else ""
    return out


def program_spans(profile, window: tuple) -> tuple:
    """((name, start, end, {attribute: value}), ...): the program's host
    spans that overlap ``window`` (ns), by start, an outer span before
    the spans it holds."""
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.end_ns)
                if ev.name.startswith(PROGRAM_PREFIX) and \
                        e > window[0] and s < window[1]:
                    out.append((ev.name, s, e, dict(ev.stats)))
    return tuple(sorted(out, key=lambda x: (x[1], -x[2])))


class Recorded(NamedTuple):
    """What a trace holds of the program's own instrumentation."""
    phases: dict        # {chip event name: phase path}
    spans: tuple        # program_spans(...)


def read_xplane(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def _window(profile):
    for plane in profile.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW:
                        return int(ev.start_ns), int(ev.end_ns)
    return None


def recorded(tr: trace.Trace, root: str | None = None):
    """``Recorded`` of the trace file under ``root`` (default
    ``TRACE_ROOT``, searched newest first) whose ``window`` span is
    ``tr``'s; None where no file there is ``tr``'s."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(root or TRACE_ROOT, "**",
                                   "*.xplane.pb*"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        raw = read_xplane(path)
        profile = ProfileData.from_serialized_xspace(raw)
        if _window(profile) == tuple(tr.window):
            return Recorded(op_phases(raw), program_spans(profile,
                                                          tr.window))
    return None


def device_phases(tr: trace.Trace, phases: dict, n: int | None = 20
                  ) -> list:
    """[[phase, seconds]] of the ``n`` phase paths with the most device
    self time (``None``: every one), ``UNSCOPED`` for the operations
    outside every phase; ``phases`` as ``op_phases`` gives them."""
    of = _phase_by_text(phases)
    ops = [o._replace(name=of(o.text) or UNSCOPED) for o in tr.ops]
    return trace.top_ops(tr._replace(ops=ops), n)


def _phase_by_text(phases: dict):
    """``text → phase`` for an ``Op.text``: the event's name (an HLO
    instruction, spaces and all) followed by its string stats, so the
    phase is that of the longest event name the text starts with."""
    by_head: dict = {}
    for name, ph in phases.items():
        by_head.setdefault(name.split(" ", 1)[0], []).append((name, ph))
    memo: dict = {}

    def of(text: str) -> str:
        if text not in memo:
            best, ph = "", ""
            for name, p in by_head.get(text.split(" ", 1)[0], ()):
                if len(name) > len(best) and (
                        text == name or text.startswith(name + " ")):
                    best, ph = name, p
            memo[text] = ph
        return memo[text]
    return of


def _innermost(spans) -> list:
    """[(name, start, end)]: disjoint pieces of the spans' union, each
    named after the innermost span covering it. ``spans`` are
    ``(name, start, end, ...)`` by start, nesting as one thread's do."""
    out, stack, t = [], [], 0      # stack: [name, end] of the open spans
    for name, s, e, *_ in spans:
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if t < end:
                out.append((top, t, end))
                t = end
        if stack and t < s:
            out.append((stack[-1][0], t, s))
        t = max(t, s)
        stack.append([name, e])
    while stack:
        top, end = stack.pop()
        if t < end:
            out.append((top, t, end))
            t = end
    return out


def idle_by_program_span(tr: trace.Trace, spans: tuple, n: int | None = 10
                         ) -> list:
    """[[span, seconds]]: the window's device-idle time, attributed to the
    innermost program span covering it (``other`` where none does),
    largest first."""
    return trace.idle_by_span(tr._replace(spans=_innermost(spans)), n)


def served_in_window(counters: dict):
    """The scheduler's reports (``repro.core.schedule.SERVED``) of the
    window's requests: the newest ones whose queries add up to the
    window's ``queries``, checked against its ``wide_rows``. None where
    the program keeps no such log or the log does not hold the window."""
    from repro.core import schedule
    log = getattr(schedule, "SERVED", None)
    n = counters.get("queries", 0)
    if log is None or not n:
        return None
    out, q = [], 0
    for rep in reversed(log):
        if q >= n:
            break
        out.append(rep)
        q += rep.n_queries
    if q != n or sum(r.n_reserved for r in out) != counters.get("wide_rows"):
        return None
    return out[::-1]


def breakdown(path: str) -> dict:
    """The phase and program-span breakdowns of the trace file ``path``."""
    from jax.profiler import ProfileData
    raw = read_xplane(path)
    profile = ProfileData.from_serialized_xspace(raw)
    tr = trace.from_profile(profile)
    spans = program_spans(profile, tr.window)
    counts: dict = {}
    for name, *_ in spans:
        counts[name] = counts.get(name, 0) + 1
    return {"device_phases": device_phases(tr, op_phases(raw), n=None),
            "idle_gaps_program": idle_by_program_span(tr, spans, n=None),
            "program_spans": counts,
            "requests": len({a.get("request") for name, _, _, a in spans
                             if name == "serve.request"})}


if __name__ == "__main__":
    print(json.dumps(breakdown(sys.argv[1])))
