"""Reduce a profiler trace of the measured window to device numbers.

The harness traces the window with ``jax.profiler`` and wraps the window
in a host span ``window`` and each call into a layer in a host span
(``SPANS``). This module reads the resulting XSpace:

* device operations: the events of each chip plane's ``XLA Ops`` line,
  clipped to the window;
* busy time: the union of those intervals, averaged over the chips;
* kernel time: the summed device time of the operations that name a
  kernel (its Pallas ``name``), in the event name or any string stat;
* idle gaps: the window's time with no device operation, attributed to
  the host span that covers it (``other`` where none does).
"""
from __future__ import annotations

import gzip
import re
from typing import NamedTuple

WINDOW = "window"
# host spans the harness writes around each call into a layer
SPANS = ("schedule", "narrow_step", "to_host", "wide_step", "merge",
         "insert", "repack")
OPS_LINE = "XLA Ops"
_CHIP_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
_HOST_PLANE = "/host:CPU"
# Pallas kernels of the serving path, named as their ``pallas_call``s are
KERNELS = ("forest_infer", "traverse_compact_sliced", "traverse_compact",
           "mlp_infer", "mlp_union", "leaf_refine", "knn_browse",
           "spatial_key", "delta_probe")


class Op(NamedTuple):
    name: str       # the label: a kernel's name, else the event's name
    start: int      # ns
    end: int        # ns
    text: str       # event name and string stats, for kernel matching


class Trace(NamedTuple):
    window: tuple   # (start, end) ns of the ``window`` span
    ops: list       # [Op] on every chip, clipped to the window
    spans: list     # [(name, start, end)] host spans of ``SPANS``
    n_chips: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _label(text: str, name: str) -> str:
    """A kernel's name; else the HLO instruction's name and its first
    array shape (``while.35 s32[512,4096]``)."""
    for k in KERNELS:
        if re.search(rf"\b{k}\b", text):
            return k
    head, _, rest = name.partition(" = ")
    shape = re.search(r"[a-z]+\d*\[\d+(?:,\d+)*\]", rest)
    return head.lstrip("%") + (" " + shape.group(0) if shape else "")


def from_profile(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` → ``Trace``. Raises if the trace
    holds no ``window`` span or no chip plane."""
    window, spans, raw, chips = None, [], [], 0
    for plane in profile.planes:
        if plane.name == _HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (int(ev.start_ns), int(ev.end_ns))
                    elif ev.name in SPANS:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
        elif _CHIP_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            chips += 1
            for ev in lines[OPS_LINE].events:
                strs = [str(v) for _, v in ev.stats if isinstance(v, str)]
                text = " ".join([ev.name] + strs)
                raw.append((_label(text, ev.name), int(ev.start_ns),
                            int(ev.end_ns), text))
    if window is None:
        raise ValueError("trace holds no 'window' span")
    if chips == 0:
        raise ValueError("trace holds no chip plane with an "
                         f"'{OPS_LINE}' line")
    lo, hi = window
    ops = [Op(n, max(s, lo), min(e, hi), t) for n, s, e, t in raw
           if e > lo and s < hi]
    return Trace(window=window, ops=ops,
                 spans=sorted(spans, key=lambda x: x[1]), n_chips=chips)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (gzipped where it ends in ``.gz``)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return from_profile(ProfileData.from_serialized_xspace(f.read()))


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    total = sum(e - s for s, e in _union((o.start, o.end) for o in tr.ops))
    return total * 1e-9 / tr.n_chips if tr.n_chips > 1 else total * 1e-9


def kernel_s(tr: Trace, kernel: str) -> float:
    """Summed device seconds of the operations that name ``kernel``."""
    pat = re.compile(rf"\b{re.escape(kernel)}\b")
    return sum(o.end - o.start for o in tr.ops if pat.search(o.text)) * 1e-9


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[label, seconds]] of the ``n`` labels with the most device self
    time: an operation's time less that of the operations nested in it
    (a while loop's body runs inside the loop's own event)."""
    acc: dict = {}
    stack = []      # [label, end, self ns] of the enclosing operations
    for o in sorted(tr.ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][1] <= o.start:
            lab, _, own = stack.pop()
            acc[lab] = acc.get(lab, 0) + own
        if stack:
            stack[-1][2] -= min(o.end, stack[-1][1]) - o.start
        stack.append([o.name, o.end, o.end - o.start])
    for lab, _, own in stack:
        acc[lab] = acc.get(lab, 0) + own
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_by_span(tr: Trace, n: int = 10) -> list:
    """[[span, seconds]]: the window's device-idle time, attributed to the
    host span covering it, largest first. On several chips an instant
    counts as idle only where no chip is busy."""
    busy = _union((o.start, o.end) for o in tr.ops)
    gaps, t = [], tr.window[0]
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < tr.window[1]:
        gaps.append((t, tr.window[1]))
    acc: dict = {}
    spans, j = tr.spans, 0      # host spans follow one another in time
    for gs, ge in gaps:
        covered = 0
        while j < len(spans) and spans[j][2] <= gs:
            j += 1
        for name, s, e in spans[j:]:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                acc[name] = acc.get(name, 0) + ov
                covered += ov
        if ge - gs > covered:
            acc["other"] = acc.get("other", 0) + (ge - gs - covered)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]
