"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* Device ops: the events of each ``/device:...`` plane's ``XLA Ops``
  line, as ``(start_ns, end_ns, name)``. A TPU trace names an op by its
  whole HLO instruction; :func:`op_name` keeps its name, opcode and
  shapes without layouts or called computations.
* Program executions: the events of each device plane's ``XLA Modules``
  line, one per call of a compiled program.
* Host spans: the benchmark's own ``jax.profiler.TraceAnnotation``
  spans (names starting ``bench.``). The profiler puts them on the
  device's clock only to about a millisecond (device ops of a call were
  seen to start 1.2 ms before the host span that dispatched it), so a
  call's device time is taken inside its own program execution, matched
  to the calls in order, never by cutting device time at host spans.
* Busy time is the union of a device's op intervals; idle is the rest
  of the window. Per-op time is self time (an op's duration less the
  ops nested in it on the same line). Collective ops are recognised by
  their HLO names.

``tests/test_bench_tracefile.py`` checks this against a trace recorded on a
v5e chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^%?(all-to-all|all-reduce|all-gather|collective-permute|"
    r"reduce-scatter|ragged-all-to-all|collective-broadcast)")


LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """``%fusion.45 = s32[89039128] fusion(s32[4194304] %copy-done, ...)``
    from the HLO text a TPU trace gives as an op's name."""
    return LAYOUT.sub("", hlo).split(", kind=")[0].split(", calls=")[0] \
        .split(", condition=")[0][:200]


@dataclass
class Trace:
    # device plane name -> [(start_ns, end_ns, op name)], sorted by start
    ops: dict = field(default_factory=dict)
    # device plane name -> [(start_ns, end_ns, program name)], sorted
    modules: dict = field(default_factory=dict)
    # [(start_ns, end_ns, span name without the prefix)]
    spans: list = field(default_factory=list)

    def span_intervals(self, name: str) -> list:
        return [(lo, hi) for lo, hi, n in self.spans if n == name]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    name = op_name if line.name == OPS_LINE else str
                    evs = [(e.start_ns, e.start_ns + e.duration_ns,
                            name(e.name)) for e in line.events]
                    if evs:
                        table = out.ops if line.name == OPS_LINE \
                            else out.modules
                        table.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns,
                     e.name[len(SPAN_PREFIX):])
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in list(out.ops.values()) + list(out.modules.values()):
        evs.sort()
    out.spans.sort()
    return out


def merge(intervals) -> list:
    """Union of ``(lo, hi)`` intervals, as sorted disjoint intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [tuple(x) for x in out]


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(trace: Trace, windows) -> dict:
    """Per device, the time inside ``windows`` in which an op ran;
    ``windows`` is one list for every device, or a dict of lists by
    device."""
    def w(dev):
        return merge(windows[dev] if isinstance(windows, dict) else windows)
    return {dev: overlap(merge((s, e) for s, e, _ in evs), w(dev))
            for dev, evs in trace.ops.items()}


def call_windows(trace: Trace, kind: str, kinds=("write", "read")):
    """Per device, the program executions of the calls of ``kind``: the
    device's executions matched in order to the benchmark's call spans
    of ``kinds``. A device that ran another number of programs than
    there were calls is an error: no call's device time can be told."""
    order = [k for _, k in sorted((lo, k) for k in kinds
                                  for lo, _ in trace.span_intervals(k))]
    if set(trace.modules) != set(trace.ops):
        raise ValueError(f"devices with ops {sorted(trace.ops)} and with "
                         f"program executions {sorted(trace.modules)}")
    out = {}
    for dev, mods in trace.modules.items():
        if len(mods) != len(order):
            raise ValueError(
                f"{dev} ran {len(mods)} programs in the window for "
                f"{len(order)} calls ({', '.join(order)}): "
                f"{sorted({n for _, _, n in mods})}")
        out[dev] = [(s, e) for (s, e, _), k in zip(mods, order) if k == kind]
    return out


def self_ns(events) -> dict:
    """Self time per op name over ``events`` (sorted by start): each
    op's duration less that of the ops nested inside it."""
    recs: dict = {}
    open_: list = []   # [start, end, child time] of enclosing ops
    for s, e, name in events:
        while open_ and open_[-1][1] <= s:
            open_.pop()
        if open_:
            open_[-1][2] += min(e, open_[-1][1]) - s
        rec = [s, e, 0.0]
        open_.append(rec)
        recs.setdefault(name, []).append(rec)
    return {name: sum(r[1] - r[0] - r[2] for r in rs)
            for name, rs in recs.items()}


def collective_ns(trace: Trace, windows: dict) -> dict:
    """Per device, the time inside its ``windows[device]`` of
    collective ops."""
    return {dev: overlap(merge((s, e) for s, e, n in evs
                               if COLLECTIVE.match(n)), merge(windows[dev]))
            for dev, evs in trace.ops.items()}


def idle_gaps(trace: Trace, window, top: int = 10) -> list:
    """The ``top`` longest idle gaps of any device inside ``window``,
    each as ``[label, seconds]``: the label is the innermost benchmark
    span that covers the gap's middle (``"none"`` where none does)."""
    lo, hi = window
    gaps = []
    for evs in trace.ops.values():
        t = lo
        for s, e in merge((s, e) for s, e, _ in evs):
            if s > t:
                gaps.append((t, min(s, hi)))
            t = max(t, e)
            if t >= hi:
                break
        if t < hi:
            gaps.append((t, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2
        cover = [(e - s, n) for s, e, n in trace.spans
                 if n != "window" and s <= mid <= e]
        out.append([min(cover)[1] if cover else "none", (g1 - g0) / 1e9])
    return out
