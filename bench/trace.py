"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to, per device, the intervals in which an operation
ran ("XLA Ops" line of each ``/device:TPU:<n>`` plane), each named by its
HLO op name, and to the benchmark's own host spans (``bench.*``
``TraceAnnotation``s on the host plane).  The program's kernels carry no
``name=``: their custom calls are named after the jitted functions that
launch them (``project_packed``, ``reconstruct_apply_packed[_workers]``).
Everything below works on plain (start, end) intervals in seconds, so it
is tested on hand-built traces.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float          # seconds
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: dict         # device index -> [Op] sorted by start
    spans: list           # host spans [Op] (bench.* annotations)
    window: tuple         # (start, end) seconds: the measured window

    def clipped(self) -> "Trace":
        """Ops and spans cut to the window."""
        lo, hi = self.window

        def cut(ops):
            return [dataclasses.replace(o, start=max(o.start, lo),
                                        end=min(o.end, hi))
                    for o in ops if o.end > lo and o.start < hi]

        return Trace({d: cut(o) for d, o in self.devices.items()},
                     cut(self.spans), self.window)


def load(path: str, window_span: str = "bench.window") -> Trace:
    """Read the newest ``*.xplane.pb`` under ``path``: each device's
    "XLA Ops" line, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {path}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append(Op(op_name(e.name), e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
            devices[int(m.group(1))] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Op(e.name, e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9))
    wins = [s for s in spans if s.name == window_span]
    if not wins:
        raise ValueError(f"trace has no {window_span!r} span")
    return Trace(devices, spans, (wins[-1].start, wins[-1].end)).clipped()


def op_name(event_name: str) -> str:
    """HLO op name of a device event ("%fusion.3 = f32[...] fusion(...)"
    -> "fusion.3"); a custom call of a jitted kernel is named after the
    jitted function ("project_packed.1")."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def union(intervals) -> list:
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
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


def spans_of(ops) -> list:
    return union((o.start, o.end) for o in ops)


def matching(ops, pattern) -> list:
    """Ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [o for o in ops if rx.search(o.name)]


def busy_s(ops) -> float:
    """Seconds in which some operation ran (union of op intervals)."""
    return length(spans_of(ops))


def kernel_s(ops, pattern) -> float:
    """Device time of the operations whose name matches ``pattern``."""
    return length(spans_of(matching(ops, pattern)))


def collectives(ops) -> list:
    return [o for o in ops if COLLECTIVE.search(o.name)]


def exposed_s(ops) -> float:
    """Collective time during which no other operation runs."""
    coll = collectives(ops)
    rest = [o for o in ops if not COLLECTIVE.search(o.name)]
    c = spans_of(coll)
    return length(c) - overlap(c, spans_of(rest))


def busy_outside_s(ops, pattern) -> float:
    """Busy time not covered by ops matching ``pattern`` or collectives."""
    rx = re.compile(pattern)
    excl = [o for o in ops if rx.search(o.name) or COLLECTIVE.search(o.name)]
    rest = [o for o in ops if o not in set(excl)]
    r = spans_of(rest)
    return length(r) - overlap(r, spans_of(excl))


def idle_gaps(ops, spans, window, top: int = 10) -> list:
    """The longest gaps between device operations inside ``window``,
    each named by the host span (other than the window's) that covers
    most of it: [[name, seconds]]."""
    lo, hi = window
    busy = spans_of(ops)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host = [sp for sp in spans if sp.name != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = 0.0, "none"
        for sp in host:
            ov = min(e, sp.end) - max(s, sp.start)
            if ov > best:
                best, name = ov, sp.name
        out.append([name, e - s])
    return out


def top_ops(ops, top: int = 10) -> list:
    """Device operations that took most time, grouped by name with any
    numeric suffix dropped: [[name, seconds]]."""
    tot: dict = {}
    for o in ops:
        key = re.sub(r"[.\d]+$", "", o.name) or o.name
        tot[key] = tot.get(key, 0.0) + o.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]
