"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, per-program and per-operation device time,
the harness's host spans with the device time inside them, and the idle
gaps named by what the host was doing.

Device planes are ``/device:TPU:<n>``: their ``XLA Ops`` line holds one
event per operation run, their ``XLA Modules`` line one per program run.
Host spans are the ``jax.profiler.TraceAnnotation`` events the harness
writes; host and device events share one clock in the trace.  The window
is the stretch the harness's spans cover, from the first one's start to the
last one's end.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # seconds


@dataclasses.dataclass
class DeviceStats:
    name: str
    busy_s: float
    modules: Dict[str, Tuple[int, float]]   # name -> (runs, seconds)
    ops: Dict[str, float]                   # "module/op" -> seconds
    gaps: List[Tuple[str, float]]           # (host span, seconds)


@dataclasses.dataclass
class SpanStats:
    count: int
    seconds: float
    busy_s: float                # device 0's busy time inside the spans


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: List[DeviceStats]
    spans: Dict[str, SpanStats]

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_time(self, pattern: str, device: int = 0
                    ) -> Tuple[int, float]:
        """(runs, seconds) of the programs on ``device`` whose name matches
        the regular expression ``pattern``."""
        rx = re.compile(pattern)
        runs, secs = 0, 0.0
        for name, (n, s) in self.devices[device].modules.items():
            if rx.search(name):
                runs += n
                secs += s
        return runs, secs


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(disjoint: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the disjoint intervals cover."""
    return sum(b - a for a, b in clip(disjoint, lo, hi))


def gaps(disjoint: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that the disjoint intervals leave free."""
    out, t = [], lo
    for a, b in clip(disjoint, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _module_name(name: str) -> str:
    """``jit_fn(6759284402428304343)`` -> ``jit_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.153 = f32[...] fusion(...)`` -> ``fusion.153``."""
    return name.split(" = ", 1)[0].lstrip("%")


# control flow whose event spans the operations of its body
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def _owner(starts: Sequence[float], items: Sequence[Tuple[float, float, str]],
           t: float, default: str) -> str:
    """The name of the last of ``items`` (sorted by start, not overlapping)
    that started at or before ``t``, if it still runs at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < items[i][1]:
        return items[i][2]
    return default


def reduce(path: str, span_names: Iterable[str]) -> Reduced:
    """Reduce the trace at ``path`` over the window that the host spans
    named ``span_names`` cover."""
    from jax.profiler import ProfileData

    keep = set(span_names)
    pd = ProfileData.from_file(path)
    raw_devices = []
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             _module_name(e.name)) for e in line.events]
            raw_devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, e.name)
                             for e in line.events if e.name in keep)
    if not raw_devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if not spans:
        raise ValueError(f"{path}: none of the spans {sorted(keep)}")
    raw_devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    spans.sort()
    span_starts = [a for a, _, _ in spans]
    lo = spans[0][0]
    hi = max(b for _, b, _ in spans)

    devices = []
    for name, ops, mods in raw_devices:
        busy = union(clip([(a, b) for a, b, _ in ops], lo, hi))
        modules: Dict[str, Tuple[int, float]] = {}
        for a, b, mname in mods:
            if lo <= a < hi:
                n, s = modules.get(mname, (0, 0.0))
                modules[mname] = (n + 1, s + min(b, hi) - a)
        optime: Dict[str, float] = {}
        mods = sorted(mods)
        mod_starts = [a for a, _, _ in mods]
        for a, b, oname in ops:
            if not (lo <= a < hi):
                continue
            op = _op_name(oname)
            if _CONTAINER.match(op):
                continue
            key = f"{_owner(mod_starts, mods, a, '?')}/{op}"
            optime[key] = optime.get(key, 0.0) + min(b, hi) - a
        named = [(_owner(span_starts, spans, (a + b) / 2, "outside spans"),
                  b - a) for a, b in gaps(busy, lo, hi)]
        devices.append(DeviceStats(name, sum(b - a for a, b in busy),
                                   modules, optime, named))

    busy0 = union(clip([(a, b) for a, b, _ in raw_devices[0][1]], lo, hi))
    stats: Dict[str, SpanStats] = {}
    for a, b, name in spans:
        st = stats.setdefault(name, SpanStats(0, 0.0, 0.0))
        st.count += 1
        st.seconds += b - a
        st.busy_s += covered(busy0, a, b)
    return Reduced(hi - lo, devices, stats)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle gaps,
    over all devices, for the result line."""
    ops: Dict[str, float] = {}
    for d in red.devices:
        for k, s in d.ops.items():
            ops[k] = ops.get(k, 0.0) + s
    gap_list = [g for d in red.devices for g in d.gaps]
    return {
        "device_ops": [[k, s] for k, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, s] for k, s in
                      sorted(gap_list, key=lambda kv: -kv[1])[:top]],
    }
