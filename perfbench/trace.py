"""From the profiler's ``.xplane.pb`` to what the per-layer readers take:
device busy and idle time, device time and launches per XLA module, the
operations that took most time, and the idle gaps by what the host was
in. One implementation for every cell; ``reduce`` works on plain tuples
so that a test can hand it a trace built by hand.

A device plane is ``/device:TPU:<n>``. Its ``XLA Modules`` line has one
event per launch of a compiled program, named ``<module>(<fingerprint>)``;
its ``XLA Ops`` line has one per operation inside them. The benchmark's
own spans (``perfbench.spans``) are ``TraceAnnotation`` events on the
host plane, so they are on the profiler's clock like the device events.
The traced window runs from the first traced request's start to the last
one's end; device events are clipped to it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

from perfbench.spans import PROVIDER_SPAN, REQUEST_SPAN

Event = Tuple[str, float, float]  # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0  # union of device-op intervals, averaged over the chips used
    chips: int = 0
    requests: int = 0  # whole requests inside the traced window
    module_s: Dict[str, float] = field(default_factory=dict)  # summed over chips
    module_runs: Dict[str, int] = field(default_factory=dict)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)  # [module/op, s], at most 10
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)  # [host state, s], at most 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str):
    """(device planes, host spans) as plain tuples from a profile file."""
    from jax.profiler import ProfileData

    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = [
                        (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name in (REQUEST_SPAN, PROVIDER_SPAN)
                )
    return devices, spans


def union_s(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Seconds covered by the union of [start_ns, end_ns) intervals, and
    the merged intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, [(s, e) for s, e in merged]


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%fusion.7 = s32[...] fusion(...)``: keep what stands before `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce(devices: Dict[str, Dict[str, Sequence[Event]]], spans: Sequence[Event]) -> Reduced:
    requests = sorted((s, e) for name, s, e in spans if name == REQUEST_SPAN)
    if not requests or not devices:
        return Reduced()
    w0, w1 = requests[0][0], max(e for _, e in requests)
    out = Reduced(window_s=(w1 - w0) / 1e9, chips=len(devices), requests=len(requests))
    module_s, module_runs = defaultdict(float), defaultdict(int)
    op_s, gap_s = defaultdict(float), defaultdict(float)
    provider = sorted((s, e) for name, s, e in spans if name == PROVIDER_SPAN)
    host = _HostState(requests, provider)
    for lines in devices.values():
        modules = sorted(
            (s, e, module_name(n)) for n, s, e in lines.get(MODULES_LINE, ()) if s >= w0 and s < w1
        )
        for s, e, name in modules:
            module_s[name] += (min(e, w1) - s) / 1e9
            module_runs[name] += 1
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in lines.get(OPS_LINE, ()) if e > w0 and s < w1]
        starts = [m[0] for m in modules]
        for n, s, e in ops:
            k = bisect.bisect_right(starts, s) - 1
            owner = modules[k][2] if k >= 0 and s < modules[k][1] else "?"
            op_s[f"{owner}/{n}"] += (e - s) / 1e9
        busy = ops if ops else [(n, s, min(e, w1)) for s, e, n in modules]
        covered, merged = union_s((s, e) for _, s, e in busy)
        out.busy_s += covered / len(devices)
        edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            for p0, p1 in host.pieces(g0, g1):
                gap_s[host.at((p0 + p1) / 2)] += (p1 - p0) / 1e9 / len(devices)
    out.module_s, out.module_runs = dict(module_s), dict(module_runs)
    out.device_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    out.idle_gaps = sorted(gap_s.items(), key=lambda kv: -kv[1])[:10]
    return out


class _HostState:
    """What the host was in at a time: inside a provider call; inside a
    request before its first provider call (``seam.pack``) or after its
    last (``seam.replay``); or between requests."""

    def __init__(self, requests, provider):
        self.requests, self.provider = requests, provider
        self.r_starts = [s for s, _ in requests]
        self.p_starts = [s for s, _ in provider]
        self.edges = sorted({x for span in requests + provider for x in span})

    def pieces(self, g0: float, g1: float):
        """[g0, g1) cut where the host's state changes."""
        lo, hi = bisect.bisect_right(self.edges, g0), bisect.bisect_left(self.edges, g1)
        cuts = [g0] + self.edges[lo:hi] + [g1]
        return [(a, b) for a, b in zip(cuts, cuts[1:]) if b > a]

    def at(self, t: float) -> str:
        k = bisect.bisect_right(self.r_starts, t) - 1
        if k < 0 or t >= self.requests[k][1]:
            return "between_requests"
        r0, r1 = self.requests[k]
        j = bisect.bisect_right(self.p_starts, t) - 1
        if j >= 0 and t < self.provider[j][1]:
            return "provider.call"
        first = bisect.bisect_left(self.p_starts, r0)
        if first < len(self.provider) and self.provider[first][0] < r1 and t < self.provider[first][0]:
            return "seam.pack"
        return "seam.replay"
