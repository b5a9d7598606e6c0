"""The program's own spans beside the device's events: what each host
step of the verify path costs a traced request, and every device-idle
instant of the traced window charged to the step that held the chip.

The program records its spans through ``tendermint_tpu.utils.trace``;
while its profiler sink is on (``trace.profiler_sink``) each span is
also a ``TraceAnnotation`` on the profile's host plane, on the clock of
the device's ``XLA Ops``. ``perfbench.attribute`` turns the sink on
over the traced window and reduces the profile here. This module adds
to ``perfbench.trace`` and changes nothing it computes: ``reduce`` takes
the same devices and benchmark spans and returns the same fields, with
the program's on top. Like ``perfbench.trace.reduce`` it works on plain
tuples, so a test can hand it a trace built by hand.

Idle time is charged instant by instant:

1. inside a request, to the work span, open on any host thread, that
   started last;
2. where no work span is open, to the wait span that is open
   (``WAIT_SPANS``; the one that started last);
3. where neither is open, to ``unattributed``;
4. outside requests, to ``between_requests``.

``idle_by_span`` so partitions exactly the idle time that
``idle_gaps`` partitions.
"""

from __future__ import annotations

import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Hashable, List, Sequence, Tuple

from perfbench import trace
from perfbench.spans import REQUEST_SPAN

# host work: the verify seam, the pipeline's threads, the model's launches
WORK_SPANS = (
    "verify.columns", "verify.pack", "verify.links", "verify.replay",
    "pipeline.submit", "pipeline.prep", "pipeline.resolve",
    "launch.plan", "launch.stage", "launch.dispatch",
    "tables.build", "tables.slab",
)
# the host blocked: on the pipeline's hand-off, on the device's verdicts
WAIT_SPANS = ("pipeline.wait", "launch.readback")
# around work spans of their own; charged only where no child is open
ENVELOPE_SPANS = ("pipeline.execute", "generic.launch")
# the names read from a profile: anything else on the host plane is ignored
PROGRAM_SPANS = WORK_SPANS + WAIT_SPANS + ENVELOPE_SPANS
# the pipeline's own threads: a wait with none of these open is a hand-off
PIPELINE_WORK = ("pipeline.prep", "pipeline.execute", "pipeline.resolve")

UNATTRIBUTED, BETWEEN = "unattributed", "between_requests"

Span = Tuple[str, float, float, Hashable]  # name, start_ns, end_ns, host thread


@dataclass
class Attributed(trace.Reduced):
    program_spans: int = 0  # program spans inside the traced window
    span_s: Dict[str, float] = field(default_factory=dict)  # s a request, own time, over threads
    idle_by_span: Dict[str, float] = field(default_factory=dict)  # s, averaged over the chips
    hop_s: float = 0.0  # s a request in pipeline.wait with no pipeline thread working
    request_p50_ms: float = 0.0  # median traced request, from the benchmark's span


def read_program_spans(path: str) -> List[Span]:
    """The program's spans of a profile file, each with its host thread
    (a line of a host plane)."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns, (p, k))
                    for e in line.events
                    if e.name in PROGRAM_SPANS
                )
    return spans


def reduce(devices, bench_spans: Sequence[trace.Event], program: Sequence[Span]) -> Attributed:
    base = trace.reduce(devices, bench_spans)
    out = Attributed(**vars(base))
    requests = sorted((s, e) for name, s, e in bench_spans if name == REQUEST_SPAN)
    if not requests or not devices:
        return out
    w0, w1 = requests[0][0], max(e for _, e in requests)
    out.request_p50_ms = statistics.median((e - s) / 1e6 for s, e in requests)
    program = [(n, max(s, w0), min(e, w1), t) for n, s, e, t in program if e > w0 and s < w1]
    if not program:
        return out
    out.program_spans = len(program)
    per = 1e9 * len(requests)
    out.span_s = {n: s / per for n, s in own_time(program).items()}
    out.hop_s = hop_ns(program) / per
    segments = charged_segments(requests, program, w0, w1)
    idle = defaultdict(float)
    for lines in devices.values():
        ops = [(max(s, w0), min(e, w1)) for _, s, e in lines.get(trace.OPS_LINE, ()) if e > w0 and s < w1]
        if not ops:
            ops = [(s, min(e, w1)) for _, s, e in lines.get(trace.MODULES_LINE, ()) if s >= w0 and s < w1]
        _, merged = trace.union_s(ops)
        for label, ns in charge(gaps(merged, w0, w1), segments):
            idle[label] += ns / 1e9 / len(devices)
    out.idle_by_span = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    return out


def own_time(program: Sequence[Span]) -> Dict[str, float]:
    """ns a span name holds its thread as the innermost open span (the
    one that started last), summed over threads: a parent's time less
    its children's, so nothing is counted twice."""
    by_thread = defaultdict(list)
    for span in program:
        by_thread[span[3]].append(span)
    out = defaultdict(float)
    for spans in by_thread.values():
        for a, b, top in _sweep(spans):
            if top is not None:
                out[top[0]] += b - a
    return dict(out)


def hop_ns(program: Sequence[Span]) -> float:
    """ns inside a pipeline.wait with no pipeline.prep, .execute or
    .resolve open on any thread: the threads handing a request on."""
    waits = trace.union_s((s, e) for n, s, e, _ in program if n == "pipeline.wait")[1]
    busy = trace.union_s((s, e) for n, s, e, _ in program if n in PIPELINE_WORK)[1]
    pieces = charge(waits, [(s, e, "busy") for s, e in busy], rest="hop")
    return sum(ns for label, ns in pieces if label == "hop")


def charged_segments(requests, program: Sequence[Span], w0: float, w1: float):
    """[(start, end, label)] tiling [w0, w1) by what idle time there is
    charged to."""
    out = []
    r_edges = sorted({x for r in requests for x in r})
    for a, b, top in _sweep(program, extra_edges=r_edges, w0=w0, w1=w1, rank=_rank):
        mid = (a + b) / 2
        if not any(s <= mid < e for s, e in requests):
            label = BETWEEN
        else:
            label = UNATTRIBUTED if top is None else top[0]
        out.append((a, b, label))
    return out


def _rank(span: Span):
    """The order in which an open span claims an instant: any work span
    over any wait, then the latest started; envelopes never."""
    if span[0] in WORK_SPANS:
        return (2,) + _innermost(span)
    if span[0] in WAIT_SPANS:
        return (1,) + _innermost(span)
    return None


def _innermost(span: Span):
    """Latest started first; of two that started together (a parent and
    its child clipped to the window's start), the one that ends first."""
    return (span[1], -span[2])


def _sweep(spans: Sequence[Span], extra_edges=(), w0=None, w1=None, rank=_innermost):
    """Consecutive [a, b) between span edges, each with the open span of
    highest rank (None where none ranks)."""
    edges = sorted({x for s in spans for x in s[1:3]} | set(extra_edges))
    if w0 is not None:
        edges = [w0] + [x for x in edges if w0 < x < w1] + [w1]
    starts = sorted(spans, key=lambda s: s[1])
    open_: List[Span] = []
    j = 0
    for a, b in zip(edges, edges[1:]):
        while j < len(starts) and starts[j][1] <= a:
            open_.append(starts[j])
            j += 1
        open_ = [s for s in open_ if s[2] > a]
        ranked = [(r, s) for s in open_ for r in [rank(s)] if r is not None]
        yield a, b, (max(ranked, key=lambda rs: rs[0])[1] if ranked else None)


def gaps(merged: Sequence[Tuple[float, float]], w0: float, w1: float) -> List[Tuple[float, float]]:
    """The complement of merged busy intervals within [w0, w1)."""
    edges = [w0] + [x for s, e in merged for x in (s, e)] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def charge(intervals, segments, rest: str = UNATTRIBUTED):
    """(label, ns) of each piece of ``intervals`` under the labelled
    ``segments`` (sorted, disjoint); a piece under none gets ``rest``."""
    out = []
    k = 0
    for a, b in intervals:
        t = a
        while k < len(segments) and segments[k][1] <= t:
            k += 1
        j = k
        while t < b:
            if j < len(segments) and segments[j][0] <= t:
                end = min(b, segments[j][1])
                out.append((segments[j][2], end - t))
                t = end
                if segments[j][1] <= t:
                    j += 1
            else:
                end = min(b, segments[j][0]) if j < len(segments) else b
                out.append((rest, end - t))
                t = end
    return out
