"""Flight-recorder span tracing for the consensus hot path.

The node is a pipeline of overlapping host/device stages (consensus
step machine, pipelined verify dispatch, device merkle engine, WAL,
mempool, RPC) with per-module counters but no way to attribute WHERE a
slow height actually went. This module is the attribution layer: a
lock-protected, bounded ring buffer ``Tracer`` recording nested spans

    with tracer.span("pipeline.execute", kind="batch", rows=n):
        ...

and instant events, exportable as Chrome trace-event JSON (load the
``dump_trace`` RPC output straight into https://ui.perfetto.dev or
chrome://tracing) and as a per-height timeline summary
(``trace_timeline`` RPC). See docs/tracing.md for the span taxonomy.

Design constraints, in order:

- **Near-zero cost disabled.** The module-level ``span()``/``instant()``
  helpers check one flag and return a shared no-op context manager
  before touching anything else — no timestamp read, no string
  formatting, no allocation beyond the caller's kwargs dict. Call sites
  therefore never need their own ``if tracing:`` guard.
- **Bounded.** The ring holds ``buffer_events`` events; the oldest are
  evicted (counted in ``dropped``) — a tracer left on for a week is a
  window over the recent past, never an OOM.
- **Thread-safe.** Spans originate from the event loop, the pipeline's
  dispatch/exec threads, and background compile threads; the ring is
  lock-protected and span nesting is tracked per-thread.

The global tracer is wired from config (``trace_enabled``,
``trace_buffer_events``) at node construction; ``TM_TRACE=0``/``1`` is
the ops kill switch overriding config without editing toml.

**The profiler sink.** While a device profile is being taken
(``jax.profiler.start_trace`` .. ``stop_trace``), ``profiler_sink(True)``
makes every span also a ``jax.profiler.TraceAnnotation``: a host event
in the same ``.xplane.pb`` as the device's ``XLA Ops``, on the same
clock, so each device-idle gap lines up with the host step that held
the chip. The sink is independent of the ring (``enabled``): with the
ring off a span is then the annotation alone. With both off ``span()``
is still one flag check (``active``).
"""

from __future__ import annotations

import os
import threading
import time
import zlib
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

DEFAULT_BUFFER_EVENTS = 65536

_PID = os.getpid()


@dataclass
class OriginContext:
    """Cross-node trace origin: who emitted a gossip message, from which
    span, at what wall-clock time. Carried as a TOLERANT trailer on the
    consensus/mempool gossip envelopes (the ``ResponseCheckTx.priority``
    append-and-tolerate precedent: old decoders ignore trailing bytes,
    new decoders default to "absent" on anything short or malformed), so
    a traced node interoperates with untraced and older peers byte-for-
    byte. ``span_id`` keys the Chrome flow event pair ("s" at the sender
    inside its propose/vote span, "f" at the receiver inside the span
    the message caused) that makes a proposer's propose span visibly
    flow into its peers' vote spans in a merged perfetto view
    (docs/tracing.md, cross-node propagation)."""

    node_id: str = ""
    span_id: int = 0
    height: int = 0
    round: int = 0
    ts_ns: int = 0  # sender wall clock (time_ns) at emission

    def encode(self, w) -> None:
        """Append onto a codec.binary.Writer (duck-typed so this module
        stays dependency-free)."""
        w.write_str(self.node_id)
        w.write_uvarint(self.span_id)
        w.write_u64(max(self.height, 0))
        w.write_i64(self.round)
        w.write_u64(max(self.ts_ns, 0))

    @classmethod
    def decode(cls, r) -> Optional["OriginContext"]:
        """Tolerant read from a codec.binary.Reader: None (never a
        raise) on truncated/malformed bytes — a byzantine trailer must
        cost the sender its trace link, not the receiver its peer."""
        try:
            return cls(
                node_id=r.read_str(max_len=256),
                span_id=r.read_uvarint(),
                height=r.read_u64(),
                round=r.read_i64(),
                ts_ns=r.read_u64(),
            )
        except Exception:
            return None


class _NoopSpan:
    """Shared do-nothing span: what call sites get while tracing is off
    (and what makes instrumentation free to leave in the hot path)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _Annotated:
    """A span while the profiler sink is on and the ring is off: the
    profiler annotation alone (``set`` keeps nothing; the ring does)."""

    __slots__ = ("_ann",)

    def __init__(self, annotation):
        self._ann = annotation

    def __enter__(self) -> "_Annotated":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(None, None, None)
        return False

    def set(self, **args) -> None:
        pass


# per-thread span stack for nesting attribution
_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _Span:
    """One live span. Records a Chrome 'X' (complete) event on exit, and
    is a profiler annotation too while the tracer's sink is on."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_tid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._ann = None

    def set(self, **args) -> None:
        """Attach/overwrite args after entry (e.g. a routing outcome
        known only mid-span)."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        self._tid = threading.get_ident()
        st = _stack()
        if st:
            # parent attribution is best-effort: concurrent asyncio tasks
            # interleave on one thread, so only the NAME is recorded
            self.args.setdefault("parent", st[-1].name)
        st.append(self)
        annotation = self._tracer._annotation
        if annotation is not None:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        else:  # interleaved async exit order: remove by identity
            try:
                st.remove(self)
            except ValueError:
                pass
        self._tracer._record("X", self.name, self._t0, dur, self._tid, self.args)
        return False


class Tracer:
    """Bounded, lock-protected ring buffer of trace events."""

    def __init__(
        self,
        buffer_events: int = DEFAULT_BUFFER_EVENTS,
        enabled: bool = True,
        node_id: str = "",
    ):
        # the annotation class while the profiler sink is on, else None
        self._annotation = None
        self.enabled = bool(enabled)
        self._cap = max(int(buffer_events), 1)
        self._ring: "deque[tuple]" = deque()
        self._lock = threading.Lock()
        self._origin_ns = time.perf_counter_ns()
        # wall-clock anchor so exported timestamps can be correlated
        # with log lines (perf_counter has an arbitrary epoch) and so
        # merge_chrome_traces can rebase multiple nodes onto one axis
        self._origin_unix_ns = time.time_ns()
        self.recorded = 0
        self.dropped = 0
        self._thread_names: Dict[int, str] = {}
        # span-id source for flow events (see set_node_id)
        self._span_seq = 0
        self.set_node_id(node_id)

    def set_node_id(self, node_id: str) -> None:
        """Cross-node trace identity: stamps exported traces
        (process_name row in perfetto) and every OriginContext this
        tracer emits; "" = anonymous single-node tracing. Also derives
        the flow-id salt — the high bits of every span id carry a node
        fingerprint so ids from different nodes never collide in a
        merged trace; the low bits are a per-tracer counter. The ONE
        place the salt formula lives (configure() reuses it)."""
        self.node_id = str(node_id)
        self._span_salt = (zlib.crc32(self.node_id.encode()) & 0xFFFFFFFF) << 20

    # -- switches ------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """Whether spans and instants are recorded into the ring."""
        return self._recording

    @enabled.setter
    def enabled(self, on: bool) -> None:
        self._recording = bool(on)
        self.active = self._recording or self._annotation is not None

    def set_profiler_sink(self, on: bool) -> None:
        """Make every span also a ``jax.profiler.TraceAnnotation`` (on)
        or stop (off). Goes with starting and stopping a device profile:
        outside one an annotation records nothing and costs ~1 µs."""
        if on:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        else:
            self._annotation = None
        self.active = self._recording or self._annotation is not None

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **args):
        """Context manager timing a stage. Returns a shared no-op while
        neither the ring nor the profiler sink is on."""
        if not self.active:
            return NOOP_SPAN
        return self._open(name, args)

    def _open(self, name: str, args: Dict[str, Any]):
        if not self._recording:  # the profiler sink alone
            annotation = self._annotation  # another thread may switch it off
            return NOOP_SPAN if annotation is None else _Annotated(annotation(name))
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker event."""
        if not self.enabled:
            return
        self._record(
            "i", name, time.perf_counter_ns(), 0, threading.get_ident(), args
        )

    # -- cross-node flow linking -------------------------------------------

    def next_span_id(self) -> int:
        """Process/node-unique id for a flow-event pair."""
        with self._lock:
            self._span_seq += 1
            return self._span_salt | (self._span_seq & 0xFFFFF)

    def flow_start(self, name: str, flow_id: int, **args) -> None:
        """Chrome flow START ("s"): perfetto draws an arrow from the
        enclosing slice to wherever the matching flow_end lands. Record
        INSIDE the span the work originates from (the proposer's
        propose span, a voter's prevote span)."""
        if not self.enabled:
            return
        args["flow"] = int(flow_id)
        self._record(
            "s", name, time.perf_counter_ns(), 0, threading.get_ident(), args
        )

    def flow_end(self, name: str, flow_id: int, **args) -> None:
        """Chrome flow END ("f", bp="e"): the receiving side of a link.
        Record inside the span the message CAUSED (a peer's vote span)."""
        if not self.enabled:
            return
        args["flow"] = int(flow_id)
        self._record(
            "f", name, time.perf_counter_ns(), 0, threading.get_ident(), args
        )

    def origin(self, height: int = 0, round_: int = 0) -> Optional[OriginContext]:
        """An OriginContext for an outgoing gossip message, with the
        flow-start half of its link already recorded. None while
        disabled — senders then attach nothing and the wire stays
        byte-identical to the untraced encoding."""
        if not self.enabled:
            return None
        sid = self.next_span_id()
        self.flow_start("gossip.origin", sid, height=height, round=round_)
        return OriginContext(
            node_id=self.node_id,
            span_id=sid,
            height=height,
            round=round_,
            ts_ns=time.time_ns(),
        )

    def link(self, ctx: Optional[OriginContext], name: str, **args) -> None:
        """Record the receiving half of a cross-node link: a flow-end
        carrying the origin's node id and the gossip propagation delay
        (receiver wall clock minus sender stamp; meaningful to clock
        skew, exact in the in-process harness)."""
        if ctx is None or not self.enabled:
            return
        if ctx.node_id:
            args.setdefault("origin_node", ctx.node_id)
        if ctx.ts_ns:
            args.setdefault(
                "gossip_ms", round((time.time_ns() - ctx.ts_ns) / 1e6, 3)
            )
        self.flow_end(name, ctx.span_id, **args)

    def _record(
        self, ph: str, name: str, t0_ns: int, dur_ns: int, tid: int, args: dict
    ) -> None:
        with self._lock:
            if tid not in self._thread_names:
                # current_thread() is the caller's own thread; cheap
                self._thread_names[tid] = threading.current_thread().name
            if len(self._ring) >= self._cap:
                self._ring.popleft()
                self.dropped += 1
            self._ring.append((ph, name, t0_ns, dur_ns, tid, args))
            self.recorded += 1

    # -- management --------------------------------------------------------

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def set_capacity(self, buffer_events: int) -> None:
        with self._lock:
            self._cap = max(int(buffer_events), 1)
            while len(self._ring) > self._cap:
                self._ring.popleft()
                self.dropped += 1

    def stats(self) -> Dict[str, float]:
        """Counters for the ``tendermint_trace_*`` metric family."""
        with self._lock:
            return {
                "enabled": 1 if self.enabled else 0,
                "events_recorded": self.recorded,
                "events_dropped": self.dropped,
                "buffer_events": len(self._ring),
                "buffer_capacity": self._cap,
            }

    def _snapshot(self) -> List[tuple]:
        with self._lock:
            return list(self._ring)

    # -- export ------------------------------------------------------------

    def export_chrome(self, limit: Optional[int] = None) -> Dict[str, Any]:
        """Chrome trace-event document (perfetto / chrome://tracing).
        Spans are 'X' complete events; instants are 'i'; thread-name
        metadata rides 'M' events. Timestamps are microseconds since
        the tracer's origin. ``limit`` keeps only the newest N events
        (a full 64k ring renders to ~10MB of JSON)."""
        events: List[Dict[str, Any]] = []
        with self._lock:
            names = dict(self._thread_names)
            ring = list(self._ring)
        if limit is not None and limit >= 0:
            # explicit slice for 0: ring[-0:] is the FULL list
            ring = ring[-limit:] if limit > 0 else []
        if self.node_id:
            events.append(
                {
                    "ph": "M", "name": "process_name", "pid": _PID, "tid": 0,
                    "args": {"name": self.node_id},
                }
            )
        for tid, tname in sorted(names.items()):
            events.append(
                {
                    "ph": "M", "name": "thread_name", "pid": _PID, "tid": tid,
                    "args": {"name": tname},
                }
            )
        for ph, name, t0_ns, dur_ns, tid, args in ring:
            ev: Dict[str, Any] = {
                "ph": ph,
                "name": name,
                "pid": _PID,
                "tid": tid,
                "ts": (t0_ns - self._origin_ns) / 1000.0,
            }
            if ph == "X":
                ev["dur"] = dur_ns / 1000.0
            if ph == "i":
                ev["s"] = "t"  # thread-scoped instant
            if args:
                if ph in ("s", "f"):
                    # flow events: the pair-matching id is a top-level
                    # field, not an arg (Chrome trace format); "f" binds
                    # to the enclosing slice via bp="e"
                    args = dict(args)
                    ev["id"] = args.pop("flow", 0)
                    ev["cat"] = "gossip"
                    if ph == "f":
                        ev["bp"] = "e"
                ev["args"] = args
            elif ph in ("s", "f"):
                ev["id"] = 0
                ev["cat"] = "gossip"
                if ph == "f":
                    ev["bp"] = "e"
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "origin_unix_ns": self._origin_unix_ns,
                "dropped_events": self.dropped,
                "node_id": self.node_id,
            },
        }

    def timeline(self, height: Optional[int] = None) -> Dict[str, Any]:
        """Per-height latency attribution: spans carrying a ``height``
        arg grouped by height then span name, plus a cross-height
        per-stage aggregate over EVERY span in the buffer. All
        durations in milliseconds."""
        per_height: Dict[int, Dict[str, Any]] = {}
        stages: Dict[str, Dict[str, float]] = {}
        for ph, name, t0_ns, dur_ns, tid, args in self._snapshot():
            if ph != "X":
                continue
            dur_ms = dur_ns / 1e6
            agg = stages.setdefault(
                name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0}
            )
            agg["count"] += 1
            agg["total_ms"] += dur_ms
            agg["max_ms"] = max(agg["max_ms"], dur_ms)
            h = args.get("height")
            if not isinstance(h, int) or (height is not None and h != height):
                continue
            hrec = per_height.setdefault(
                h, {"first_ts_ns": t0_ns, "last_ts_ns": t0_ns + dur_ns, "stages": {}}
            )
            hrec["first_ts_ns"] = min(hrec["first_ts_ns"], t0_ns)
            hrec["last_ts_ns"] = max(hrec["last_ts_ns"], t0_ns + dur_ns)
            srec = hrec["stages"].setdefault(
                name, {"count": 0, "total_ms": 0.0, "max_ms": 0.0}
            )
            srec["count"] += 1
            srec["total_ms"] += dur_ms
            srec["max_ms"] = max(srec["max_ms"], dur_ms)
        heights = []
        for h in sorted(per_height):
            rec = per_height[h]
            heights.append(
                {
                    "height": h,
                    "wall_ms": round((rec["last_ts_ns"] - rec["first_ts_ns"]) / 1e6, 3),
                    "stages": {
                        k: {
                            "count": v["count"],
                            "total_ms": round(v["total_ms"], 3),
                            "max_ms": round(v["max_ms"], 3),
                        }
                        for k, v in sorted(rec["stages"].items())
                    },
                }
            )
        return {
            "heights": heights,
            "stages": {
                k: {
                    "count": v["count"],
                    "total_ms": round(v["total_ms"], 3),
                    "max_ms": round(v["max_ms"], 3),
                    "avg_ms": round(v["total_ms"] / v["count"], 4) if v["count"] else 0,
                }
                for k, v in sorted(stages.items())
            },
        }


def merge_chrome_traces(docs: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge per-node Chrome trace documents into ONE perfetto-loadable
    document: each input becomes its own process row (pid = input
    index + 1, process_name from the tracer's node_id) and every
    timestamp is rebased onto the earliest node's clock via the
    ``origin_unix_ns`` wall-clock anchor — so a proposer's propose span
    and the vote spans it caused on other nodes line up on one time
    axis, with the flow-event pairs (shared ``id``) drawn as arrows
    between them. Flow ids are node-salted at allocation
    (``next_span_id``), so no rewriting is needed here."""
    anchors = [
        int(d.get("otherData", {}).get("origin_unix_ns", 0) or 0) for d in docs
    ]
    base = min((a for a in anchors if a), default=0)
    events: List[Dict[str, Any]] = []
    dropped = 0
    for i, doc in enumerate(docs):
        pid = i + 1
        other = doc.get("otherData", {})
        dropped += int(other.get("dropped_events", 0) or 0)
        shift_us = ((anchors[i] - base) / 1000.0) if anchors[i] and base else 0.0
        node = other.get("node_id") or f"node{i}"
        seen_process_name = False
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                seen_process_name = True
            events.append(ev)
        if not seen_process_name:
            events.append(
                {
                    "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                    "args": {"name": node},
                }
            )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"origin_unix_ns": base, "dropped_events": dropped},
    }


# -- global tracer ----------------------------------------------------------
#
# One process-wide tracer (like the crypto provider and merkle engine
# seams): every subsystem records into the same ring so the exported
# trace interleaves consensus steps with the device work they caused.

def _env_enabled(default: bool) -> bool:
    """TM_TRACE=0 force-disables, TM_TRACE=1 force-enables (ops kill
    switch; mirrors TM_MERKLE_DEVICE / TM_CRYPTO_PROVIDER). Allowlist
    for ON: an unrecognized spelling (off/disabled/typo) must fail
    SAFE — disabled — never force-enable hot-path recording."""
    v = os.environ.get("TM_TRACE")
    if v is None or v == "":
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


_tracer = Tracer(enabled=_env_enabled(False))


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(t: Tracer) -> Tracer:
    """Install a specific tracer (tests/bench); bypasses the TM_TRACE
    override on purpose."""
    global _tracer
    _tracer = t
    return t


def configure(
    enabled: Optional[bool] = None,
    buffer_events: Optional[int] = None,
    node_id: Optional[str] = None,
) -> Tracer:
    """Apply config to the global tracer (node wiring). ``TM_TRACE``
    overrides ``enabled``. ``node_id`` is the cross-node trace identity
    stamped on exported documents and every OriginContext this process
    emits."""
    if buffer_events is not None:
        _tracer.set_capacity(buffer_events)
    if enabled is not None:
        _tracer.enabled = _env_enabled(bool(enabled))
    if node_id is not None:
        _tracer.set_node_id(node_id)
    return _tracer


def enabled() -> bool:
    return _tracer.enabled


def profiler_sink(on: bool) -> None:
    """Mirror the global tracer's spans into a device profile: call with
    True right after ``jax.profiler.start_trace`` and with False right
    before ``stop_trace`` (``utils/prof.py``'s profiler route does)."""
    _tracer.set_profiler_sink(on)


def span(name: str, **args):
    """``with trace.span("stage", height=h):`` — the hot-path entry
    point. One flag check while the ring and the profiler sink are off."""
    t = _tracer
    if not t.active:
        return NOOP_SPAN
    return t._open(name, args)


def instant(name: str, **args) -> None:
    t = _tracer
    if t.enabled:
        t._record(
            "i", name, time.perf_counter_ns(), 0, threading.get_ident(), args
        )
