"""The benchmark's own spans: a recording wrapper around the device
provider, and the names its spans carry into the profiler's trace.

The wrapper is the only thing the benchmark puts inside the timed call.
It sits where the node's pipeline hands rows to the device provider
(under ``PipelinedVerifier``, whose threads make the calls), notes when
each call started and ended and keeps the per-row verdicts it handed
back — the answer where it is produced — so that ``seam_host_ms`` (entry
time minus provider time) and the row comparison both read what the
timed path did, not a second pass. Calls are charged to the one request
that is open: with one caller that is exact; several callers would need
a request id carried through the pipeline, which the program lacks.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import List, Optional

import numpy as np

REQUEST_SPAN = "pb.request"
PROVIDER_SPAN = "pb.provider.call"


class RequestRecord:
    """What one request left behind."""

    __slots__ = ("index", "pool_index", "rows", "t0", "t1", "provider_s", "calls", "row_ok", "outcome")

    def __init__(self, index: int, pool_index: int, rows: int):
        self.index, self.pool_index, self.rows = index, pool_index, rows
        self.t0 = self.t1 = 0.0
        self.provider_s = 0.0
        self.calls = 0
        self.row_ok: List[np.ndarray] = []
        self.outcome: Optional[BaseException] = None


class PlainRecorder:
    """Times a request and nothing else: for a system that has no device
    provider to wrap (the control, a test's dummy)."""

    @contextlib.contextmanager
    def request(self, rec: RequestRecord):
        rec.t0 = time.perf_counter()
        try:
            yield rec
        finally:
            rec.t1 = time.perf_counter()


def serve(entry, i: int) -> RequestRecord:
    """Request ``i`` of ``entry``, start to verdict: a fresh object made
    before the clock starts, the call inside the recorder's span, and the
    exception — which IS the verdict, compared after the window — kept."""
    request, rec = entry.fresh_request(i)
    with entry.recorder.request(rec):
        try:
            entry.call(request)
        except Exception as e:
            rec.outcome = e
    return rec


class RecordingProvider(PlainRecorder):
    """Wraps the device provider. Every batch call is timed into the
    open request and its verdict array kept; everything else passes
    through untouched."""

    def __init__(self, inner, annotate: bool = False):
        self.inner = inner
        self.name = inner.name
        self._rec: Optional[RequestRecord] = None
        self._lock = threading.Lock()
        self._annotate = annotate

    def __getattr__(self, name):
        return getattr(self.inner, name)

    @contextlib.contextmanager
    def request(self, rec: RequestRecord):
        """The span of one request: provider calls made while it is open
        are charged to ``rec``."""
        self._rec = rec
        try:
            with _annotation(REQUEST_SPAN, self._annotate), super().request(rec):
                yield rec
        finally:
            self._rec = None

    def _timed(self, method: str, args, kwargs):
        with _annotation(PROVIDER_SPAN, self._annotate):
            t0 = time.perf_counter()
            out = getattr(self.inner, method)(*args, **kwargs)
            t1 = time.perf_counter()
        with self._lock:
            rec = self._rec
            if rec is not None:
                rec.provider_s += t1 - t0
                rec.calls += 1
                if out is not None:
                    rec.row_ok.append(np.array(out, dtype=bool))
        return out

    def verify_batch(self, *a, **kw):
        return self._timed("verify_batch", a, kw)

    def verify_rows_cached(self, *a, **kw):
        return self._timed("verify_rows_cached", a, kw)

    def verify_rows_cached_templated(self, *a, **kw):
        return self._timed("verify_rows_cached_templated", a, kw)

    def verify_commit_batch(self, pubkeys, msgs, sigs, powers, counted):
        ok = np.asarray(self.verify_batch(pubkeys, msgs, sigs))
        return ok, int(np.asarray(powers)[ok & np.asarray(counted, dtype=bool)].sum())


def _annotation(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
