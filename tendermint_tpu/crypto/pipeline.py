"""Pipelined verification dispatch: async micro-batching over a BatchVerifier.

The device path (crypto/batch.py -> models/verifier.py) is fast per
CALL, but every call site blocks on its own device round trip: the
fast-sync reactors alternate verify/apply serially, and vote ingest
pays a dispatch per drain even when several drains race. On an
earlier, remotely attached chip (not re-measured) the overlapped
device rate ran ~5x faster than back-to-back synchronous calls
(tabled_pipelined_ms 26.29 vs tabled_p50_ms 123.97; record removed in
PR 22) because a synchronous caller leaves the device idle during host
prep and result readback.

``PipelinedVerifier`` closes that gap without touching the kernels:

- callers SUBMIT work and get a Future; a dispatch thread micro-batches
  whatever is queued into one device-sized bucket (same-shape requests
  concatenate into a single provider call, commit specs group into one
  cross-height ``verify_commits_batched`` call);
- the pipeline is DOUBLE-BUFFERED: the dispatch thread does host prep
  (row packing, dedupe hashing, template stacking) for bundle N+1 while
  a second thread executes bundle N on the device — the bounded
  handoff queue (depth 1) is the second buffer;
- a bounded LRU ``SigCache`` keyed by digest(pubkey, sign bytes, sig)
  makes gossip redelivery free: rows whose exact triple already
  verified successfully resolve without a device round trip, both
  across submissions and WITHIN one bundle (two peers delivering the
  same commit concurrently verify its rows once). Only successful
  verifies are ever cached, so a failed signature can never poison the
  cache, and the signature bytes are part of the key, so a hit can
  never mask a row that differs only in its sig.

The wrapper is itself a BatchVerifier, so it drops into
``set_default_provider`` and every existing call site
(ValidatorSet.verify_commit, VoteSet ingest, the light client) routes
through the shared dispatch queue unchanged — a single gossiped vote
and a 10k-row bulk ingest land in the same jit bucket. Counters
(queue depth, batch occupancy, cache hits) are exposed via ``stats()``
and surfaced as ``tendermint_crypto_*`` metrics (docs/metrics.md);
``stop()`` drains the queue and joins the threads so node shutdown is
clean.
"""

from __future__ import annotations

import hashlib
import queue
import threading
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Dict, List, Optional

import time

import numpy as np

from tendermint_tpu.crypto.batch import (
    GENERIC_COUNTS, H2D_COUNTS, SEAM_COUNTS, TABLE_COUNTS, TABLED_COUNTS, BatchVerifier,
    CPUBatchVerifier,
)
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils import trace

# Most rows the grouper coalesces into one bundle; matches the verifier
# model's streaming window (models/verifier.py MAX_DEVICE_ROWS) so that
# coalescing never forces the windowed path. A single item past it is a
# bundle of its own and streams as windows and a tail (a DeliverBatch of
# a full block's SigCache misses does).
MAX_BUNDLE_ROWS = 16384

# Template stacking cap per bundle (mirrors vote_set's byzantine-flood
# cap): beyond this, templated groups stop coalescing rather than grow
# an unbounded template upload.
MAX_BUNDLE_TEMPLATES = 512


class SigCache:
    """Bounded LRU of digests of (pubkey, sign bytes, signature) triples
    that verified SUCCESSFULLY — the gossip dedupe cache.

    Thread-safe. ``capacity=0`` disables caching entirely (every lookup
    misses, nothing is stored). Only genuinely-verified triples may be
    inserted (callers enforce it; the pipeline only inserts rows whose
    device verdict was True), which is what makes a hit equivalent to
    re-verifying: same bytes, same deterministic answer.
    """

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = int(capacity)
        self._od: "OrderedDict[bytes, None]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0

    @staticmethod
    def key(pubkey: bytes, sign_bytes: bytes, sig: bytes) -> bytes:
        """Digest of one (pubkey, sign bytes, sig) triple. All three
        components are hashed with length framing so no two distinct
        triples can collide by concatenation."""
        h = hashlib.sha256()
        h.update(len(pubkey).to_bytes(2, "big"))
        h.update(pubkey)
        h.update(len(sign_bytes).to_bytes(4, "big"))
        h.update(sign_bytes)
        h.update(len(sig).to_bytes(2, "big"))
        h.update(sig)
        return h.digest()

    @staticmethod
    def key_templated(pubkey: bytes, template: bytes, ts8: bytes, sig: bytes) -> bytes:
        """Key for the templated sign-bytes form (codec/signbytes.py):
        (template, ts8) uniquely determines the materialized sign bytes
        — the timestamp splice is deterministic — so hashing the parts
        avoids materializing 160 bytes per row on the hot ingest path.
        NOTE: this is a distinct keyspace from ``key`` (same triple,
        different digest); each call site must use one form
        consistently, which they do (vote ingest is always templated)."""
        h = hashlib.sha256()
        h.update(len(pubkey).to_bytes(2, "big"))
        h.update(pubkey)
        h.update(b"tpl")
        h.update(len(template).to_bytes(4, "big"))
        h.update(template)
        h.update(ts8)
        h.update(len(sig).to_bytes(2, "big"))
        h.update(sig)
        return h.digest()

    def seen(self, key: bytes) -> bool:
        with self._lock:
            if key in self._od:
                self._od.move_to_end(key)
                self.hits += 1
                return True
            self.misses += 1
            return False

    def add(self, key: bytes) -> None:
        if self.capacity <= 0:
            return
        with self._lock:
            if key in self._od:
                self._od.move_to_end(key)
                return
            self._od[key] = None
            self.insertions += 1
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)
                self.evictions += 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "size": len(self._od),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "insertions": self.insertions,
                "evictions": self.evictions,
            }


_default_cache: Optional[SigCache] = None
_default_cache_lock = threading.Lock()


def default_sig_cache() -> SigCache:
    """Process-wide dedupe cache: gossip redelivers the same vote into
    different VoteSets (rounds, catch-up replays), so the cache must
    outlive any one set."""
    global _default_cache
    with _default_cache_lock:
        if _default_cache is None:
            _default_cache = SigCache()
        return _default_cache


def set_default_sig_cache(c: Optional[SigCache]) -> None:
    global _default_cache
    with _default_cache_lock:
        _default_cache = c


def cached_verify(pub_key, msg: bytes, sig: bytes, cache: Optional[SigCache] = None) -> bool:
    """Host-verify one signature with the shared SigCache in front.

    The single-signature analog of the pipeline's dedupe path, for call
    sites that verify inline on the event loop (the consensus proposal
    check): gossip redelivery — or, in the simulator, the same proposal
    fanned out to hundreds of in-process nodes — costs one hash instead
    of a full scalar-mult verify. Same safety argument as the pipeline:
    only successful verifies are inserted, and the signature bytes are
    part of the key, so a hit is equivalent to re-verifying."""
    c = cache if cache is not None else default_sig_cache()
    k = None
    if c.capacity > 0:
        try:
            raw = pub_key.bytes()
        except Exception:
            raw = None
        if raw is not None:
            k = SigCache.key(raw, msg, sig)
            if c.seen(k):
                return True
    ok = bool(pub_key.verify(msg, sig))
    if ok and k is not None:
        c.add(k)
    return ok


class _Item:
    """One submitted request awaiting dispatch."""

    __slots__ = ("kind", "fut", "n", "data", "t_enq")

    def __init__(self, kind: str, fut: Future, n: int, data: tuple):
        self.kind = kind  # "batch" | "rows" | "tpl" | "commit"
        self.fut = fut
        self.n = n  # row count (1 for commit specs)
        self.data = data
        self.t_enq = time.perf_counter_ns()  # enqueue→dispatch wait (trace)


class _Bundle:
    """Prepped work handed from the dispatch thread to the exec thread."""

    __slots__ = ("kind", "items", "prep")

    def __init__(self, kind: str, items: List[_Item], prep: dict):
        self.kind = kind
        self.items = items
        self.prep = prep


_SENTINEL = object()


class PipelineShutdownError(Exception):
    """The pipeline stopped (or a worker wedged through shutdown) before
    this request was executed."""


def _is_liveness_error(e: Exception) -> bool:
    """Errors meaning 'the pipeline failed this request, not the
    signatures' — the sync interface retries those serially."""
    from concurrent.futures import CancelledError

    from tendermint_tpu.utils.watchdog import FutureDeadlineError

    return isinstance(e, (FutureDeadlineError, PipelineShutdownError, CancelledError))


class PipelinedVerifier(BatchVerifier):
    """Future-based micro-batching front end over ``inner``.

    ``depth`` is advisory for callers that pipeline multi-step work
    (the fast-sync reactors keep ``depth`` commits in flight);
    ``flush_deadline_s`` is how long the dispatcher lingers after the
    first queued item to let concurrent submitters coalesce (0 = only
    the natural coalescing that back-pressure provides: while the
    device executes bundle N, everything submitted meanwhile groups
    into bundle N+1).
    """

    name = "pipelined"

    def __init__(
        self,
        inner: Optional[BatchVerifier] = None,
        *,
        depth: int = 8,
        flush_deadline_s: float = 0.0,
        max_bundle_rows: int = MAX_BUNDLE_ROWS,
        cache: Optional[SigCache] = None,
    ):
        self.inner = inner if inner is not None else CPUBatchVerifier()
        self.name = f"pipelined({self.inner.name})"
        self.depth = int(depth)
        self.flush_deadline_s = float(flush_deadline_s)
        self.max_bundle_rows = int(max_bundle_rows)
        self.cache = cache if cache is not None else default_sig_cache()

        self._q: "deque[_Item]" = deque()
        self._cv = threading.Condition()
        self._stopped = False
        # depth-1 handoff: the second buffer of the double-buffer — the
        # dispatcher preps bundle N+1 while the exec thread runs N, and
        # blocks here (letting the queue accumulate) when both are full
        self._hand: "queue.Queue" = queue.Queue(maxsize=1)

        # counters (under _cv to share the lock with the queue)
        self.submitted_calls = 0
        self.submitted_rows = 0
        self.dispatched_bundles = 0
        self.dispatched_rows = 0
        self.device_rows = 0  # rows that actually reached inner
        self.coalesced_bundles = 0  # bundles that merged >1 request
        self.bundle_dup_rows = 0  # in-bundle duplicate rows collapsed
        self.max_queue_depth = 0
        self._occupancy_sum = 0  # requests per bundle, summed
        # cross-node coalescing telemetry (``sources`` row labels):
        # bundles whose device rows carried labels from >1 node, and the
        # running max of distinct labels in one bundle (both monotonic)
        self.multi_source_bundles = 0
        self.max_bundle_sources = 0
        self.worker_restarts = 0
        self.fallback_serial = 0  # sync callers that timed out + verified serially

        # watchdog integration (attach_watchdog): every submitted future
        # gets a resolution deadline, so a crashed exec thread can never
        # strand a caller — the future fails with FutureDeadlineError
        # and sync paths fall back to a direct inner call.
        self._watchdog = None
        self._deadline_s: Optional[float] = None

        # submit→execute wait distribution (models/telemetry.py): the
        # unified engine-telemetry protocol's queue_wait section, and
        # the "verify-bundle queue+execute" signal the height ledger
        # attributes per height. Observed unconditionally per bundle
        # (one perf_counter read + a bucket increment).
        from tendermint_tpu.models.telemetry import QueueWaitHist

        self.queue_wait = QueueWaitHist()

        # bundle currently executing (or abandoned by a dead exec
        # thread) — what _fail_leftovers resolves that the queues can't
        self._inflight_bundle: Optional[_Bundle] = None
        # set by _fail_leftovers: from then on the dispatch thread must
        # fail any bundle it holds instead of depositing it (nobody
        # will drain the handoff slot again)
        self._leftovers_failed = False

        self._dispatch_t = self._spawn("dispatch")
        self._exec_t = self._spawn("exec")

    def _spawn(self, which: str) -> threading.Thread:
        target = self._dispatch_loop if which == "dispatch" else self._exec_loop
        t = threading.Thread(target=target, daemon=True, name=f"verify-{which}")
        t.start()
        return t

    # -- supervision (utils/watchdog.py wiring) ----------------------------

    def attach_watchdog(self, wd, deadline_s: Optional[float] = None) -> None:
        """Register the dispatch/exec threads for restart-on-death and
        (optionally) put a resolution deadline on every submitted
        future. Liveness treats a stopped pipeline as healthy — its
        threads are SUPPOSED to be gone."""
        self._watchdog = wd
        self._deadline_s = deadline_s
        wd.register_worker(
            "pipeline.dispatch",
            lambda: self._stopped or self._dispatch_t.is_alive(),
            self.restart_workers,
        )
        wd.register_worker(
            "pipeline.exec",
            lambda: self._stopped or self._exec_t.is_alive(),
            self.restart_workers,
        )

    def workers_alive(self) -> bool:
        return self._dispatch_t.is_alive() and self._exec_t.is_alive()

    def restart_workers(self) -> List[str]:
        """Replace dead dispatch/exec threads (watchdog restart hook;
        also callable directly). Work still queued is picked up by the
        replacements; a bundle that died IN the exec thread is lost —
        its futures resolve via the watchdog deadline. Thread-safe and
        idempotent: live threads are left alone."""
        restarted: List[str] = []
        orphan = None
        with self._cv:
            if self._stopped:
                return restarted
            if not self._dispatch_t.is_alive():
                self._dispatch_t = self._spawn("dispatch")
                restarted.append("dispatch")
            if not self._exec_t.is_alive():
                # the bundle the dead thread was holding is unrecoverable
                # work: fail its futures NOW (liveness error -> sync
                # callers re-verify serially) instead of leaving them to
                # the deadline — or to nothing, if none is configured
                orphan = self._inflight_bundle
                self._inflight_bundle = None
                self._exec_t = self._spawn("exec")
                restarted.append("exec")
            self.worker_restarts += len(restarted)
        if orphan is not None:
            err = PipelineShutdownError("exec worker died holding this bundle")
            for it in orphan.items:
                self._resolve(it.fut, exc=err)
        if restarted:
            trace.instant("pipeline.workers_restarted", which=",".join(restarted))
        return restarted

    # -- submit API --------------------------------------------------------

    def submit_batch(
        self, pubkeys, msgs, sigs, msg_lens=None, dedupe: bool = False, sources=None
    ) -> "Future[np.ndarray]":
        """Verify (N,32)/(N,L)/(N,64) rows; resolves to (N,) bool.
        ``dedupe=True`` routes rows through the SigCache (gossip
        redelivery shape: commits/votes that may arrive repeatedly).
        ``sources`` optionally labels each row with the logical node it
        belongs to (the simulator's shared-engine workload): bundles
        whose device rows span >1 source count into
        ``multi_source_bundles`` / ``max_bundle_sources`` — the
        telemetry that proves cross-node traffic actually coalesces."""
        fut: Future = Future()
        n = int(len(pubkeys))
        if n == 0:
            fut.set_result(np.zeros(0, dtype=bool))
            return fut
        pk = np.asarray(pubkeys, dtype=np.uint8)
        mg = np.asarray(msgs, dtype=np.uint8)
        sg = np.asarray(sigs, dtype=np.uint8)
        lens = None if msg_lens is None else np.asarray(msg_lens, dtype=np.int32)
        src = None
        if sources is not None:
            src = tuple(str(s) for s in sources)
            if len(src) != n:
                raise ValueError(f"sources has {len(src)} labels for {n} rows")
        self._enqueue(_Item("batch", fut, n, (pk, mg, sg, lens, bool(dedupe), src)))
        return fut

    def submit_rows(
        self, valset_key: bytes, all_pubkeys, row_idx, msgs, sigs
    ) -> "Future[np.ndarray]":
        """Per-valset cached-table rows (crypto/batch.verify_rows_cached
        shape). Unlike the raw provider method this ALWAYS resolves to a
        result array: when the cached path declines (None), the exec
        thread falls back to the generic batch kernel itself, so callers
        need no fallback of their own."""
        fut: Future = Future()
        n = int(len(row_idx))
        if n == 0:
            fut.set_result(np.zeros(0, dtype=bool))
            return fut
        self._enqueue(
            _Item(
                "rows",
                fut,
                n,
                (
                    bytes(valset_key),
                    all_pubkeys,
                    np.asarray(row_idx, dtype=np.int32),
                    np.asarray(msgs, dtype=np.uint8),
                    np.asarray(sigs, dtype=np.uint8),
                ),
            )
        )
        return fut

    def submit_rows_templated(
        self, valset_key: bytes, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
    ) -> "Future[np.ndarray]":
        """Templated-message rows (one template per BlockID + 8 ts bytes
        per row — codec/signbytes.py layout). Same always-resolves
        contract as submit_rows."""
        fut: Future = Future()
        n = int(len(row_idx))
        if n == 0:
            fut.set_result(np.zeros(0, dtype=bool))
            return fut
        self._enqueue(
            _Item(
                "tpl",
                fut,
                n,
                (
                    bytes(valset_key),
                    all_pubkeys,
                    np.asarray(row_idx, dtype=np.int32),
                    np.asarray(templates, dtype=np.uint8),
                    np.asarray(tmpl_idx, dtype=np.int32),
                    np.asarray(ts8, dtype=np.uint8),
                    np.asarray(sigs, dtype=np.uint8),
                ),
            )
        )
        return fut

    def submit_commit(self, spec) -> "Future[Optional[Exception]]":
        """One CommitVerifySpec (types/validator_set.py); resolves to
        None on acceptance or the exception verify_commit would have
        raised. Concurrent specs — the fast-sync window, the light
        client's bisection chain — group into ONE cross-height
        verify_commits_batched device call."""
        fut: Future = Future()
        self._enqueue(_Item("commit", fut, 1, (spec,)))
        return fut

    def _enqueue(self, item: _Item) -> None:
        with trace.span("pipeline.submit"), self._cv:
            if not self._stopped:
                self._q.append(item)
                self.submitted_calls += 1
                self.submitted_rows += item.n
                self.max_queue_depth = max(self.max_queue_depth, len(self._q))
                self._cv.notify_all()
                if self._watchdog is not None and self._deadline_s is not None:
                    self._watchdog.watch_future(
                        item.fut, self._deadline_s, name=f"pipeline.{item.kind}"
                    )
                return
        # stopped: run inline so teardown races degrade gracefully
        # instead of hanging a caller on a future nobody will resolve
        self._run_bundle(self._prep([item]))

    # -- BatchVerifier interface (sync callers share the queue) ------------
    #
    # A sync caller blocking on .result() must never hang on a wedged
    # pipeline: when a watchdog deadline is configured, a future that
    # fails with a deadline/shutdown error is re-verified SERIALLY
    # against the inner provider — the exact call the caller would have
    # made with the pipeline disabled. Without a watchdog the behavior
    # is unchanged (wait indefinitely, like any Future).

    def _await_or_serial(self, fut: Future, serial):
        try:
            with trace.span("pipeline.wait"):
                return fut.result()
        except Exception as e:
            if not _is_liveness_error(e):
                raise
        with self._cv:
            self.fallback_serial += 1
        trace.instant("pipeline.fallback_serial")
        return serial()

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None) -> np.ndarray:
        return self._await_or_serial(
            self.submit_batch(pubkeys, msgs, sigs, msg_lens=msg_lens),
            lambda: self.inner.verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens),
        )

    def verify_rows_cached(self, valset_key, all_pubkeys, row_idx, msgs, sigs):
        def serial():
            out = None
            f = getattr(self.inner, "verify_rows_cached", None)
            if f is not None:
                out = f(valset_key, all_pubkeys, row_idx, msgs, sigs)
            if out is None:
                pk = np.asarray(all_pubkeys, dtype=np.uint8)[
                    np.asarray(row_idx, dtype=np.int32)
                ]
                out = self.inner.verify_batch(pk, msgs, sigs)
            return np.asarray(out)

        return self._await_or_serial(
            self.submit_rows(valset_key, all_pubkeys, row_idx, msgs, sigs), serial
        )

    def verify_rows_cached_templated(
        self, valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
    ):
        def serial():
            from tendermint_tpu.codec.signbytes import splice_timestamps

            mg = splice_timestamps(
                np.asarray(templates, dtype=np.uint8)[
                    np.asarray(tmpl_idx, dtype=np.int32)
                ],
                np.asarray(ts8, dtype=np.uint8),
            )
            pk = np.asarray(all_pubkeys, dtype=np.uint8)[
                np.asarray(row_idx, dtype=np.int32)
            ]
            return np.asarray(self.inner.verify_batch(pk, mg, sigs))

        return self._await_or_serial(
            self.submit_rows_templated(
                valset_key, all_pubkeys, row_idx, templates, tmpl_idx, ts8, sigs
            ),
            serial,
        )

    # verify_commit_batch: inherited — composes over verify_batch, so
    # the rows go through the shared queue (the host tally is
    # microseconds)

    # -- inner passthroughs -------------------------------------------------

    def warmup(self, *a, **kw):
        f = getattr(self.inner, "warmup", None)
        return f(*a, **kw) if f is not None else None

    def register_valset(self, *a, **kw):
        f = getattr(self.inner, "register_valset", None)
        return f(*a, **kw) if f is not None else None

    @property
    def model(self):
        return getattr(self.inner, "model", None)

    # -- stats / lifecycle --------------------------------------------------

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._q)

    def stats(self) -> Dict[str, float]:
        with self._cv:
            bundles = self.dispatched_bundles
            s = {
                "queue_depth": len(self._q),
                "max_queue_depth": self.max_queue_depth,
                "submitted_calls": self.submitted_calls,
                "submitted_rows": self.submitted_rows,
                "dispatched_bundles": bundles,
                "dispatched_rows": self.dispatched_rows,
                "device_rows": self.device_rows,
                "coalesced_bundles": self.coalesced_bundles,
                "bundle_dup_rows": self.bundle_dup_rows,
                "batch_occupancy_avg": (
                    self._occupancy_sum / bundles if bundles else 0.0
                ),
                "multi_source_bundles": self.multi_source_bundles,
                "max_bundle_sources": self.max_bundle_sources,
                "worker_restarts": self.worker_restarts,
                "fallback_serial": self.fallback_serial,
            }
        for k, v in self.cache.stats().items():
            s[f"cache_{k}"] = v
        s.update(SEAM_COUNTS.snapshot())
        s.update(TABLED_COUNTS.snapshot())
        s.update(TABLE_COUNTS.snapshot())
        s.update(GENERIC_COUNTS.snapshot())
        s.update(H2D_COUNTS.snapshot())
        return s

    def engine_stats(self) -> Dict[str, object]:
        """The unified engine-telemetry protocol (models/telemetry.py):
        bucket compile state comes from the wrapped verifier model's
        executables + per-valset tables; ``device_rows``/``host_rows``
        are the INNER provider's counts of what a device executable
        verified and what its host path served (crypto/batch.RowCounts)
        — a row handed to the provider is not thereby a device row."""
        from tendermint_tpu.models.telemetry import breaker_view, bucket_entry

        row_counts = getattr(self.inner, "row_counts", None)
        device_rows, host_rows = row_counts.snapshot() if row_counts else (0, 0)
        with self._cv:
            counters = {
                "submitted_calls": self.submitted_calls,
                "submitted_rows": self.submitted_rows,
                "dispatched_bundles": self.dispatched_bundles,
                "coalesced_bundles": self.coalesced_bundles,
                "bundle_dup_rows": self.bundle_dup_rows,
                "multi_source_bundles": self.multi_source_bundles,
                "max_bundle_sources": self.max_bundle_sources,
                "fallback_serial": self.fallback_serial,
                "worker_restarts": self.worker_restarts,
            }
            # instantaneous, NOT in counters: the protocol's counters
            # section is monotonic extras (the height ledger diffs it;
            # a draining queue would show up as a negative "delta")
            queue_depth = len(self._q)
        cache = self.cache.stats()
        counters["cache_hits"] = cache["hits"]
        counters["cache_misses"] = cache["misses"]
        # the verify seam packs before it reaches any provider, so its
        # counts are the process's (crypto/batch.SEAM_COUNTS), as are the
        # cached-table path's slot-order / gathered row counts, the key
        # pool's, the generic family's and the launches' H2D bytes
        counters.update(SEAM_COUNTS.snapshot())
        counters.update(TABLED_COUNTS.snapshot())
        counters.update(TABLE_COUNTS.snapshot())
        counters.update(GENERIC_COUNTS.snapshot())
        counters.update(H2D_COUNTS.snapshot())
        buckets: Dict[str, dict] = {}
        breakers: Dict[str, dict] = {}
        model = self.model  # the wrapped VerifierModel (None for CPU inner)
        if model is not None:
            entries = getattr(model, "_entries", None)
            if entries:
                # keys are (kind, n_pad, msg_len) for the plain buckets
                # and (kind, n_pad, msg_len, tpl_pad, table_rows,
                # n_shards) for tabled/templated ones — label by joining
                # whatever arity the model used
                for key, e in dict(entries).items():
                    parts = key if isinstance(key, tuple) else (key,)
                    label = "/".join(str(p) for p in parts)
                    buckets[f"fn:{label}"] = bucket_entry(e)
            tables = getattr(model, "_valset_tables", None)
            if tables:
                for key, e in dict(tables).items():
                    label = key.hex()[:12] if isinstance(key, bytes) else str(key)
                    buckets[f"tables:{label}"] = bucket_entry(e)
            pool = getattr(model, "key_pool", None)
            if pool is not None and (pool.ready or pool.compiling or pool.failed):
                buckets["tables:pool"] = bucket_entry(pool)
            breakers = breaker_view(getattr(model, "tables_breaker", None))
        return {
            "engine": "pipeline",
            "device_rows": float(device_rows),
            "host_rows": float(host_rows),
            "buckets": buckets,
            "breakers": breakers,
            "queue_wait_ms": self.queue_wait.snapshot(),
            "counters": counters,
            "queue_depth": queue_depth,
        }

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Drain and join. With ``drain`` (the node-stop path) every
        already-submitted future completes before the threads exit;
        without, pending futures are cancelled.

        A wedged/dead worker must not turn stop() into a hang for
        CALLERS either: if the joins time out (or a worker died before
        stop), whatever is still queued or handed off is failed with
        PipelineShutdownError so no ``fut.result()`` blocks forever."""
        with self._cv:
            if self._stopped:
                return
            self._stopped = True
            if not drain:
                while self._q:
                    self._q.popleft().fut.cancel()
            self._cv.notify_all()
        self._dispatch_t.join(timeout=timeout)
        self._exec_t.join(timeout=timeout)
        if self._dispatch_t.is_alive() or self._exec_t.is_alive():
            trace.instant(
                "pipeline.stop_wedged",
                dispatch_alive=self._dispatch_t.is_alive(),
                exec_alive=self._exec_t.is_alive(),
            )
        self._fail_leftovers()

    def _fail_leftovers(self) -> None:
        """Resolve every future still reachable after shutdown: the
        submit queue (dispatch never took it) and the handoff slot
        (exec never ran it). Already-resolved futures are skipped by
        _resolve's done() check."""
        err = PipelineShutdownError("verify pipeline stopped before executing request")
        leftovers: List[_Item] = []
        # harvest the in-flight bundle unconditionally: a DEAD exec
        # thread abandoned it, and a wedged-but-alive one (join timed
        # out mid-_run_bundle, e.g. a hung device dispatch) will never
        # finish it either — both ways its callers must not hang.
        # Normal completion cleared the marker; a late resolution from
        # a wedged thread that eventually wakes is swallowed by
        # _resolve's done() check.
        orphan = self._inflight_bundle
        if orphan is not None:
            self._inflight_bundle = None
            leftovers.extend(orphan.items)
        # tmlint: disable=no-permanent-latch -- one-way stop() ordering flag, not a device-path latch: the pipeline is shutting down for good
        self._leftovers_failed = True  # before the drain: see below
        with self._cv:
            while self._q:
                leftovers.append(self._q.popleft())
        # drain the handoff slot — and KEEP draining while the dispatch
        # thread is alive: a dispatcher blocked in put() succeeds the
        # instant the first get frees the slot, re-stranding its bundle
        # where nobody would fail it. Bounded: dispatch also fails its
        # own bundle once it observes _leftovers_failed (set above), so
        # one of the two sides always resolves those futures.
        deadline = time.monotonic() + 2.0
        while True:
            try:
                bundle = self._hand.get_nowait()
            except queue.Empty:
                if not self._dispatch_t.is_alive() or time.monotonic() >= deadline:
                    break
                time.sleep(0.01)
                continue
            if bundle is _SENTINEL:
                continue
            leftovers.extend(bundle.items)
        for it in leftovers:
            self._resolve(it.fut, exc=err)
        # dispatch died before delivering its shutdown sentinel: wake a
        # still-live exec thread so it can exit instead of blocking on
        # the handoff forever
        if self._exec_t.is_alive() and not self._dispatch_t.is_alive():
            try:
                self._hand.put_nowait(_SENTINEL)
            except queue.Full:  # pragma: no cover - race
                pass

    # context-manager sugar for tests/benches
    def __enter__(self) -> "PipelinedVerifier":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- dispatch thread: group + host prep ---------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            # chaos site: a raise HERE (before any item is popped) kills
            # the dispatch thread without losing work — queued items wait
            # for the watchdog to start a replacement
            faults.maybe("pipeline.dispatch")
            with self._cv:
                while not self._q and not self._stopped:
                    self._cv.wait()
                if not self._q and self._stopped:
                    break
                if (
                    self.flush_deadline_s > 0
                    and not self._stopped
                    and self._hand.full()
                ):
                    # optional lingering, ONLY while the exec thread is
                    # busy and the handoff slot is taken — dispatching
                    # couldn't proceed anyway, so the wait costs nothing.
                    # When the pipeline is idle the group is cut
                    # immediately: a lone synchronous caller (a blocked
                    # event loop cannot produce concurrent submitters)
                    # must never pay the flush window as pure latency.
                    import time as _time

                    deadline = _time.monotonic() + self.flush_deadline_s
                    while (
                        not self._stopped
                        and self._hand.full()
                        and sum(i.n for i in self._q) < self.max_bundle_rows
                        and _time.monotonic() < deadline
                    ):
                        self._cv.wait(timeout=deadline - _time.monotonic())
                group = self._take_group_locked()
            try:
                with trace.span(
                    "pipeline.prep",
                    kind=group[0].kind,
                    requests=len(group),
                    rows=sum(i.n for i in group),
                ):
                    bundle = self._prep(group)
            except Exception as e:
                # same invariant as _resolve: a prep failure must fail
                # THIS group's futures, never the dispatch thread — a
                # dead dispatcher would wedge every later verification
                for it in group:
                    self._resolve(it.fut, exc=e)
                continue
            # blocks while exec runs the prior bundle — but never
            # forever: once stop() has failed the leftovers, a deposit
            # would strand these futures in the handoff slot, so fail
            # them here instead
            while True:
                try:
                    self._hand.put(bundle, timeout=0.2)
                    break
                except queue.Full:
                    if self._leftovers_failed:
                        err = PipelineShutdownError(
                            "verify pipeline stopped before executing request"
                        )
                        for it in bundle.items:
                            self._resolve(it.fut, exc=err)
                        break
        try:
            # sentinel only matters to a LIVE exec thread (which drains
            # the slot promptly); don't block on a dead one
            self._hand.put(_SENTINEL, timeout=1.0)
        except queue.Full:  # pragma: no cover - exec dead with full slot
            pass

    def _take_group_locked(self) -> List[_Item]:
        """Pop the maximal leading run of the queue that can share one
        device call: same kind and compatible shapes, bounded by
        max_bundle_rows (always at least one item)."""
        head = self._q.popleft()
        group = [head]
        rows = head.n
        templates = head.data[3].shape[0] if head.kind == "tpl" else 0
        while self._q:
            nxt = self._q[0]
            if nxt.kind != head.kind or rows + nxt.n > self.max_bundle_rows:
                break
            if not self._compatible(head, nxt):
                break
            if head.kind == "tpl":
                t = nxt.data[3].shape[0]
                if templates + t > MAX_BUNDLE_TEMPLATES:
                    break
                templates += t
            group.append(self._q.popleft())
            rows += nxt.n
        return group

    @staticmethod
    def _compatible(a: _Item, b: _Item) -> bool:
        if a.kind == "batch":
            # same row width; ragged (msg_lens) items merge by carrying
            # explicit lengths for every row
            return a.data[1].shape[1] == b.data[1].shape[1]
        if a.kind == "rows":
            return a.data[0] == b.data[0] and a.data[3].shape[1] == b.data[3].shape[1]
        if a.kind == "tpl":
            return a.data[0] == b.data[0] and a.data[3].shape[1] == b.data[3].shape[1]
        return True  # commit specs always group

    def _prep(self, group: List[_Item]) -> _Bundle:
        kind = group[0].kind
        prep: dict = {}
        if kind == "batch":
            pk = np.concatenate([i.data[0] for i in group], axis=0)
            mg = np.concatenate([i.data[1] for i in group], axis=0)
            sg = np.concatenate([i.data[2] for i in group], axis=0)
            if any(i.data[3] is not None for i in group):
                width = mg.shape[1]
                lens = np.concatenate(
                    [
                        i.data[3]
                        if i.data[3] is not None
                        else np.full(i.n, width, dtype=np.int32)
                        for i in group
                    ]
                )
            else:
                lens = None
            prep.update(pk=pk, mg=mg, sg=sg, lens=lens)
            if any(len(i.data) > 5 and i.data[5] is not None for i in group):
                srcs: List[str] = []
                for i in group:
                    row_src = i.data[5] if len(i.data) > 5 else None
                    srcs.extend(row_src if row_src is not None else ("",) * i.n)
                prep["sources"] = srcs
            if any(i.data[4] for i in group):
                self._prep_dedupe(group, prep)
        elif kind == "rows":
            prep.update(
                vkey=group[0].data[0],
                all_pk=group[0].data[1],
                idx=np.concatenate([i.data[2] for i in group]),
                mg=np.concatenate([i.data[3] for i in group], axis=0),
                sg=np.concatenate([i.data[4] for i in group], axis=0),
            )
        elif kind == "tpl":
            # stack each request's templates; per-row template indices
            # offset into the stacked matrix (the verify_commits_batched
            # pattern, types/validator_set.py)
            tpls, idx_parts, off = [], [], 0
            for i in group:
                tpls.append(i.data[3])
                idx_parts.append(i.data[4] + off)
                off += i.data[3].shape[0]
            prep.update(
                vkey=group[0].data[0],
                all_pk=group[0].data[1],
                idx=np.concatenate([i.data[2] for i in group]),
                templates=np.concatenate(tpls, axis=0),
                tmpl_idx=np.concatenate(idx_parts),
                ts8=np.concatenate([i.data[5] for i in group], axis=0),
                sg=np.concatenate([i.data[6] for i in group], axis=0),
            )
        elif kind == "commit":
            prep.update(specs=[i.data[0] for i in group])
        return _Bundle(kind, group, prep)

    def _prep_dedupe(self, group: List[_Item], prep: dict) -> None:
        """Host-side dedupe for a 'batch' bundle: rows whose triple is
        already in the SigCache resolve from it; duplicate rows WITHIN
        the bundle collapse to one device row (concurrent gossip
        deliveries of the same commit). Builds:

        - prep["unique"]: indices (into the concatenated rows) that go
          to the device;
        - prep["remap"]: per-row index into the unique set (-1 = cache
          hit, resolved True);
        - prep["keys"]: per-unique-row cache key, inserted on success.

        This hashing is exactly the host prep the double-buffer exists
        to overlap with device execution of the previous bundle."""
        pk, mg, sg, lens = prep["pk"], prep["mg"], prep["sg"], prep["lens"]
        n = pk.shape[0]
        remap = np.empty(n, dtype=np.int64)
        unique: List[int] = []
        keys: List[bytes] = []
        in_bundle: Dict[bytes, int] = {}
        # rows of non-dedupe items still dispatch, but skip the cache
        dedupe_row = np.zeros(n, dtype=bool)
        off = 0
        for it in group:
            if it.data[4]:
                dedupe_row[off : off + it.n] = True
            off += it.n
        for r in range(n):
            if not dedupe_row[r]:
                remap[r] = len(unique)
                unique.append(r)
                keys.append(b"")
                continue
            m = mg[r] if lens is None else mg[r, : int(lens[r])]
            k = SigCache.key(pk[r].tobytes(), m.tobytes(), sg[r].tobytes())
            prior = in_bundle.get(k)
            if prior is not None:
                remap[r] = prior
                continue
            if self.cache.seen(k):
                remap[r] = -1
                continue
            in_bundle[k] = len(unique)
            remap[r] = len(unique)
            unique.append(r)
            keys.append(k)
        prep["remap"] = remap
        prep["unique"] = np.asarray(unique, dtype=np.int64)
        prep["keys"] = keys
        dups = n - len(unique) - int((remap < 0).sum())
        if dups:
            with self._cv:
                self.bundle_dup_rows += dups

    # -- exec thread: device call + result fan-out ---------------------------

    def _exec_loop(self) -> None:
        while True:
            bundle = self._hand.get()
            if bundle is _SENTINEL:
                break
            # tracked so stop()/restart can reach this bundle's futures
            # if the thread dies mid-execution; cleared ONLY on normal
            # completion — an escaping exception (thread death) must
            # leave the marker for _fail_leftovers/restart_workers
            self._inflight_bundle = bundle
            # chaos site: a raise HERE kills the exec thread WITH a
            # bundle in hand — the harshest pipeline failure. Those
            # futures resolve via the watchdog deadline, restart, or
            # stop(); callers then fall back to serial verify.
            faults.maybe("pipeline.exec")
            self._run_bundle(bundle)
            self._inflight_bundle = None

    @staticmethod
    def _resolve(fut: Future, value=None, exc: Optional[Exception] = None) -> None:
        """Complete a future, tolerating a caller-side cancellation that
        lands between the done() check and the set — e.g. an asyncio
        task awaiting wrap_future() being cancelled at reactor shutdown.
        An InvalidStateError here must never kill the exec thread (that
        would wedge the handoff queue and deadlock every verify)."""
        try:
            if fut.done():
                return
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(value)
        except Exception:
            pass  # cancelled concurrently: nobody is waiting

    def _run_bundle(self, bundle: _Bundle) -> None:
        rows = sum(i.n for i in bundle.items)
        sp = trace.span(
            "pipeline.execute",
            kind=bundle.kind,
            requests=len(bundle.items),
            rows=rows,
        )
        with sp:
            # dispatch-occupancy attribution: how long the oldest
            # request waited from submit to device execution — always
            # observed into the engine-telemetry histogram, attached to
            # the span only while tracing
            now = time.perf_counter_ns()
            wait_ms = (now - min(i.t_enq for i in bundle.items)) / 1e6
            self.queue_wait.observe_ms(wait_ms)
            if sp is not trace.NOOP_SPAN:
                sp.set(queue_wait_ms=round(wait_ms, 3))
                if "remap" in bundle.prep:
                    remap = bundle.prep["remap"]
                    sp.set(
                        cache_hits=int((remap < 0).sum()),
                        device_rows=int(bundle.prep["unique"].size),
                    )
            try:
                ok = self._execute(bundle)
            except Exception as e:
                for it in bundle.items:
                    self._resolve(it.fut, exc=e)
                return
        srcs = bundle.prep.get("sources")
        distinct = ()
        if srcs:
            if "unique" in bundle.prep:
                # only rows that actually reached the device count: a
                # row resolved from the cache is not bundle workload
                distinct = {srcs[int(r)] for r in bundle.prep["unique"]} - {""}
            else:
                distinct = set(srcs) - {""}
        with self._cv:
            self.dispatched_bundles += 1
            self.dispatched_rows += rows
            self._occupancy_sum += len(bundle.items)
            if len(bundle.items) > 1:
                self.coalesced_bundles += 1
            if len(distinct) > 1:
                self.multi_source_bundles += 1
            self.max_bundle_sources = max(self.max_bundle_sources, len(distinct))
        with trace.span("pipeline.resolve", kind=bundle.kind, requests=len(bundle.items)):
            if bundle.kind == "commit":
                for it, res in zip(bundle.items, ok):
                    self._resolve(it.fut, res)
                return
            off = 0
            for it in bundle.items:
                self._resolve(it.fut, np.asarray(ok[off : off + it.n]))
                off += it.n

    def _execute(self, bundle: _Bundle):
        p = bundle.prep
        if bundle.kind == "commit":
            from tendermint_tpu.types.validator_set import verify_commits_batched

            return verify_commits_batched(p["specs"], provider=self.inner)
        if bundle.kind == "batch":
            if "remap" not in p:
                with self._cv:
                    self.device_rows += p["pk"].shape[0]
                return self.inner.verify_batch(
                    p["pk"], p["mg"], p["sg"], msg_lens=p["lens"]
                )
            unique, remap, keys = p["unique"], p["remap"], p["keys"]
            if unique.size:
                with self._cv:
                    self.device_rows += int(unique.size)
                ok_u = np.asarray(
                    self.inner.verify_batch(
                        p["pk"][unique],
                        p["mg"][unique],
                        p["sg"][unique],
                        msg_lens=None if p["lens"] is None else p["lens"][unique],
                    )
                )
                for j in np.nonzero(ok_u)[0]:
                    if keys[j]:
                        self.cache.add(keys[j])
            else:
                ok_u = np.zeros(0, dtype=bool)
            out = np.empty(remap.shape[0], dtype=bool)
            hit = remap < 0
            out[hit] = True  # cache hits: this exact triple verified before
            out[~hit] = ok_u[remap[~hit]]
            return out
        if bundle.kind == "rows":
            with self._cv:
                self.device_rows += int(p["idx"].shape[0])
            out = None
            f = getattr(self.inner, "verify_rows_cached", None)
            if f is not None:
                out = f(p["vkey"], p["all_pk"], p["idx"], p["mg"], p["sg"])
            if out is None:
                pk = np.asarray(p["all_pk"], dtype=np.uint8)[p["idx"]]
                out = self.inner.verify_batch(pk, p["mg"], p["sg"])
            return np.asarray(out)
        # "tpl"
        with self._cv:
            self.device_rows += int(p["idx"].shape[0])
        out = None
        f_t = getattr(self.inner, "verify_rows_cached_templated", None)
        if f_t is not None:
            out = f_t(
                p["vkey"], p["all_pk"], p["idx"],
                p["templates"], p["tmpl_idx"], p["ts8"], p["sg"],
            )
        if out is None:
            from tendermint_tpu.codec.signbytes import splice_timestamps

            mg = splice_timestamps(p["templates"][p["tmpl_idx"]], p["ts8"])
            f = getattr(self.inner, "verify_rows_cached", None)
            if f is not None:
                out = f(p["vkey"], p["all_pk"], p["idx"], mg, p["sg"])
            if out is None:
                pk = np.asarray(p["all_pk"], dtype=np.uint8)[p["idx"]]
                out = self.inner.verify_batch(pk, mg, p["sg"])
        return np.asarray(out)
