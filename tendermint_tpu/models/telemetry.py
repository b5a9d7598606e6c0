"""The unified engine-telemetry protocol: one ``engine_stats()`` shape
for every device engine.

Before this module the four engines each grew an ad-hoc surface —
``PipelinedVerifier.stats()`` + ``VerifierModel.compile_stats()``,
``MerkleHasher.stats``/``compile_stats()``, ``BLSEngine.stats``/
``compile_stats()``, ``TxKeyHasher.stats()`` — four key vocabularies
for the same four questions: which jit buckets are warm/compiling/
failed, what is the breaker doing, how many rows ran on device vs
host, and how long does work wait before the device sees it. This
module fixes the vocabulary; each engine implements

    engine_stats() -> {
        "engine":       str,            # "pipeline"|"merkle"|"bls"|"txhash"
        "device_rows":  float,          # rows a device executable verified/hashed
        "host_rows":    float,          # rows the host path served instead
        "buckets":      {key: {"state": "ready|compiling|failed|cold",
                               "compile_s": float|None}},
        "breakers":     {name: {"state", "state_code", "trips",
                                "recoveries"}},
        "queue_wait_ms": snapshot|None, # QueueWaitHist.snapshot()
        "counters":     {...},          # engine-specific monotonic extras
    }

consumed three ways: the ``engines`` RPC route (rpc/core.py), the
``tendermint_engine_*`` labeled metric family (utils/metrics.py
EngineMetrics), and the height ledger's per-height engine deltas
(consensus/ledger.py via ``flatten_engine_counters``). docs/metrics.md
documents the exported family.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List

# Queue-wait buckets in MILLISECONDS (upper bounds); the metrics-side
# histogram uses the same edges in seconds so snapshots merge 1:1.
QUEUE_WAIT_BUCKETS_MS = (0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 1000)


class QueueWaitHist:
    """Thread-safe fixed-bucket histogram of submit→execute waits.

    Engines observe in milliseconds; ``snapshot()`` returns cumulative-
    free (per-bucket) counts + sum + count so the exposition layer can
    delta-merge it into a real Prometheus histogram
    (utils/metrics.py Histogram.add_raw, via EngineMetrics.update)."""

    __slots__ = ("_lock", "counts", "sum_ms", "count")

    def __init__(self):
        self._lock = threading.Lock()
        self.counts = [0] * (len(QUEUE_WAIT_BUCKETS_MS) + 1)
        self.sum_ms = 0.0
        self.count = 0

    def observe_ms(self, ms: float) -> None:
        with self._lock:
            self.sum_ms += ms
            self.count += 1
            for i, b in enumerate(QUEUE_WAIT_BUCKETS_MS):
                if ms <= b:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "bucket_ms": list(QUEUE_WAIT_BUCKETS_MS),
                "counts": list(self.counts),
                "sum_ms": self.sum_ms,
                "count": self.count,
            }


def breaker_view(*breakers) -> Dict[str, Dict[str, Any]]:
    """The protocol's breaker section from CircuitBreaker instances
    (None entries skipped)."""
    out: Dict[str, Dict[str, Any]] = {}
    for b in breakers:
        if b is None:
            continue
        st = b.stats()
        out[b.name] = {
            "state": st.get("state"),
            "state_code": st.get("state_code", 0),
            "trips": st.get("trips", 0),
            "recoveries": st.get("recoveries", 0),
        }
    return out


def bucket_entry(e) -> Dict[str, Any]:
    """One bucket's protocol view from an engine-internal entry object
    (duck-typed ready/compiling/failed[/compile_s])."""
    if getattr(e, "failed", False):
        state = "failed"
    elif getattr(e, "ready", False):
        state = "ready"
    elif getattr(e, "compiling", False):
        state = "compiling"
    else:
        state = "cold"
    return {"state": state, "compile_s": getattr(e, "compile_s", None)}


def bucket_view(entries: Dict) -> Dict[str, Dict[str, Any]]:
    """The protocol's bucket section from an engine's internal bucket
    map ({key: obj with ready/compiling/failed[/compile_s]})."""
    return {str(key): bucket_entry(e) for key, e in entries.items()}


def bucket_counts(stats: Dict[str, Any]) -> Dict[str, int]:
    """ready/compiling/failed/cold tallies over one engine_stats()."""
    tally = {"ready": 0, "compiling": 0, "failed": 0, "cold": 0}
    for b in (stats.get("buckets") or {}).values():
        tally[b.get("state", "cold")] = tally.get(b.get("state", "cold"), 0) + 1
    return tally


def flatten_engine_counters(
    all_stats: Dict[str, Dict[str, Any]]
) -> Dict[str, float]:
    """Flat ``{engine.key: value}`` numeric view over a collection of
    engine_stats() — the height ledger diffs two of these to attribute
    engine work to a height (consensus/ledger.py engines_fn)."""
    flat: Dict[str, float] = {}
    for name, st in (all_stats or {}).items():
        if not isinstance(st, dict):
            continue
        for k in ("device_rows", "host_rows"):
            v = st.get(k)
            if isinstance(v, (int, float)):
                flat[f"{name}.{k}"] = float(v)
        for k, v in (st.get("counters") or {}).items():
            if isinstance(v, (int, float)):
                flat[f"{name}.{k}"] = float(v)
        qw = st.get("queue_wait_ms")
        if isinstance(qw, dict):
            flat[f"{name}.queue_waits"] = float(qw.get("count", 0))
            flat[f"{name}.queue_wait_sum_ms"] = float(qw.get("sum_ms", 0.0))
    return flat


def collect_engine_stats(engines: List) -> Dict[str, Dict[str, Any]]:
    """{engine-name: engine_stats()} over objects implementing the
    protocol (Nones and protocol-less objects skipped; a failing
    engine reports an "error" stanza instead of killing the caller —
    this feeds the metrics pump and an RPC route)."""
    out: Dict[str, Dict[str, Any]] = {}
    for eng in engines:
        fn = getattr(eng, "engine_stats", None)
        if eng is None or fn is None:
            continue
        try:
            st = fn()
            if st is None:  # engine present but never engaged
                continue
            out[st.get("engine", type(eng).__name__)] = st
        except Exception as e:  # pragma: no cover - defensive
            out[type(eng).__name__] = {"engine": type(eng).__name__, "error": repr(e)[:200]}
    return out
