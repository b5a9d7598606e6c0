"""Metrics: Prometheus-text-format counters/gauges/histograms.

Reference: go-kit metrics with the Prometheus provider — per-module
Metrics structs with PrometheusMetrics()/NopMetrics() constructors
(consensus/metrics.go, p2p/metrics.go, mempool/metrics.go,
state/metrics.go), served at instrumentation.prometheus_listen_addr
(node/node.go:781-784; metric table docs/tendermint-core/metrics.md).

All instruments are thread-safe: mutation (``inc``/``set``/``add``/
``observe``) and exposition hold a per-metric lock — values are written
from the event loop, the crypto pipeline's dispatch/exec threads, and
background compile threads concurrently with the scrape handler.

Labels: every instrument supports ``with_labels(k=v, ...)``, returning
a child instrument exposing ``name{k="v",...}`` series (go-kit
``With``). Children share the parent's HELP/TYPE header; the unlabeled
base series is emitted only while no children exist or the base was
itself written, so a fully-labeled family never exports a stray
``name 0`` sample. Label values are escaped per the Prometheus text
format (backslash, double quote, newline).
"""

from __future__ import annotations

import asyncio
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple


def escape_label_value(v: str) -> str:
    """Prometheus text-format label escaping (backslash first)."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _render_labels(labels: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{k}="{escape_label_value(v)}"' for k, v in labels]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class Metric:
    def __init__(self, name: str, help_: str, namespace: str, subsystem: str):
        self.name = f"{namespace}_{subsystem}_{name}" if subsystem else f"{namespace}_{name}"
        self.help = help_
        self._lock = threading.Lock()
        self._labels: Tuple[Tuple[str, str], ...] = ()
        self._children: "OrderedDict[Tuple[Tuple[str, str], ...], Metric]" = OrderedDict()
        self._parent: Optional["Metric"] = None
        self._touched = False

    # -- labels ------------------------------------------------------------

    def with_labels(self, **labels) -> "Metric":
        """Child instrument for this label set (created once, then
        returned again — so ``m.with_labels(peer=p).inc()`` is cheap on
        repeat calls). Chaining composes go-kit-style:
        ``m.with_labels(a=1).with_labels(b=2)`` is the ``{a,b}`` child
        of the ROOT instrument (only the root's children are exposed)."""
        if self._parent is not None:
            merged = dict(self._labels)
            merged.update((k, str(v)) for k, v in labels.items())
            return self._parent.with_labels(**merged)
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                child.name = self.name  # series name comes from the parent
                child.help = self.help
                child._labels = key
                child._parent = self
                self._children[key] = child
            return child

    def _make_child(self) -> "Metric":
        raise NotImplementedError

    # -- exposition --------------------------------------------------------

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            children = list(self._children.values())
            emit_base = self._touched or not children
        if emit_base:
            out.extend(self._sample_lines())
        for c in children:
            out.extend(c._sample_lines())
        return out

    def _sample_lines(self) -> List[str]:
        raise NotImplementedError


class Gauge(Metric):
    kind = "gauge"

    def __init__(self, name, help_="", namespace="tendermint", subsystem=""):
        super().__init__(name, help_, namespace, subsystem)
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)
            self._touched = True

    def add(self, v: float) -> None:
        with self._lock:
            self.value += v
            self._touched = True

    def _make_child(self) -> "Gauge":
        return Gauge("child", self.help)

    def _sample_lines(self) -> List[str]:
        with self._lock:
            v = self.value
        return [f"{self.name}{_render_labels(self._labels)} {v}"]


class Counter(Metric):
    kind = "counter"

    def __init__(self, name, help_="", namespace="tendermint", subsystem=""):
        super().__init__(name, help_, namespace, subsystem)
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError(f"counter {self.name} cannot decrease (inc {v})")
        with self._lock:
            self.value += v
            self._touched = True

    def _make_child(self) -> "Counter":
        return Counter("child", self.help)

    def _sample_lines(self) -> List[str]:
        with self._lock:
            v = self.value
        return [f"{self.name}{_render_labels(self._labels)} {v}"]


class Histogram(Metric):
    kind = "histogram"
    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10)

    def __init__(self, name, help_="", namespace="tendermint", subsystem="", buckets=None):
        super().__init__(name, help_, namespace, subsystem)
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        with self._lock:
            self._touched = True
            self.sum += v
            self.count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1

    def add_raw(self, bucket_counts, sum_v: float, count_v: int) -> None:
        """Merge per-bucket INCREMENTS from an external histogram
        snapshot (the engine-telemetry queue-wait hists keep their own
        counts; a distribution can't be rebuilt from observe() calls).
        ``bucket_counts`` must match this histogram's bucket layout
        (len(buckets)+1, the last being the +Inf overflow). Exposition
        invariants (cumulative monotone, +Inf == _count) hold because
        sum/count/buckets advance together."""
        if len(bucket_counts) != len(self.counts):
            raise ValueError(
                f"histogram {self.name}: snapshot has {len(bucket_counts)} "
                f"buckets, instrument has {len(self.counts)}"
            )
        if count_v < 0 or any(c < 0 for c in bucket_counts):
            raise ValueError(f"histogram {self.name}: negative raw increment")
        with self._lock:
            self._touched = True
            for i, c in enumerate(bucket_counts):
                self.counts[i] += int(c)
            self.sum += float(sum_v)
            self.count += int(count_v)

    def _make_child(self) -> "Histogram":
        return Histogram("child", self.help, buckets=self.buckets)

    def _sample_lines(self) -> List[str]:
        with self._lock:
            counts = list(self.counts)
            total, s = self.count, self.sum
        out = []
        lbl = self._labels
        acc = 0
        for b, c in zip(self.buckets, counts):
            acc += c
            le = 'le="%s"' % b
            out.append(f"{self.name}_bucket{_render_labels(lbl, le)} {acc}")
        inf = 'le="+Inf"'
        out.append(f"{self.name}_bucket{_render_labels(lbl, inf)} {total}")
        out.append(f"{self.name}_sum{_render_labels(lbl)} {s}")
        out.append(f"{self.name}_count{_render_labels(lbl)} {total}")
        return out


class Registry:
    def __init__(self):
        self._metrics: List[Metric] = []
        self._lock = threading.Lock()

    def register(self, m: Metric) -> Metric:
        with self._lock:
            self._metrics.append(m)
        return m

    def expose_text(self) -> str:
        with self._lock:
            metrics = list(self._metrics)
        lines: List[str] = []
        for m in metrics:
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"


class _SnapshotCounters:
    """Feed true counters from a monotonic-snapshot source.

    The crypto pipeline and merkle engine keep their own internal
    counters and hand the node periodic ``stats()`` snapshots; the
    metric pump can only see absolute values, not increments. This
    helper turns those snapshots into genuine Prometheus counters by
    inc()'ing the positive delta vs the previous snapshot. A snapshot
    that goes BACKWARD (source replaced/restarted, e.g. a new
    PipelinedVerifier after reconfiguration) is treated as a fresh
    source: the full new value is added, mirroring how Prometheus
    ``rate()`` handles counter resets."""

    def __init__(self):
        self._last: Dict[str, float] = {}

    def feed(self, counter: Counter, key: str, stats: dict) -> None:
        new = float(stats.get(key, 0) or 0)
        prev = self._last.get(key, 0.0)
        counter.inc(new - prev if new >= prev else new)
        self._last[key] = new


# -- per-module metric structs (reference per-package metrics.go) ----------


class ConsensusMetrics:
    """Reference consensus/metrics.go (213 lines)."""

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "consensus"
        reg = r.register
        self.height = reg(Gauge("height", "Height of the chain.", namespace, sub))
        self.rounds = reg(Gauge("rounds", "Number of rounds.", namespace, sub))
        self.validators = reg(Gauge("validators", "Number of validators.", namespace, sub))
        self.validators_power = reg(Gauge("validators_power", "Total voting power.", namespace, sub))
        self.missing_validators = reg(Gauge("missing_validators", "Validators missing from the last commit.", namespace, sub))
        self.byzantine_validators = reg(Gauge("byzantine_validators", "Validators that equivocated.", namespace, sub))
        self.block_interval_seconds = reg(Histogram("block_interval_seconds", "Time between blocks.", namespace, sub))
        self.num_txs = reg(Gauge("num_txs", "Txs in the latest block.", namespace, sub))
        self.block_size_bytes = reg(Gauge("block_size_bytes", "Size of the latest block.", namespace, sub))
        self.total_txs = reg(Counter("total_txs", "Total transactions committed.", namespace, sub))
        self.committed_height = reg(Gauge("latest_block_height", "Latest committed height.", namespace, sub))
        self.fast_syncing = reg(Gauge("fast_syncing", "Whether fast-sync is active.", namespace, sub))
        # per-step latency attribution (flight recorder summary; the
        # full span detail rides the dump_trace RPC). Labeled by step.
        self.step_duration_seconds = reg(
            Histogram(
                "step_duration_seconds",
                "Wall seconds spent in each consensus step transition (label: step).",
                namespace, sub,
                buckets=[i / 1000 for i in (1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000)],
            )
        )
        # per-height phase decomposition (consensus/ledger.py): each
        # committed height's wall time tiled into named phases + an
        # explicit unaccounted residual — the always-on form of the
        # height_report RPC (docs/tracing.md, height ledger)
        self.height_phase_seconds = reg(
            Histogram(
                "height_phase_seconds",
                "Wall seconds each committed height spent per named phase "
                "(label: phase; includes an explicit 'unaccounted' residual "
                "so attribution gaps are visible).",
                namespace, sub,
                buckets=[i / 1000 for i in (1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000)],
            )
        )


class P2PMetrics:
    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "p2p"
        self.peers = r.register(Gauge("peers", "Number of connected peers.", namespace, sub))
        self.peer_receive_bytes_total = r.register(Counter("peer_receive_bytes_total", "Bytes received.", namespace, sub))
        self.peer_send_bytes_total = r.register(Counter("peer_send_bytes_total", "Bytes sent.", namespace, sub))


class MempoolMetrics:
    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "mempool"
        self.size = r.register(Gauge("size", "Number of uncommitted txs.", namespace, sub))
        self.tx_size_bytes = r.register(Histogram("tx_size_bytes", "Tx sizes.", namespace, sub, buckets=(32, 128, 512, 2048, 8192, 32768)))
        self.failed_txs = r.register(Counter("failed_txs", "Rejected txs.", namespace, sub))
        self.recheck_times = r.register(Counter("recheck_times", "Tx rechecks.", namespace, sub))


class CryptoMetrics:
    """Pipelined verification dispatch + gossip dedupe cache
    (crypto/pipeline.py). Monotonic totals are TRUE counters fed by
    snapshot deltas from PipelinedVerifier.stats() on each pump;
    instantaneous values (queue depth, occupancy, cache size) stay
    gauges. See docs/verification-pipeline.md."""

    _COUNTERS = (
        ("pipeline_submitted", "submitted_calls"),
        ("pipeline_bundles", "dispatched_bundles"),
        ("pipeline_rows", "submitted_rows"),
        ("pipeline_device_rows", "device_rows"),
        ("dedupe_cache_hits", "cache_hits"),
        ("dedupe_cache_misses", "cache_misses"),
        ("seam_column_rows", "seam_column_rows"),
        ("seam_packed_rows", "seam_packed_rows"),
        ("seam_fixup_rows", "seam_fixup_rows"),
        ("seam_overlapped_rows", "seam_overlapped_rows"),
        ("seam_multiset_rows", "seam_multiset_rows"),
        ("tabled_slot_rows", "tabled_slot_rows"),
        ("tabled_slot_pad", "tabled_slot_pad"),
        ("tabled_gathered_rows", "tabled_gathered_rows"),
        ("tabled_kernel_slots", "tabled_kernel_slots"),
        ("table_keys_built", "table_keys_built"),
        ("table_keys_loaded", "table_keys_loaded"),
        ("table_keys_reused", "table_keys_reused"),
        ("table_keys_evicted", "table_keys_evicted"),
        ("table_slabs", "table_slabs"),
        ("table_slab_columns", "table_slab_columns"),
        ("generic_rows", "generic_rows"),
        ("generic_pad_rows", "generic_pad_rows"),
        ("generic_windows", "generic_windows"),
        ("generic_launches", "generic_launches"),
        ("generic_kernel_rows", "generic_kernel_rows"),
        ("h2d_bytes", "h2d_bytes"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "crypto"
        reg = r.register
        self.pipeline_queue_depth = reg(Gauge("pipeline_queue_depth", "Verify requests waiting for dispatch.", namespace, sub))
        self.pipeline_submitted = reg(Counter("pipeline_submitted_total", "Verify requests submitted.", namespace, sub))
        self.pipeline_bundles = reg(Counter("pipeline_bundles_total", "Device bundles dispatched.", namespace, sub))
        self.pipeline_rows = reg(Counter("pipeline_rows_total", "Signature rows submitted.", namespace, sub))
        self.pipeline_device_rows = reg(Counter("pipeline_device_rows_total", "Signature rows that reached the device (post-dedupe).", namespace, sub))
        self.pipeline_batch_occupancy = reg(Gauge("pipeline_batch_occupancy_avg", "Mean requests coalesced per bundle.", namespace, sub))
        self.dedupe_cache_hits = reg(Counter("dedupe_cache_hits_total", "Dedupe-cache hits (device round trips saved).", namespace, sub))
        self.dedupe_cache_misses = reg(Counter("dedupe_cache_misses_total", "Dedupe-cache misses.", namespace, sub))
        self.dedupe_cache_size = reg(Gauge("dedupe_cache_size", "Verified triples currently cached.", namespace, sub))
        self.seam_column_rows = reg(Counter("seam_column_rows_total", "Commit signature slots read into columns (once per Commit object).", namespace, sub))
        self.seam_packed_rows = reg(Counter("seam_packed_rows_total", "Commit rows packed from columns for a provider.", namespace, sub))
        self.seam_fixup_rows = reg(Counter("seam_fixup_rows_total", "Packed rows off the common shape: non-64-byte signature, non-ed25519 key, unknown address.", namespace, sub))
        self.seam_overlapped_rows = reg(Counter("seam_overlapped_rows_total", "Packed rows a provider took as a later group of one call: packed while the device ran the launch before them.", namespace, sub))
        self.seam_multiset_rows = reg(Counter("seam_multiset_rows_total", "Rows of spec lists over more than one validator set that the cached key tables answered.", namespace, sub))
        self.tabled_slot_rows = reg(Counter("tabled_slot_rows_total", "Rows verified in slot order: key tables read in place.", namespace, sub))
        self.tabled_slot_pad = reg(Counter("tabled_slot_pad_total", "Empty slots launched with the slot-order rows.", namespace, sub))
        self.tabled_gathered_rows = reg(Counter("tabled_gathered_rows_total", "Rows verified with their key tables gathered per row.", namespace, sub))
        self.tabled_kernel_slots = reg(Counter("tabled_kernel_slots_total", "Slots launched into a stage-2 program whose point arithmetic is the Pallas kernel form.", namespace, sub))
        self.table_keys_built = reg(Counter("table_keys_built_total", "Validator keys whose table the device built into the key pool.", namespace, sub))
        self.table_keys_loaded = reg(Counter("table_keys_loaded_total", "Validator keys whose table was read back from the table files.", namespace, sub))
        self.table_keys_reused = reg(Counter("table_keys_reused_total", "Validator keys a call asked for and found pooled.", namespace, sub))
        self.table_keys_evicted = reg(Counter("table_keys_evicted_total", "Least-recently-used keys dropped from the key pool under its byte bound.", namespace, sub))
        self.table_slabs = reg(Counter("table_slabs_total", "Launches whose table operand was gathered from the key pool.", namespace, sub))
        self.table_slab_columns = reg(Counter("table_slab_columns_total", "Key-table columns those gathers copied.", namespace, sub))
        self.generic_rows = reg(Counter("generic_rows_total", "Rows the generic verify family verified on the device: no validator set, the key decompressed and tabled per row.", namespace, sub))
        self.generic_pad_rows = reg(Counter("generic_pad_rows_total", "Empty rows launched with the generic rows up to their bucket.", namespace, sub))
        self.generic_windows = reg(Counter("generic_windows_total", "Full 16,384-row windows that generic batches past one launch streamed.", namespace, sub))
        self.generic_launches = reg(Counter("generic_launches_total", "Generic three-stage launches, a streamed batch's tail included.", namespace, sub))
        self.generic_kernel_rows = reg(Counter("generic_kernel_rows_total", "Rows (real and pad) launched into a generic launch whose three stages take the Pallas kernel form (stage 2's point arithmetic, stages 1 and 3's exponentiation chains).", namespace, sub))
        self.h2d_bytes = reg(Counter("h2d_bytes_total", "Bytes copied host to device for served verify launches (tabled and generic; table builds excluded).", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a PipelinedVerifier.stats() snapshot into the
        instruments (delta-feed for counters, set for gauges)."""
        self.pipeline_queue_depth.set(stats.get("queue_depth", 0))
        self.pipeline_batch_occupancy.set(stats.get("batch_occupancy_avg", 0))
        self.dedupe_cache_size.set(stats.get("cache_size", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class MerkleMetrics:
    """Device merkle engine counters (crypto/merkle.py device_stats():
    the batched SHA-256 engine behind tx/part-set/validator-set
    hashing, models/hasher.py). Monotonic totals are TRUE counters fed
    by snapshot deltas, like CryptoMetrics.
    See docs/merkle-acceleration.md."""

    _COUNTERS = (
        ("device_roots", "device_roots"),
        ("device_proof_sets", "device_proof_sets"),
        ("device_leaves", "device_leaves"),
        ("host_roots", "host_roots"),
        ("host_proof_sets", "host_proof_sets"),
        ("fallback_cold", "fallback_cold"),
        ("fallback_shape", "fallback_shape"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "merkle"
        reg = r.register
        self.device_enabled = reg(Gauge("device_enabled", "1 when the device merkle engine is configured on.", namespace, sub))
        self.device_roots = reg(Counter("device_roots_total", "Merkle roots computed on the device engine.", namespace, sub))
        self.device_proof_sets = reg(Counter("device_proof_sets_total", "Full proof sets (root + aunts) computed on the device engine.", namespace, sub))
        self.device_leaves = reg(Counter("device_leaves_total", "Leaves hashed by the device engine.", namespace, sub))
        self.host_roots = reg(Counter("host_roots_total", "Merkle roots computed on the host path (below threshold or fallback).", namespace, sub))
        self.host_proof_sets = reg(Counter("host_proof_sets_total", "Proof sets computed on the host path.", namespace, sub))
        self.fallback_cold = reg(Counter("fallback_cold_total", "Qualifying trees served on host while a device bucket compiled.", namespace, sub))
        self.fallback_shape = reg(Counter("fallback_shape_total", "Qualifying trees outside the device size caps (leaf count/bytes).", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a crypto.merkle.device_stats() snapshot into the
        instruments."""
        self.device_enabled.set(stats.get("device_enabled", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class TraceMetrics:
    """Flight-recorder health (utils/trace.py Tracer.stats()): is the
    tracer on, how full is the ring, is it dropping. The span payloads
    themselves are served by the dump_trace RPC, not scraped."""

    _COUNTERS = (
        ("events_recorded", "events_recorded"),
        ("events_dropped", "events_dropped"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "trace"
        reg = r.register
        self.enabled = reg(Gauge("enabled", "1 when span tracing is enabled.", namespace, sub))
        self.events_recorded = reg(Counter("events_recorded_total", "Trace events recorded into the ring buffer.", namespace, sub))
        self.events_dropped = reg(Counter("events_dropped_total", "Trace events evicted from the full ring buffer.", namespace, sub))
        self.buffer_events = reg(Gauge("buffer_events", "Events currently held in the ring buffer.", namespace, sub))
        self.buffer_capacity = reg(Gauge("buffer_capacity", "Ring buffer capacity (trace_buffer_events).", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a Tracer.stats() snapshot into the instruments."""
        self.enabled.set(stats.get("enabled", 0))
        self.buffer_events.set(stats.get("buffer_events", 0))
        self.buffer_capacity.set(stats.get("buffer_capacity", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class HealthMetrics:
    """Self-healing / chaos layer health (``tendermint_health_*``):
    watchdog restarts + stalls + future deadlines (utils/watchdog.py
    Watchdog.stats()), circuit-breaker state/trips/recoveries for every
    registered breaker (watchdog.breaker_stats()), and injected-fault
    counters (utils/faultinject.py stats()). Monotonic totals are TRUE
    counters fed by snapshot deltas, like CryptoMetrics; per-entity
    series ride labels (worker=, breaker=, site=).
    See docs/robustness.md."""

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "health"
        reg = r.register
        self.watchdog_enabled = reg(Gauge("watchdog_enabled", "1 when the watchdog supervisor thread is running.", namespace, sub))
        self.worker_restarts = reg(Counter("worker_restarts_total", "Dead worker loops restarted by the watchdog (label: worker).", namespace, sub))
        self.worker_stalls = reg(Counter("worker_stalls_total", "Stall episodes recorded on progress probes/heartbeats (label: worker).", namespace, sub))
        self.future_timeouts = reg(Counter("future_timeouts_total", "Futures force-failed by a watchdog deadline.", namespace, sub))
        self.breaker_state = reg(Gauge("breaker_state", "Circuit-breaker state: 0 closed, 1 half-open, 2 open (label: breaker).", namespace, sub))
        self.breaker_trips = reg(Counter("breaker_trips_total", "Circuit-breaker trips to open (label: breaker).", namespace, sub))
        self.breaker_recoveries = reg(Counter("breaker_recoveries_total", "Half-open probes that closed a breaker (label: breaker).", namespace, sub))
        self.faults_enabled = reg(Gauge("faults_enabled", "1 when fault injection is armed (TM_FAULTS / programmatic).", namespace, sub))
        self.faults_injected = reg(Counter("faults_injected_total", "Faults injected at registered sites (label: site).", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(
        self,
        watchdog_stats: Optional[dict] = None,
        breaker_stats: Optional[dict] = None,
        fault_stats: Optional[dict] = None,
    ) -> None:
        """Fold the three snapshot sources into the instruments. Any
        source may be None (e.g. no watchdog configured)."""
        d = self._deltas
        if watchdog_stats is not None:
            self.watchdog_enabled.set(watchdog_stats.get("running", 0))
            d.feed(self.future_timeouts, "future_timeouts", watchdog_stats)
            for worker, ws in watchdog_stats.get("workers", {}).items():
                d.feed(
                    self.worker_restarts.with_labels(worker=worker),
                    f"restarts/{worker}", {f"restarts/{worker}": ws.get("restarts", 0)},
                )
            for name, ps in watchdog_stats.get("stalls", {}).items():
                d.feed(
                    self.worker_stalls.with_labels(worker=name),
                    f"stalls/{name}", {f"stalls/{name}": ps.get("stalls", 0)},
                )
        if breaker_stats is not None:
            for name, bs in breaker_stats.items():
                self.breaker_state.with_labels(breaker=name).set(bs.get("state_code", 0))
                d.feed(
                    self.breaker_trips.with_labels(breaker=name),
                    f"trips/{name}", {f"trips/{name}": bs.get("trips", 0)},
                )
                d.feed(
                    self.breaker_recoveries.with_labels(breaker=name),
                    f"recoveries/{name}", {f"recoveries/{name}": bs.get("recoveries", 0)},
                )
        if fault_stats is not None:
            self.faults_enabled.set(fault_stats.get("enabled", 0))
            for site, ss in fault_stats.get("sites", {}).items():
                d.feed(
                    self.faults_injected.with_labels(site=site),
                    f"faults/{site}", {f"faults/{site}": ss.get("triggers", 0)},
                )


class StallMetrics:
    """Consensus stall autopsy (``tendermint_stall_*``,
    consensus/flightrec.py StallTracker.stats()): is the node's height
    probe currently stalled, for how long, at which height/round, and
    the quorum shortfall from the live VoteSet (missing voting power +
    silent validator count). Edge counters (stalls/recoveries) are
    TRUE counters fed by snapshot deltas, like CryptoMetrics; the full
    machine-readable diagnosis rides the dump_debug RPC.
    See docs/observability.md."""

    _COUNTERS = (
        ("stalls", "stalls"),
        ("recoveries", "recoveries"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "stall"
        reg = r.register
        self.stalled = reg(Gauge("stalled", "1 while the consensus height probe is stalled past the watchdog horizon.", namespace, sub))
        self.stalled_seconds = reg(Gauge("stalled_seconds", "Seconds the current stall has lasted (0 when not stalled).", namespace, sub))
        self.stalls = reg(Counter("stalls_total", "Consensus stall episodes detected.", namespace, sub))
        self.recoveries = reg(Counter("recoveries_total", "Stall episodes that ended with the height advancing again.", namespace, sub))
        self.height = reg(Gauge("height", "Height the last stall was diagnosed at.", namespace, sub))
        self.round = reg(Gauge("round", "Round the last stall was diagnosed at.", namespace, sub))
        self.missing_power = reg(Gauge("missing_power", "Voting power short of the +2/3 precommit quorum in the last diagnosis.", namespace, sub))
        self.missing_validators = reg(Gauge("missing_validators", "Validators silent for the entire stalled height in the last diagnosis.", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a StallTracker.stats() snapshot into the instruments."""
        self.stalled.set(stats.get("stalled", 0))
        self.stalled_seconds.set(stats.get("stalled_seconds", 0))
        self.height.set(stats.get("height", 0))
        self.round.set(stats.get("round", 0))
        self.missing_power.set(stats.get("missing_power", 0))
        self.missing_validators.set(stats.get("missing_validators", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class ByzMetrics:
    """Byzantine-defense telemetry (``tendermint_byz_*``): what the
    receive seam is shedding and who got quarantined for it. Fed from
    two snapshot sources — the switch's PeerGuard (p2p/behaviour.py:
    malformed frames by exception class, duplicate-run floods shed,
    far-future drops, quarantine trips) and the consensus state's
    ``byz_rejects`` backstop counter (consensus/state.py _handle_msg —
    peer messages whose handler raised anything unclassified).
    Monotonic totals are TRUE counters fed by snapshot deltas, like
    CryptoMetrics. See docs/robustness.md (attack playbook) and
    docs/metrics.md."""

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "byz"
        reg = r.register
        self.malformed_frames = reg(Counter("malformed_frames_total", "Malformed frames rejected at the decode seam (label: klass = exception class).", namespace, sub))
        self.floods_shed = reg(Counter("floods_shed_total", "Frames shed by the duplicate-run flood defense before reactor dispatch.", namespace, sub))
        self.future_drops = reg(Counter("future_buffer_drops_total", "Far-future consensus messages shed before any buffering.", namespace, sub))
        self.quarantines = reg(Counter("peer_quarantines_total", "Peers quarantined for repeated malformed traffic.", namespace, sub))
        self.handler_rejects = reg(Counter("handler_rejects_total", "Peer messages rejected by the consensus handler backstop (unclassified handler exception).", namespace, sub))
        self.quarantined_peers = reg(Gauge("quarantined_peers", "Peers currently serving a quarantine cooldown.", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, guard_stats: dict, handler_rejects: int = 0) -> None:
        """Fold a PeerGuard.stats() snapshot + the consensus backstop
        counter into the instruments."""
        d = self._deltas
        for klass, n in guard_stats.get("malformed_by_class", {}).items():
            d.feed(
                self.malformed_frames.with_labels(klass=klass),
                f"malformed/{klass}", {f"malformed/{klass}": n},
            )
        d.feed(self.floods_shed, "floods_shed", guard_stats)
        d.feed(self.future_drops, "future_drops", guard_stats)
        d.feed(self.quarantines, "quarantines", guard_stats)
        d.feed(self.handler_rejects, "handler_rejects", {"handler_rejects": handler_rejects})
        self.quarantined_peers.set(len(guard_stats.get("quarantined_peers", ())))


class LightServeMetrics:
    """Batched light-client verification service
    (``tendermint_lightserve_*``, lightserve/service.py +
    aggregator.py): client request volume, how well the shared store /
    single-flight / bundle funnel collapse it, and the bisection-depth
    distribution. Monotonic totals are TRUE counters fed by snapshot
    deltas from ``LightServeService.stats()`` on each pump, like
    CryptoMetrics; the bisection-depth histogram is observed directly
    by the service (a distribution can't be rebuilt from snapshot
    deltas). See docs/light-service.md."""

    _COUNTERS = (
        ("requests", "requests"),
        ("store_hits", "store_hits"),
        ("singleflight_runs", "singleflight_runs"),
        ("singleflight_hits", "singleflight_hits"),
        ("headers_verified", "headers_verified"),
        ("bundles", "bundles"),
        ("bundle_rows", "bundle_rows"),
        ("fetches", "fetches"),
        ("fetch_failures", "fetch_failures"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "lightserve"
        reg = r.register
        self.requests = reg(Counter("requests_total", "Client verify requests served.", namespace, sub))
        self.store_hits = reg(Counter("store_hits_total", "Requests answered from the shared verified-header store (no crypto).", namespace, sub))
        self.singleflight_runs = reg(Counter("singleflight_runs_total", "Bisections actually executed.", namespace, sub))
        self.singleflight_hits = reg(Counter("singleflight_hits_total", "Requests that shared another caller's in-flight bisection.", namespace, sub))
        self.headers_verified = reg(Counter("headers_verified_total", "Headers verified and added to the shared store.", namespace, sub))
        self.bundles = reg(Counter("bundles_total", "Aggregator bundles dispatched to the device.", namespace, sub))
        self.bundle_rows = reg(Counter("bundle_rows_total", "Signature rows dispatched in aggregator bundles.", namespace, sub))
        self.fetches = reg(Counter("fetches_total", "Header-source fetches.", namespace, sub))
        self.fetch_failures = reg(Counter("fetch_failures_total", "Header-source fetch attempts that failed (pre-retry).", namespace, sub))
        self.bundle_occupancy = reg(Gauge("bundle_occupancy_avg", "Mean verify requests coalesced per bundle.", namespace, sub))
        self.trusted_height = reg(Gauge("trusted_height", "Latest verified height in the shared store.", namespace, sub))
        self.trusted_heights = reg(Gauge("trusted_heights", "Heights currently held in the shared store.", namespace, sub))
        self.bisection_depth = reg(
            Histogram(
                "bisection_depth",
                "Links verified per bisection (skip-verification pivot chain length).",
                namespace, sub,
                buckets=(1, 2, 4, 8, 16, 32, 64),
            )
        )
        self._deltas = _SnapshotCounters()

    def observe_bisection_depth(self, depth: int) -> None:
        self.bisection_depth.observe(depth)

    def update(self, stats: dict) -> None:
        """Fold a LightServeService.stats() snapshot into the
        instruments (delta-feed for counters, set for gauges)."""
        self.bundle_occupancy.set(stats.get("bundle_occupancy_avg", 0))
        self.trusted_height.set(stats.get("trusted_height", 0))
        self.trusted_heights.set(stats.get("trusted_heights", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class IngestMetrics:
    """Batched mempool admission (``tendermint_ingest_*``,
    ingest/batcher.py + the mempool QoS lane): tx volume in/out of the
    admission funnel, how well concurrent CheckTx calls coalesce into
    device bundles, where tx-key hashing ran, and the lane occupancy /
    flood-defense counters. Monotonic totals are TRUE counters fed by
    snapshot deltas from ``IngestBatcher.stats()`` +
    ``Mempool.lane_stats()`` on each pump, like CryptoMetrics; the
    bundle-size histogram is observed directly by the batcher. See
    docs/ingest.md and docs/metrics.md."""

    _BATCHER_COUNTERS = (
        ("submitted", "submitted"),
        ("admitted", "admitted"),
        ("rejected", "rejected"),
        ("admission_errors", "admission_errors"),
        ("bundles", "bundles"),
        ("bundle_txs", "bundle_txs"),
        ("sig_rows", "sig_rows"),
        ("hash_device_rows", "hash_device_rows"),
        ("hash_host_rows", "hash_host_rows"),
    )
    _LANE_COUNTERS = (
        ("lane_evictions", "evicted"),
        ("sender_capped", "sender_capped"),
        ("recheck_cache_drops", "recheck_cache_drops"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "ingest"
        reg = r.register
        self.submitted = reg(Counter("submitted_total", "Txs submitted to the admission funnel.", namespace, sub))
        self.admitted = reg(Counter("admitted_total", "Txs the app accepted into the pool.", namespace, sub))
        self.rejected = reg(Counter("rejected_total", "Txs the app rejected (CheckTx code != OK).", namespace, sub))
        self.admission_errors = reg(Counter("admission_errors_total", "Txs refused by admission outside an app acceptance: cache dup / oversize / pre-check (before the app), flood cap / failed lane eviction (after it).", namespace, sub))
        self.bundles = reg(Counter("bundles_total", "Admission bundles dispatched.", namespace, sub))
        self.bundle_txs = reg(Counter("bundle_txs_total", "Txs carried in admission bundles.", namespace, sub))
        self.sig_rows = reg(Counter("sig_rows_total", "Signature rows pre-verified through the pipeline.", namespace, sub))
        self.hash_device_rows = reg(Counter("hash_device_rows_total", "Tx keys hashed by the device SHA-256 engine.", namespace, sub))
        self.hash_host_rows = reg(Counter("hash_host_rows_total", "Tx keys hashed on host (below threshold or fallback).", namespace, sub))
        self.lane_evictions = reg(Counter("lane_evictions_total", "Lower-priority txs evicted for paid traffic.", namespace, sub))
        self.sender_capped = reg(Counter("sender_capped_total", "Admissions refused by the per-sender flood cap.", namespace, sub))
        self.recheck_cache_drops = reg(Counter("recheck_cache_drops_total", "Pool txs dropped at recheck without an ABCI round-trip (cache no longer vouches).", namespace, sub))
        self.queue_depth = reg(Gauge("queue_depth", "Txs waiting for bundle dispatch.", namespace, sub))
        self.bundle_occupancy = reg(Gauge("bundle_occupancy_avg", "Mean txs coalesced per bundle.", namespace, sub))
        self.lane_txs = reg(Gauge("lane_txs", "Pool txs per QoS lane (label: lane).", namespace, sub))
        self.bundle_size = reg(
            Histogram(
                "bundle_size_txs",
                "Txs per dispatched admission bundle.",
                namespace, sub,
                buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
            )
        )
        self._deltas = _SnapshotCounters()

    def observe_bundle_txs(self, n: int) -> None:
        self.bundle_size.observe(n)

    def update(self, batcher_stats: dict, lane_stats: Optional[dict] = None) -> None:
        """Fold an IngestBatcher.stats() snapshot (and optionally the
        mempool's lane_stats()) into the instruments."""
        self.queue_depth.set(batcher_stats.get("queue_depth", 0))
        self.bundle_occupancy.set(batcher_stats.get("bundle_occupancy_avg", 0))
        for attr, key in self._BATCHER_COUNTERS:
            self._deltas.feed(getattr(self, attr), key, batcher_stats)
        if lane_stats is not None:
            self.lane_txs.with_labels(lane="paid").set(lane_stats.get("lane_paid", 0))
            self.lane_txs.with_labels(lane="free").set(lane_stats.get("lane_free", 0))
            for attr, key in self._LANE_COUNTERS:
                self._deltas.feed(getattr(self, attr), key, lane_stats)


class BLSMetrics:
    """BLS12-381 aggregation track (``tendermint_bls_*``,
    crypto/bls.BLSBatchVerifier.stats(): provider row counters merged
    with the models/bls.BLSEngine device counters): how many signature
    rows / hash-to-G2 maps / aggregate checks ran, where they executed
    (device kernels vs the pure-Python oracle fallback), and why the
    device declined (cold bucket vs shape caps). Monotonic totals are
    TRUE counters fed by snapshot deltas, like CryptoMetrics. See
    docs/bls-aggregation.md and docs/metrics.md."""

    _COUNTERS = (
        ("rows", "rows"),
        ("device_rows", "device_rows"),
        ("host_rows", "host_rows"),
        ("device_maps", "device_maps"),
        ("host_maps", "host_maps"),
        ("aggregate_checks", "aggregate_checks"),
        ("device_aggregates", "device_aggregates"),
        ("fallback_cold", "engine_fallback_cold"),
        ("fallback_shape", "engine_fallback_shape"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "bls"
        reg = r.register
        self.device_enabled = reg(Gauge("device_enabled", "1 when the BLS device engine is configured on.", namespace, sub))
        self.rows = reg(Counter("rows_total", "BLS signature rows submitted for verification.", namespace, sub))
        self.device_rows = reg(Counter("device_rows_total", "Rows verified by the device pairing kernel.", namespace, sub))
        self.host_rows = reg(Counter("host_rows_total", "Rows verified by the pure-Python oracle (fallback or below the device floor).", namespace, sub))
        self.device_maps = reg(Counter("device_maps_total", "Hash-to-G2 maps computed by the device kernel.", namespace, sub))
        self.host_maps = reg(Counter("host_maps_total", "Hash-to-G2 maps computed on host.", namespace, sub))
        self.aggregate_checks = reg(Counter("aggregate_checks_total", "AggregatedCommit verifications (one pairing per commit).", namespace, sub))
        self.device_aggregates = reg(Counter("device_aggregates_total", "Aggregate-pubkey sums computed by the device tree kernel.", namespace, sub))
        self.fallback_cold = reg(Counter("fallback_cold_total", "Device-eligible calls served on host while a bucket compiled.", namespace, sub))
        self.fallback_shape = reg(Counter("fallback_shape_total", "Device-eligible calls outside the kernel size caps.", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a BLSBatchVerifier.stats() snapshot into the
        instruments."""
        self.device_enabled.set(stats.get("device_enabled", 0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class MeshMetrics:
    """Mesh runtime (``tendermint_mesh_*``,
    parallel/topology.MeshRouter.stats()): where bundles routed
    (collective vs single-device), how rows spread across the local
    devices, and the health of the per-device ``mesh.device<i>``
    breakers — the shed/readmit story of a sick chip. Monotonic totals
    are TRUE counters fed by snapshot deltas, like CryptoMetrics. See
    docs/metrics.md and docs/verification-pipeline.md (Multi-chip)."""

    _COUNTERS = (
        ("collective_bundles", "collective_bundles"),
        ("single_bundles", "single_bundles"),
        ("shard_failures", "shard_failures"),
        ("sheds", "sheds"),
        ("readmits", "readmits"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "mesh"
        reg = r.register
        self.devices = reg(Gauge("devices", "Local devices in the mesh topology (0 when the mesh is off).", namespace, sub))
        self.admitted = reg(Gauge("admitted", "Devices currently admitted by the per-device breakers.", namespace, sub))
        self.collective_bundles = reg(Counter("collective_bundles_total", "Bundles sharded across two or more devices.", namespace, sub))
        self.single_bundles = reg(Counter("single_bundles_total", "Bundles routed to the single-device path (sub-threshold or degraded).", namespace, sub))
        self.shard_failures = reg(Counter("shard_failures_total", "Collective bundles that failed and fell back to the unmeshed path.", namespace, sub))
        self.sheds = reg(Counter("sheds_total", "Devices shed from the admitted set by a tripped breaker.", namespace, sub))
        self.readmits = reg(Counter("readmits_total", "Devices re-admitted after a successful half-open probe.", namespace, sub))
        self.shard_imbalance = reg(Gauge("shard_imbalance", "Row imbalance of the last collective plan: (max-min)/chunk, 0 is even.", namespace, sub))
        self.device_rows = reg(Counter("device_rows_total", "Rows routed to each device by collective plans (label: device).", namespace, sub))
        self.breaker_state = reg(Gauge("breaker_state", "Per-device breaker state: 0 closed, 1 half-open, 2 open (label: device).", namespace, sub))
        self._deltas = _SnapshotCounters()

    def update(self, stats: dict) -> None:
        """Fold a MeshRouter.stats() snapshot into the instruments."""
        if not stats:
            return
        self.devices.set(stats.get("devices", 0))
        self.admitted.set(stats.get("admitted", 0))
        self.shard_imbalance.set(stats.get("shard_imbalance", 0.0))
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)
        for i, rows in enumerate(stats.get("device_rows") or []):
            k = f"rows/{i}"
            self._deltas.feed(
                self.device_rows.with_labels(device=str(i)), k, {k: rows}
            )
        for i, b in enumerate(stats.get("breakers") or []):
            self.breaker_state.with_labels(device=str(i)).set(
                b.get("state_code", 0)
            )


class ExecMetrics:
    """Batched block execution (``tendermint_exec_*``,
    state/execution.BlockExecutor.exec_stats()): how many DeliverBatch
    requests ran and how many txs they carried, the optimistic-parallel
    scheduler's conflict / serial-re-run pressure, where the apps'
    batch work executed (device vs host rows), and how often a failed
    batch degraded to the per-tx path. Monotonic totals are TRUE
    counters fed by snapshot deltas, like CryptoMetrics; the batch-size
    histogram is observed directly by the executor. See
    docs/execution.md and docs/metrics.md."""

    _COUNTERS = (
        ("batches", "batches"),
        ("batch_txs", "batch_txs"),
        ("fallbacks", "fallbacks"),
        ("conflicts", "conflicts"),
        ("serial_reruns", "serial_reruns"),
        ("device_rows", "device_rows"),
        ("host_rows", "host_rows"),
    )

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "exec"
        reg = r.register
        self.batches = reg(Counter("batches_total", "DeliverBatch requests executed.", namespace, sub))
        self.batch_txs = reg(Counter("batch_txs_total", "Txs delivered via DeliverBatch requests.", namespace, sub))
        self.fallbacks = reg(Counter("fallbacks_total", "Blocks (or block remainders) degraded to the per-tx DeliverTx path.", namespace, sub))
        self.conflicts = reg(Counter("conflicts_total", "Speculative txs whose read/write footprint hit an earlier tx's writes.", namespace, sub))
        self.serial_reruns = reg(Counter("serial_reruns_total", "Conflicting txs re-executed on the serial path.", namespace, sub))
        self.device_rows = reg(Counter("device_rows_total", "App batch rows (signatures, hashes) executed on the device engines.", namespace, sub))
        self.host_rows = reg(Counter("host_rows_total", "App batch rows executed on host (no engine injected or fallback).", namespace, sub))
        self.batch_size = reg(
            Histogram(
                "batch_size_txs",
                "Txs per DeliverBatch request.",
                namespace, sub,
                buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
            )
        )
        self._deltas = _SnapshotCounters()

    def observe_batch_txs(self, n: int) -> None:
        self.batch_size.observe(n)

    def update(self, stats: dict) -> None:
        """Fold a BlockExecutor.exec_stats() snapshot into the
        instruments."""
        for attr, key in self._COUNTERS:
            self._deltas.feed(getattr(self, attr), key, stats)


class EngineMetrics:
    """Unified device-engine telemetry (``tendermint_engine_*``): ONE
    labeled family over every engine implementing the
    ``engine_stats()`` protocol (models/telemetry.py — the pipelined
    verifier, merkle hasher, BLS engine, tx-key hasher), replacing
    per-engine scrape vocabularies for the cross-engine questions:
    where are rows executing (device vs host), which jit buckets are
    warm/compiling/failed, is a breaker open, and how long does work
    wait before the device sees it. Engine-specific detail keeps riding
    the per-engine families (crypto/merkle/bls/ingest) and the
    ``engines`` RPC route. Monotonic totals are TRUE counters fed by
    snapshot deltas like CryptoMetrics; the queue-wait histogram merges
    raw bucket deltas from each engine's own hist
    (Histogram.add_raw)."""

    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        sub = "engine"
        reg = r.register
        self.device_rows = reg(Counter("device_rows_total", "Rows executed on the device path (label: engine).", namespace, sub))
        self.host_rows = reg(Counter("host_rows_total", "Rows/requests served by the host fallback path (label: engine).", namespace, sub))
        self.buckets_ready = reg(Gauge("buckets_ready", "Jit buckets with a warm executable (label: engine).", namespace, sub))
        self.buckets_compiling = reg(Gauge("buckets_compiling", "Jit buckets compiling in the background (label: engine).", namespace, sub))
        self.buckets_failed = reg(Gauge("buckets_failed", "Jit buckets parked on the host path behind a breaker (label: engine).", namespace, sub))
        self.breaker_state_max = reg(Gauge("breaker_state_max", "Worst breaker state across the engine's breakers: 0 closed, 1 half-open, 2 open (label: engine).", namespace, sub))
        self.compile_seconds = reg(Counter("compile_seconds_total", "Cumulative jit compile seconds recorded on warm buckets (label: engine).", namespace, sub))
        from tendermint_tpu.models.telemetry import QUEUE_WAIT_BUCKETS_MS

        self.queue_wait_seconds = reg(
            Histogram(
                "queue_wait_seconds",
                "Submit-to-execute wait of device work (label: engine; engines without a queue export nothing).",
                namespace, sub,
                buckets=[b / 1000.0 for b in QUEUE_WAIT_BUCKETS_MS],
            )
        )
        self._deltas = _SnapshotCounters()
        # per-engine last queue-wait snapshot, for raw bucket deltas
        self._qw_last: Dict[str, dict] = {}

    def update(self, stats_by_engine: Dict[str, dict]) -> None:
        """Fold a models/telemetry.collect_engine_stats() collection
        into the instruments."""
        from tendermint_tpu.models.telemetry import bucket_counts

        d = self._deltas
        for name, st in (stats_by_engine or {}).items():
            if not isinstance(st, dict) or "error" in st:
                continue
            d.feed(
                self.device_rows.with_labels(engine=name),
                f"dev/{name}", {f"dev/{name}": st.get("device_rows", 0)},
            )
            d.feed(
                self.host_rows.with_labels(engine=name),
                f"host/{name}", {f"host/{name}": st.get("host_rows", 0)},
            )
            tally = bucket_counts(st)
            self.buckets_ready.with_labels(engine=name).set(tally["ready"])
            self.buckets_compiling.with_labels(engine=name).set(tally["compiling"])
            self.buckets_failed.with_labels(engine=name).set(tally["failed"])
            # compile seconds feed PER BUCKET, not as a sum: bucket
            # tables are LRU-evicted (models/verifier.py valset cap),
            # and a shrinking sum would trip _SnapshotCounters' reset
            # heuristic — re-adding the surviving buckets' compile time
            # on every eviction.
            for bkey, b in (st.get("buckets") or {}).items():
                cs = b.get("compile_s") or 0.0
                if cs:
                    k = f"compile/{name}/{bkey}"
                    d.feed(
                        self.compile_seconds.with_labels(engine=name),
                        k, {k: cs},
                    )
            worst = max(
                (b.get("state_code", 0) for b in (st.get("breakers") or {}).values()),
                default=0,
            )
            self.breaker_state_max.with_labels(engine=name).set(worst)
            qw = st.get("queue_wait_ms")
            if isinstance(qw, dict) and qw.get("counts"):
                last = self._qw_last.get(name)
                counts, s, c = qw["counts"], qw.get("sum_ms", 0.0), qw.get("count", 0)
                if last is not None and c >= last.get("count", 0):
                    dc = [a - b for a, b in zip(counts, last["counts"])]
                    ds, dn = s - last.get("sum_ms", 0.0), c - last.get("count", 0)
                else:
                    # fresh/reset source: take the full new value
                    dc, ds, dn = list(counts), s, c
                if dn > 0 and all(x >= 0 for x in dc):
                    self.queue_wait_seconds.with_labels(engine=name).add_raw(
                        dc, ds / 1000.0, dn
                    )
                self._qw_last[name] = {"counts": list(counts), "sum_ms": s, "count": c}


class StateMetrics:
    def __init__(self, registry: Optional[Registry] = None, namespace="tendermint"):
        r = registry or Registry()
        self.block_processing_time = r.register(
            Histogram("block_processing_time", "Seconds to process a block.", namespace, "state",
                      buckets=[i / 1000 for i in (1, 5, 10, 25, 50, 100, 250, 500, 1000)])
        )


class MetricsServer:
    """Serves the registry at /metrics (reference node/node.go:781)."""

    def __init__(self, registry: Registry, host: str = "127.0.0.1", port: int = 26660):
        self.registry = registry
        self._host, self._port = host, port
        self._server = None
        self.bound_port: Optional[int] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._handle, self._host, self._port)
        self.bound_port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _handle(self, reader, writer) -> None:
        try:
            await reader.readline()
            while (await reader.readline()) not in (b"\r\n", b"\n", b""):
                pass
            body = self.registry.expose_text().encode()
            writer.write(
                b"HTTP/1.1 200 OK\r\nContent-Type: text/plain; version=0.0.4\r\n"
                + f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n".encode()
                + body
            )
            await writer.drain()
        finally:
            writer.close()
