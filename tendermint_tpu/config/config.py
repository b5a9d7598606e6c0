"""Configuration tree for a node.

Reference: config/config.go — master `Config` of 8 sections (:60-72) with
Default*/Test* constructors and ValidateBasic; consensus timeouts at
:749-800; p2p knobs :480; mempool :626 region; TOML rendering
config/toml.go:55. Here the on-disk format is TOML written/parsed with
the stdlib (tomllib for reads, a small renderer for writes) — no viper.

Timeouts are stored in milliseconds (ints) like the reference's
time.Duration fields; helpers return float seconds for asyncio.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields, is_dataclass
from typing import List, Optional

# -- directory layout (reference config/config.go:25-40) -------------------

DEFAULT_CONFIG_DIR = "config"
DEFAULT_DATA_DIR = "data"
DEFAULT_CONFIG_FILE = "config.toml"
DEFAULT_GENESIS_FILE = "genesis.json"
DEFAULT_PRIVVAL_KEY_FILE = "priv_validator_key.json"
DEFAULT_PRIVVAL_STATE_FILE = "priv_validator_state.json"
DEFAULT_NODE_KEY_FILE = "node_key.json"
DEFAULT_ADDR_BOOK_FILE = "addrbook.json"


@dataclass
class BaseConfig:
    """Top-level options (reference BaseConfig config/config.go:137)."""

    root_dir: str = ""
    chain_id: str = ""  # filled from genesis at load
    moniker: str = "anonymous"
    fast_sync: bool = True
    db_backend: str = "sqlite"  # sqlite | memdb
    db_dir: str = DEFAULT_DATA_DIR
    log_level: str = "main:info,state:info,*:error"
    log_format: str = "plain"
    genesis_file_name: str = os.path.join(DEFAULT_CONFIG_DIR, DEFAULT_GENESIS_FILE)
    priv_validator_key_name: str = os.path.join(DEFAULT_CONFIG_DIR, DEFAULT_PRIVVAL_KEY_FILE)
    priv_validator_state_name: str = os.path.join(DEFAULT_DATA_DIR, DEFAULT_PRIVVAL_STATE_FILE)
    priv_validator_laddr: str = ""  # remote signer listen addr
    node_key_name: str = os.path.join(DEFAULT_CONFIG_DIR, DEFAULT_NODE_KEY_FILE)
    abci: str = "local"  # local | socket | grpc
    proxy_app: str = "kvstore"  # app id for local, or tcp://... for socket/grpc
    prof_laddr: str = ""
    filter_peers: bool = False
    # TPU crypto provider selection (the plugin seam BASELINE.json names)
    crypto_provider: str = "tpu"  # tpu | cpu
    # crypto.pipeline: wrap the provider in the pipelined dispatcher
    # (crypto/pipeline.py) — future-based micro-batching with a gossip
    # dedupe cache. depth = how many fast-sync commits the reactors
    # keep in flight (the K-deep verify window,
    # blockchain/verify_window.py); flush_ms = how long the dispatcher
    # lingers to coalesce concurrent requests into one device call
    # (0 = only the natural back-pressure coalescing).
    crypto_pipeline: bool = True
    crypto_pipeline_depth: int = 8
    crypto_pipeline_flush_ms: int = 0
    # Shard the verify batch over a device mesh when this many JAX
    # devices are available (0/1 = single device). The sharded program
    # is shard_map'd per stage, rows sharded and verdicts returned to
    # the host, which tallies the quorum (models/verifier.py, no
    # collective); on hosts with fewer devices the node falls
    # back to single-device and logs it.
    crypto_mesh_devices: int = 0
    # The seam-level mesh runtime (parallel/topology.py): discover the
    # local device topology at node start and route EVERY device engine
    # — pipelined verifier, merkle leaf stage, BLS pairing rows, tx-key
    # SHA-256 — across all admitted devices through one MeshRouter.
    # Bundles below mesh_min_rows stay single-device (small commits
    # never pay collective latency); per-device circuit breakers shed a
    # sick chip's shard to the survivors and half-open probes re-admit
    # it. crypto_mesh_devices (above) caps the discovered topology when
    # > 0. TM_MESH=0/1 is the env kill switch overriding mesh_enabled
    # without editing toml. The degenerate 1-device topology is
    # bit-identical to the unmeshed path (tier-1 pinned).
    mesh_enabled: bool = False
    mesh_min_rows: int = 256
    # Device-batched SHA-256 merkle engine (models/hasher.py behind
    # crypto/merkle.py): tx roots, part-set roots, validator-set /
    # commit-sig / evidence hashes with at least merkle_device_threshold
    # leaves hash on the accelerator; smaller trees and every fallback
    # stay on the iterative host path (bit-identical roots/proofs). The
    # node enables the engine non-blocking: cold size-buckets hash on
    # host while their dispatch chain compiles in the background.
    merkle_device: bool = True
    merkle_device_threshold: int = 1024
    # Flight-recorder span tracing (utils/trace.py): consensus step
    # transitions, pipeline bundle lifecycle, merkle routing, WAL
    # fsyncs, mempool CheckTx and RPC requests recorded into a bounded
    # ring buffer, exported via the dump_trace / trace_timeline RPCs as
    # Chrome trace-event JSON (perfetto). Near-zero cost when disabled
    # (the default); TM_TRACE=0/1 is the env kill switch overriding
    # this without editing toml. trace_buffer_events bounds the ring —
    # the oldest events are evicted (and counted) once it fills.
    trace_enabled: bool = False
    trace_buffer_events: int = 65536
    # Consensus flight recorder (consensus/flightrec.py): an ALWAYS-ON
    # bounded ring of structured consensus events (step transitions,
    # votes in/out, proposal/part arrivals, timeouts, WAL fsyncs,
    # breaker trips, stall edges) per node — unlike the span tracer it
    # cannot be disabled, because a black box that was off during the
    # crash is useless. flightrec_events bounds the ring (the last N
    # events are served by dump_debug and persisted to the WAL-adjacent
    # .flightrec tail at every height fsync for offline autopsy).
    flightrec_events: int = 4096
    # Self-healing supervision (utils/watchdog.py): a daemon thread that
    # restarts dead pipeline workers, flags stalled pumps/height
    # progress, and enforces resolution deadlines on pipeline /
    # verify-window futures (a stuck future fails with a timeout and the
    # caller falls back to serial verify instead of hanging).
    # TM_WATCHDOG=0/1 overrides watchdog_enabled without editing toml.
    watchdog_enabled: bool = True
    watchdog_interval_ms: int = 1000
    # deadline for pipeline-submitted futures and the fast-sync verify
    # window await; 0 disables future deadlines
    watchdog_future_deadline_ms: int = 10_000
    # consensus height unchanged for this long -> a health stall is
    # recorded (metric + trace instant; no restart). 0 disables.
    watchdog_height_stall_ms: int = 120_000
    # Circuit-breaker defaults for the device engines (verifier tables,
    # merkle compile, merkle device path): consecutive failures before
    # tripping open, and how long before a half-open recovery probe.
    breaker_failure_threshold: int = 3
    breaker_cooldown_ms: int = 30_000
    # Batched light-client verification service (lightserve/): the node
    # serves verified headers to a fleet of thin clients — concurrent
    # verify requests coalesce into device-sized commit bundles
    # (bundle_rows signature rows max; the aggregator lingers flush_ms
    # so a thundering herd lands in one dispatch) behind a shared
    # verified-header store with single-flight bisection. laddr = a
    # dedicated RPC endpoint for the fleet ("" = routes only on the
    # main RPC). See docs/light-service.md.
    lightserve_enabled: bool = False
    lightserve_laddr: str = ""
    lightserve_bundle_rows: int = 4096
    lightserve_flush_ms: int = 2
    # Batched mempool admission (ingest/): concurrent broadcast_tx_* /
    # gossip CheckTx calls coalesce into bundles — tx keys hash in one
    # device SHA-256 call (above ingest_hash_threshold rows), signature
    # rows pre-verify through the pipelined provider + SigCache, then
    # admission replays the serial order. The dispatch task lingers
    # ingest_flush_ms so a herd of concurrent submitters lands in one
    # bundle (bounded by ingest_bundle_txs). See docs/ingest.md.
    ingest_enabled: bool = True
    ingest_bundle_txs: int = 256
    ingest_flush_ms: int = 2
    ingest_hash_threshold: int = 64
    # BLS12-381 signature aggregation (crypto/bls.py, models/bls.py;
    # docs/bls-aggregation.md): bls_device enables the batched device
    # kernels (pairing checks, hash-to-G2 maps, aggregate-pubkey sums)
    # behind the breaker-gated host-oracle fallback; buckets compile
    # lazily on the first BLS row, so an all-ed25519 chain never pays a
    # BLS compile. bls_device_rows is the minimum batch before the
    # device path engages (below it, the pure-Python oracle wins on
    # dispatch overhead). TM_BLS_DEVICE / TM_BLS_DEVICE_ROWS override
    # without editing toml. priv_validator_key_type selects the scheme
    # for a FRESHLY GENERATED validator key ("ed25519" | "bls12-381");
    # existing key files keep their recorded type.
    bls_device: bool = True
    bls_device_rows: int = 2
    priv_validator_key_type: str = "ed25519"
    # Batched block execution (state/parallel_exec.py; docs/execution.md):
    # exec_parallel delivers a block's txs as chunked DeliverBatch
    # requests — batch-aware apps answer with ONE device signature
    # bundle / hash bundle plus an optimistic-parallel apply whose
    # results are bit-identical to the serial DeliverTx loop; any batch
    # failure degrades to per-tx delivery. exec_batch_txs bounds the
    # txs per request. TM_EXEC=0 is the kill switch (no toml edit).
    exec_parallel: bool = True
    exec_batch_txs: int = 256

    def genesis_file(self) -> str:
        return _rootify(self.genesis_file_name, self.root_dir)

    def priv_validator_key_file(self) -> str:
        return _rootify(self.priv_validator_key_name, self.root_dir)

    def priv_validator_state_file(self) -> str:
        return _rootify(self.priv_validator_state_name, self.root_dir)

    def node_key_file(self) -> str:
        return _rootify(self.node_key_name, self.root_dir)

    def db_path(self) -> str:
        return _rootify(self.db_dir, self.root_dir)

    def validate_basic(self) -> Optional[str]:
        if self.db_backend not in ("sqlite", "memdb"):
            return f"unknown db_backend {self.db_backend!r}"
        if self.abci not in ("local", "socket", "grpc"):
            return f"unknown abci transport {self.abci!r}"
        if self.crypto_pipeline_depth < 1:
            return "crypto_pipeline_depth must be >= 1"
        if self.crypto_pipeline_flush_ms < 0:
            return "crypto_pipeline_flush_ms can't be negative"
        if self.crypto_mesh_devices < 0:
            return "crypto_mesh_devices can't be negative"
        if self.mesh_min_rows < 1:
            return "mesh_min_rows must be >= 1"
        if self.merkle_device_threshold < 2:
            return "merkle_device_threshold must be >= 2"
        if self.trace_buffer_events < 1:
            return "trace_buffer_events must be >= 1"
        if self.flightrec_events < 1:
            return "flightrec_events must be >= 1"
        if self.watchdog_interval_ms < 1:
            return "watchdog_interval_ms must be >= 1"
        if self.watchdog_future_deadline_ms < 0:
            return "watchdog_future_deadline_ms can't be negative"
        if self.watchdog_height_stall_ms < 0:
            return "watchdog_height_stall_ms can't be negative"
        if self.breaker_failure_threshold < 1:
            return "breaker_failure_threshold must be >= 1"
        if self.breaker_cooldown_ms < 0:
            return "breaker_cooldown_ms can't be negative"
        if self.lightserve_bundle_rows < 1:
            return "lightserve_bundle_rows must be >= 1"
        if self.lightserve_flush_ms < 0:
            return "lightserve_flush_ms can't be negative"
        if self.ingest_bundle_txs < 1:
            return "ingest_bundle_txs must be >= 1"
        if self.ingest_flush_ms < 0:
            return "ingest_flush_ms can't be negative"
        if self.ingest_hash_threshold < 1:
            return "ingest_hash_threshold must be >= 1"
        if self.bls_device_rows < 1:
            return "bls_device_rows must be >= 1"
        if self.priv_validator_key_type not in ("ed25519", "bls12-381"):
            return f"unknown priv_validator_key_type {self.priv_validator_key_type!r}"
        if self.exec_batch_txs < 1:
            return "exec_batch_txs must be >= 1"
        return None


@dataclass
class RPCConfig:
    """Reference RPCConfig config/config.go:326."""

    root_dir: str = ""
    laddr: str = "tcp://127.0.0.1:26657"
    cors_allowed_origins: List[str] = field(default_factory=list)
    cors_allowed_methods: List[str] = field(default_factory=lambda: ["HEAD", "GET", "POST"])
    cors_allowed_headers: List[str] = field(
        default_factory=lambda: ["Origin", "Accept", "Content-Type", "X-Requested-With", "X-Server-Time"]
    )
    grpc_laddr: str = ""
    grpc_max_open_connections: int = 900
    unsafe: bool = False
    max_open_connections: int = 900
    max_subscription_clients: int = 100
    max_subscriptions_per_client: int = 5
    timeout_broadcast_tx_commit_ms: int = 10_000
    max_body_bytes: int = 1_000_000
    max_header_bytes: int = 1 << 20

    def validate_basic(self) -> Optional[str]:
        if self.grpc_max_open_connections < 0:
            return "grpc_max_open_connections can't be negative"
        if self.max_open_connections < 0:
            return "max_open_connections can't be negative"
        if self.max_subscription_clients < 0:
            return "max_subscription_clients can't be negative"
        if self.max_subscriptions_per_client < 0:
            return "max_subscriptions_per_client can't be negative"
        if self.timeout_broadcast_tx_commit_ms < 0:
            return "timeout_broadcast_tx_commit can't be negative"
        if self.max_body_bytes < 0:
            return "max_body_bytes can't be negative"
        return None


@dataclass
class P2PConfig:
    """Reference P2PConfig config/config.go:480."""

    root_dir: str = ""
    laddr: str = "tcp://0.0.0.0:26656"
    external_address: str = ""
    seeds: str = ""  # comma-separated
    persistent_peers: str = ""
    upnp: bool = False
    addr_book_file: str = os.path.join(DEFAULT_CONFIG_DIR, DEFAULT_ADDR_BOOK_FILE)
    addr_book_strict: bool = True
    max_num_inbound_peers: int = 40
    max_num_outbound_peers: int = 10
    unconditional_peer_ids: str = ""
    persistent_peers_max_dial_period_ms: int = 0
    flush_throttle_timeout_ms: int = 100
    max_packet_msg_payload_size: int = 1024
    send_rate: int = 5_120_000  # bytes/s
    recv_rate: int = 5_120_000
    pex: bool = True
    seed_mode: bool = False
    private_peer_ids: str = ""
    allow_duplicate_ip: bool = False
    handshake_timeout_ms: int = 20_000
    dial_timeout_ms: int = 3_000
    test_fuzz: bool = False
    test_fuzz_config: "FuzzConnConfig" = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.test_fuzz_config is None:
            self.test_fuzz_config = FuzzConnConfig()

    def addr_book_path(self) -> str:
        return _rootify(self.addr_book_file, self.root_dir)

    def validate_basic(self) -> Optional[str]:
        if self.max_num_inbound_peers < 0:
            return "max_num_inbound_peers can't be negative"
        if self.max_num_outbound_peers < 0:
            return "max_num_outbound_peers can't be negative"
        if self.flush_throttle_timeout_ms < 0:
            return "flush_throttle_timeout can't be negative"
        if self.max_packet_msg_payload_size < 0:
            return "max_packet_msg_payload_size can't be negative"
        if self.send_rate < 0:
            return "send_rate can't be negative"
        if self.recv_rate < 0:
            return "recv_rate can't be negative"
        return None


@dataclass
class FuzzConnConfig:
    """Reference FuzzConnConfig config/config.go:626."""

    mode: str = "drop"  # drop | delay
    max_delay_ms: int = 3_000
    prob_drop_rw: float = 0.2
    prob_drop_conn: float = 0.0
    prob_sleep: float = 0.0


@dataclass
class MempoolConfig:
    """Reference MempoolConfig config/config.go:646."""

    root_dir: str = ""
    recheck: bool = True
    broadcast: bool = True
    wal_dir: str = ""
    size: int = 5_000
    max_txs_bytes: int = 1_073_741_824  # 1GB
    cache_size: int = 10_000
    max_tx_bytes: int = 1_048_576  # 1MB
    # QoS lane (docs/ingest.md): priority-ordered reap + lane-aware
    # eviction — when the pool is full, a tx whose app-assigned
    # priority (ResponseCheckTx.priority, e.g. the payments fee)
    # strictly outranks resident entries evicts them instead of being
    # rejected, so paid traffic survives spam floods. max_txs_per_sender
    # bounds pending txs per app-declared sender (0 = uncapped).
    priority_lanes: bool = True
    max_txs_per_sender: int = 0

    def wal_dir_path(self) -> str:
        return _rootify(self.wal_dir, self.root_dir) if self.wal_dir else ""

    def wal_enabled(self) -> bool:
        return self.wal_dir != ""

    def validate_basic(self) -> Optional[str]:
        if self.size < 0:
            return "size can't be negative"
        if self.max_txs_bytes < 0:
            return "max_txs_bytes can't be negative"
        if self.cache_size < 0:
            return "cache_size can't be negative"
        if self.max_tx_bytes < 0:
            return "max_tx_bytes can't be negative"
        if self.max_txs_per_sender < 0:
            return "max_txs_per_sender can't be negative"
        return None


@dataclass
class FastSyncConfig:
    """Reference FastSyncConfig config/config.go:708.

    Engine selection, matching the reference's generations (one wire
    protocol, blockchain/messages.py):

    - "v0": the requester/pool engine (blockchain/pool.py +
      reactor_v0.py) — per-height requesters, timeout redo, deliverer
      punishment, per-pair verification (blockchain/v0/pool.go).
    - "v2" (default) and "v1" (same FSM generation): the pure-FSM
      scheduler + processor (blockchain/scheduler.py + reactor.py)
      with cross-height BATCHED commit verification — the TPU-first
      redesign (blockchain/v2/scheduler.go)."""

    version: str = "v2"

    def validate_basic(self) -> Optional[str]:
        if self.version not in ("v0", "v1", "v2"):
            return f"unknown fastsync version {self.version!r}"
        return None


@dataclass
class ConsensusConfig:
    """Reference ConsensusConfig config/config.go:749-800. All *_ms
    fields are milliseconds; *_delta_ms grow the timeout per round."""

    root_dir: str = ""
    wal_file_name: str = os.path.join(DEFAULT_DATA_DIR, "cs.wal", "wal")
    timeout_propose_ms: int = 3_000
    timeout_propose_delta_ms: int = 500
    timeout_prevote_ms: int = 1_000
    timeout_prevote_delta_ms: int = 500
    timeout_precommit_ms: int = 1_000
    timeout_precommit_delta_ms: int = 500
    timeout_commit_ms: int = 1_000
    skip_timeout_commit: bool = False
    create_empty_blocks: bool = True
    create_empty_blocks_interval_ms: int = 0
    peer_gossip_sleep_duration_ms: int = 100
    peer_query_maj23_sleep_duration_ms: int = 2_000

    def wal_file(self) -> str:
        return _rootify(self.wal_file_name, self.root_dir)

    # -- timeout schedule (reference config/config.go:846-886) -------------

    def propose_s(self, round_: int) -> float:
        return (self.timeout_propose_ms + self.timeout_propose_delta_ms * round_) / 1000.0

    def prevote_s(self, round_: int) -> float:
        return (self.timeout_prevote_ms + self.timeout_prevote_delta_ms * round_) / 1000.0

    def precommit_s(self, round_: int) -> float:
        return (self.timeout_precommit_ms + self.timeout_precommit_delta_ms * round_) / 1000.0

    def commit_s(self) -> float:
        return self.timeout_commit_ms / 1000.0

    def empty_blocks_interval_s(self) -> float:
        return self.create_empty_blocks_interval_ms / 1000.0

    def validate_basic(self) -> Optional[str]:
        for name in (
            "timeout_propose_ms",
            "timeout_propose_delta_ms",
            "timeout_prevote_ms",
            "timeout_prevote_delta_ms",
            "timeout_precommit_ms",
            "timeout_precommit_delta_ms",
            "timeout_commit_ms",
            "create_empty_blocks_interval_ms",
            "peer_gossip_sleep_duration_ms",
            "peer_query_maj23_sleep_duration_ms",
        ):
            if getattr(self, name) < 0:
                return f"{name} can't be negative"
        return None


@dataclass
class TxIndexConfig:
    """Reference TxIndexConfig config/config.go:898."""

    indexer: str = "kv"  # kv | null
    index_keys: str = ""
    index_all_keys: bool = False


@dataclass
class InstrumentationConfig:
    """Reference InstrumentationConfig config/config.go:935."""

    prometheus: bool = False
    prometheus_listen_addr: str = ":26660"
    max_open_connections: int = 3
    namespace: str = "tendermint"


@dataclass
class PrivValidatorConfig:
    """Remote-signer client knobs (subset of BaseConfig in the reference,
    split out for clarity)."""

    laddr: str = ""


@dataclass
class Config:
    """Reference Config config/config.go:60-72."""

    base: BaseConfig = field(default_factory=BaseConfig)
    rpc: RPCConfig = field(default_factory=RPCConfig)
    p2p: P2PConfig = field(default_factory=P2PConfig)
    mempool: MempoolConfig = field(default_factory=MempoolConfig)
    fastsync: FastSyncConfig = field(default_factory=FastSyncConfig)
    consensus: ConsensusConfig = field(default_factory=ConsensusConfig)
    tx_index: TxIndexConfig = field(default_factory=TxIndexConfig)
    instrumentation: InstrumentationConfig = field(default_factory=InstrumentationConfig)

    def set_root(self, root: str) -> "Config":
        self.base.root_dir = root
        self.rpc.root_dir = root
        self.p2p.root_dir = root
        self.mempool.root_dir = root
        self.consensus.root_dir = root
        return self

    @property
    def root_dir(self) -> str:
        return self.base.root_dir

    def validate_basic(self) -> Optional[str]:
        for name, sec in (
            ("base", self.base),
            ("rpc", self.rpc),
            ("p2p", self.p2p),
            ("mempool", self.mempool),
            ("fastsync", self.fastsync),
            ("consensus", self.consensus),
        ):
            err = sec.validate_basic()
            if err:
                return f"error in [{name}] section: {err}"
        return None


def default_config() -> Config:
    return Config()


def test_config() -> Config:
    """Fast preset for tests (reference TestConfig config/config.go:107):
    aggressive timeouts so in-process consensus nets converge quickly."""
    cfg = Config()
    cfg.base.chain_id = "tendermint_test"
    cfg.base.proxy_app = "kvstore"
    cfg.base.fast_sync = False
    cfg.base.db_backend = "memdb"
    # cpu: in-process test nets must not pay XLA compiles; the TPU
    # provider path has its own dedicated integration test
    cfg.base.crypto_provider = "cpu"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.allow_duplicate_ip = True
    cfg.p2p.flush_throttle_timeout_ms = 10
    cfg.consensus.timeout_propose_ms = 400
    cfg.consensus.timeout_propose_delta_ms = 100
    cfg.consensus.timeout_prevote_ms = 200
    cfg.consensus.timeout_prevote_delta_ms = 100
    cfg.consensus.timeout_precommit_ms = 200
    cfg.consensus.timeout_precommit_delta_ms = 100
    cfg.consensus.timeout_commit_ms = 20
    cfg.consensus.skip_timeout_commit = True
    cfg.consensus.peer_gossip_sleep_duration_ms = 5
    cfg.consensus.peer_query_maj23_sleep_duration_ms = 250
    return cfg


# -- ensure directory layout (reference EnsureRoot config/toml.go:21) ------


def ensure_root(root: str) -> None:
    os.makedirs(os.path.join(root, DEFAULT_CONFIG_DIR), exist_ok=True)
    os.makedirs(os.path.join(root, DEFAULT_DATA_DIR), exist_ok=True)


# -- TOML round-trip -------------------------------------------------------

_SECTIONS = (
    ("rpc", "rpc"),
    ("p2p", "p2p"),
    ("mempool", "mempool"),
    ("fastsync", "fastsync"),
    ("consensus", "consensus"),
    ("tx_index", "tx_index"),
    ("instrumentation", "instrumentation"),
)

_SKIP_FIELDS = {"root_dir", "test_fuzz_config"}


def _toml_value(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return repr(v)
    if isinstance(v, str):
        return '"' + v.replace("\\", "\\\\").replace('"', '\\"') + '"'
    if isinstance(v, list):
        return "[" + ", ".join(_toml_value(x) for x in v) + "]"
    raise TypeError(f"unsupported TOML value {v!r}")


def _render_section(obj, header: str) -> str:
    lines = [f"[{header}]"] if header else []
    for f in fields(obj):
        if f.name in _SKIP_FIELDS:
            continue
        v = getattr(obj, f.name)
        if is_dataclass(v):
            continue
        lines.append(f"{f.name} = {_toml_value(v)}")
    return "\n".join(lines) + "\n"


def write_config_file(path: str, cfg: Config) -> None:
    """Render cfg to TOML (reference WriteConfigFile config/toml.go:55)."""
    parts = [
        "# Generated by tendermint_tpu. Millisecond durations use *_ms keys.\n",
        _render_section(cfg.base, ""),
    ]
    for attr, header in _SECTIONS:
        parts.append("\n" + _render_section(getattr(cfg, attr), header))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as fp:
        fp.write("".join(parts))


def load_config(path: str) -> Config:
    try:
        import tomllib

        with open(path, "rb") as fp:
            raw = tomllib.load(fp)
    except ImportError:  # Python < 3.11: parse the subset we render
        with open(path, "r") as fp:
            raw = _parse_toml_subset(fp.read())
    cfg = Config()
    _apply(cfg.base, {k: v for k, v in raw.items() if not isinstance(v, dict)})
    for attr, header in _SECTIONS:
        if header in raw:
            _apply(getattr(cfg, attr), raw[header])
    # Ops override: force the crypto provider without editing config.toml
    # (used by CI/test rigs to pin "cpu"; mirrors 12-factor env config).
    env_provider = os.environ.get("TM_CRYPTO_PROVIDER")
    if env_provider:
        cfg.base.crypto_provider = env_provider
    # BLS device kill switch + batch floor (docs/running-in-production.md)
    env_bls = os.environ.get("TM_BLS_DEVICE")
    if env_bls is not None:
        cfg.base.bls_device = env_bls not in ("0", "false", "")
    env_bls_rows = os.environ.get("TM_BLS_DEVICE_ROWS")
    if env_bls_rows:
        try:
            cfg.base.bls_device_rows = int(env_bls_rows)
        except ValueError:
            pass
    # Mesh runtime kill switch (docs/running-in-production.md): TM_MESH=0
    # grounds every engine to single-device without editing toml;
    # TM_MESH=1 force-enables the router on a node configured off.
    env_mesh = os.environ.get("TM_MESH")
    if env_mesh is not None:
        cfg.base.mesh_enabled = env_mesh not in ("0", "false", "")
    # Batched-execution kill switch (docs/running-in-production.md):
    # TM_EXEC=0 pins every block to the serial per-tx DeliverTx path.
    env_exec = os.environ.get("TM_EXEC")
    if env_exec is not None:
        cfg.base.exec_parallel = env_exec not in ("0", "false", "")
    return cfg


def _parse_toml_subset(text: str) -> dict:
    """Minimal TOML reader for the exact subset write_config_file emits
    (flat [section]s; str/bool/int/float and flat string lists). Used
    only when stdlib tomllib (3.11+) is unavailable."""
    import ast

    root: dict = {}
    cur = root
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("[") and line.endswith("]"):
            cur = root.setdefault(line[1:-1].strip(), {})
            continue
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip()
        if not _:
            continue
        if val.startswith("'"):
            # TOML literal string: NO escape processing (ast would
            # reinterpret backslashes)
            end = val.find("'", 1)
            if end < 0:
                raise ValueError(f"unterminated string for {key!r}")
            cur[key] = val[1:end]
            continue
        if val.startswith('"'):
            # scan to the closing unescaped quote so a trailing
            # comment is not swallowed into the value
            i = 1
            while i < len(val):
                if val[i] == "\\":
                    i += 2
                    continue
                if val[i] == '"':
                    break
                i += 1
            cur[key] = ast.literal_eval(val[: i + 1])
            continue
        # non-string value: an inline comment is not part of it
        val = val.split("#", 1)[0].strip()
        if val in ("true", "false"):
            cur[key] = val == "true"
        else:
            # lists/numbers as rendered by _toml_value are valid
            # Python literals
            cur[key] = ast.literal_eval(val)
    return root


def _apply(obj, d: dict) -> None:
    names = {f.name for f in fields(obj)}
    for k, v in d.items():
        if k in names and k not in _SKIP_FIELDS:
            setattr(obj, k, v)


def _rootify(path: str, root: str) -> str:
    if os.path.isabs(path):
        return path
    return os.path.join(root, path)
