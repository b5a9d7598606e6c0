"""Node: constructor-injection of the entire stack.

Reference: node/node.go — NewNode :565 (wiring order: DBs → state/genesis
→ proxyApp → eventBus/indexer → handshake → mempool/evidence/blockExec →
bcReactor → consensus reactor → transport → switch → dial persistent),
DefaultNewNode :90, OnStart :760 (RPC before p2p), makeNodeInfo :1090.
"""

from __future__ import annotations

import asyncio
import os
from typing import Optional

from tendermint_tpu.abci.client.local import LocalClient
from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.config import Config
from tendermint_tpu.consensus.reactor import ConsensusReactor
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.consensus.wal import BaseWAL
from tendermint_tpu.db.base import DB
from tendermint_tpu.db.memdb import MemDB
from tendermint_tpu.db.sqlitedb import SQLiteDB
from tendermint_tpu.evidence import EvidencePool, EvidenceReactor
from tendermint_tpu.mempool import Mempool
from tendermint_tpu.mempool.reactor import MempoolReactor
from tendermint_tpu.p2p.key import NodeKey, load_or_gen_node_key
from tendermint_tpu.p2p.netaddress import NetAddress
from tendermint_tpu.p2p.node_info import NodeInfo
from tendermint_tpu.p2p.switch import Switch
from tendermint_tpu.p2p.transport import Transport
from tendermint_tpu.privval import load_or_gen_file_pv
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import State, state_from_genesis_doc
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.state.txindex import (
    IndexerService,
    KVTxIndexer,
    NullTxIndexer,
)
from tendermint_tpu.store.block_store import BlockStore
from tendermint_tpu.types.events import EventBus
from tendermint_tpu.types.genesis import GenesisDoc
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.service import Service
from tendermint_tpu.version import TM_CORE_SEMVER


def make_db(name: str, config: Config) -> DB:
    if config.base.db_backend == "memdb":
        return MemDB()
    return SQLiteDB(name, config.base.db_path())


def default_app(config: Config):
    """Local in-process app from config.proxy_app (reference
    proxy.DefaultClientCreator proxy/client.go:66)."""
    spec = config.base.proxy_app
    if spec == "kvstore":
        from tendermint_tpu.abci.examples.kvstore import KVStoreApplication

        return KVStoreApplication()
    if spec == "persistent_kvstore":
        from tendermint_tpu.abci.examples.kvstore import PersistentKVStoreApplication

        return PersistentKVStoreApplication(make_db("app", config))
    if spec == "counter":
        from tendermint_tpu.abci.examples.counter import CounterApplication

        return CounterApplication()
    if spec == "payments":
        from tendermint_tpu.abci.examples.payments import PaymentsApplication

        return PaymentsApplication()
    if spec == "kvproofs":
        from tendermint_tpu.abci.examples.kvproofs import KVProofsApplication

        return KVProofsApplication()
    if spec == "noop":
        from tendermint_tpu.abci.application import Application

        return Application()
    raise ValueError(f"unknown local proxy_app {spec!r} (socket transport: todo)")


class Node(Service):
    """Reference node.Node (node/node.go:60 region)."""

    def __init__(
        self,
        config: Config,
        genesis_doc: GenesisDoc,
        priv_validator,
        node_key: NodeKey,
        app=None,
        logger=None,
    ):
        super().__init__("node")
        self.config = config
        self.genesis_doc = genesis_doc
        self.node_key = node_key
        self.logger = logger or get_logger("node")

        # -- flight recorder (utils/trace.py) --------------------------------
        # Configured FIRST so provider/engine construction below already
        # records into the ring. TM_TRACE=0/1 overrides config inside
        # configure() (the ops kill switch).
        from tendermint_tpu.utils import trace as _trace

        _trace.configure(
            enabled=config.base.trace_enabled,
            buffer_events=config.base.trace_buffer_events,
            # cross-node identity: stamps exported traces and every
            # gossip OriginContext this node emits (docs/tracing.md)
            node_id=node_key.id[:12],
        )

        # -- robustness layer (utils/faultinject.py + utils/watchdog.py) -----
        # Breaker defaults must land BEFORE the engines below construct
        # their breakers' first transitions; fault injection is armed by
        # TM_FAULTS (parsed at import) — log it loudly so a chaos rig
        # left enabled is visible at boot.
        from tendermint_tpu.utils import faultinject as _faults
        from tendermint_tpu.utils import watchdog as _watchdog

        _watchdog.set_breaker_defaults(
            failure_threshold=config.base.breaker_failure_threshold,
            cooldown_s=config.base.breaker_cooldown_ms / 1000.0,
        )
        if _faults.enabled():
            self.logger.error(
                "FAULT INJECTION ARMED", sites=_faults.get_registry().armed()
            )
        # TM_WATCHDOG=0/1 overrides config (ops kill switch, like TM_TRACE)
        _env_wd = os.environ.get("TM_WATCHDOG")
        wd_enabled = (
            config.base.watchdog_enabled if _env_wd in (None, "") else _env_wd == "1"
        )
        self.watchdog: Optional[_watchdog.Watchdog] = (
            _watchdog.Watchdog(
                interval_s=config.base.watchdog_interval_ms / 1000.0,
                logger=self.logger,
            )
            if wd_enabled
            else None
        )
        self._future_deadline_s: Optional[float] = (
            config.base.watchdog_future_deadline_ms / 1000.0
            if config.base.watchdog_future_deadline_ms > 0
            else None
        )

        # -- mesh runtime (parallel/topology.py) -----------------------------
        # ONE topology + router shared by every device engine below, so
        # the engines share the same admitted set: a chip a chunked
        # engine blames is excluded from the verifier's shard_map mesh
        # too. Built AFTER set_breaker_defaults so the per-device
        # mesh.device<i> breakers inherit the configured thresholds.
        # mesh_enabled rides config (TM_MESH kill switch applied in
        # load_config); crypto_mesh_devices caps the inventory.
        self.mesh_router = None
        if config.base.mesh_enabled:
            from tendermint_tpu.parallel import DeviceTopology, MeshRouter

            topo = DeviceTopology.discover(
                max_devices=config.base.crypto_mesh_devices
            )
            if topo is None:
                self.logger.error(
                    "mesh_enabled but no jax backend; running single-device"
                )
            else:
                self.mesh_router = MeshRouter(
                    topo,
                    min_rows=config.base.mesh_min_rows,
                    logger=self.logger,
                )
                self.logger.info(
                    "mesh runtime",
                    devices=len(topo),
                    platform=topo.platform,
                    min_rows=config.base.mesh_min_rows,
                )

        # -- crypto provider (the BASELINE.json plugin seam) ----------------
        # Every VerifyCommit / VoteSet ingest / light-client call in this
        # process drains through this provider (reference behavior is the
        # serial loop at types/validator_set.go:641; provider "tpu" is the
        # batched device redesign). block_on_compile=False: a live node
        # must never stall consensus on an XLA compile — cold buckets are
        # verified on host while the device program compiles in the
        # background (models/verifier.py).
        from tendermint_tpu.crypto.batch import make_provider, set_default_provider

        mesh = None
        if (
            config.base.crypto_provider == "tpu"
            and config.base.crypto_mesh_devices > 1
        ):
            mesh = self._build_crypto_mesh(config.base.crypto_mesh_devices)
        self.crypto_provider = make_provider(
            config.base.crypto_provider,
            mesh=mesh,
            block_on_compile=False,
            router=self.mesh_router,
        )
        if config.base.crypto_pipeline:
            # pipelined dispatch layer (crypto/pipeline.py): future-based
            # micro-batching + the gossip dedupe cache. The wrapper IS a
            # BatchVerifier, so every verify site below routes through
            # its shared queue; on_stop drains it.
            from tendermint_tpu.crypto.pipeline import PipelinedVerifier

            self.crypto_provider = PipelinedVerifier(
                self.crypto_provider,
                depth=config.base.crypto_pipeline_depth,
                flush_deadline_s=config.base.crypto_pipeline_flush_ms / 1000.0,
            )
            if self.watchdog is not None:
                # supervise the dispatch/exec threads (restart-on-death)
                # and bound every submitted future: a dead exec thread
                # can strand a bundle; the deadline fails those futures
                # and callers fall back to serial verify
                self.crypto_provider.attach_watchdog(
                    self.watchdog, deadline_s=self._future_deadline_s
                )
        set_default_provider(self.crypto_provider)
        self.logger.info(
            "crypto provider",
            name=self.crypto_provider.name,
            mesh_devices=0 if mesh is None else mesh.devices.size,
        )

        # -- BLS aggregation track (crypto/bls.py; docs/bls-aggregation.md)
        # The provider behind every BLS validator row and every
        # AggregatedCommit check. Device kernels compile LAZILY on the
        # first BLS row, so an all-ed25519 chain pays nothing; the
        # host oracle is the breaker-gated fallback either way.
        from tendermint_tpu.crypto.bls import (
            make_bls_provider,
            set_default_bls_provider,
        )

        self.bls_provider = make_bls_provider(
            device=config.base.bls_device, router=self.mesh_router
        )
        self.bls_provider.min_device_rows = config.base.bls_device_rows
        set_default_bls_provider(self.bls_provider)

        # -- device merkle engine (crypto/merkle.py seam) --------------------
        # Tx roots / part-set roots / validator-set hashes with at least
        # merkle_device_threshold leaves batch onto the accelerator;
        # non-blocking like the verifier — a cold size-bucket hashes on
        # host while its dispatch chain compiles in the background.
        from tendermint_tpu.crypto import merkle as _merkle

        # TM_MERKLE_DEVICE=0/1 is the ops kill switch (mirrors
        # TM_CRYPTO_PROVIDER): it overrides config without editing toml.
        _env_merkle = os.environ.get("TM_MERKLE_DEVICE")
        # effective state is remembered so the boot-time warmup gate
        # agrees with the kill switch, not just with config.toml
        self._merkle_enabled = (
            config.base.merkle_device if _env_merkle is None else _env_merkle == "1"
        )
        _merkle.configure_device(
            enabled=self._merkle_enabled,
            threshold=config.base.merkle_device_threshold,
            block_on_compile=False,
            router=self.mesh_router,
        )

        # -- storage -------------------------------------------------------
        self.block_store = BlockStore(make_db("blockstore", config))
        self.state_store = StateStore(make_db("state", config))
        state = self.state_store.load()
        if state is None:
            state = state_from_genesis_doc(genesis_doc)
            self.state_store.save(state)

        # -- app -----------------------------------------------------------
        if app is not None or config.base.abci == "local":
            self.app = app if app is not None else default_app(config)
            self.proxy_app = LocalClient(self.app)
        elif config.base.abci == "socket":
            # remote app over the ABCI socket protocol (reference
            # proxy.DefaultClientCreator remote path, proxy/client.go:75)
            from tendermint_tpu.abci.client.socket import SocketClient

            self.app = None
            self.proxy_app = SocketClient(config.base.proxy_app)
        elif config.base.abci == "grpc":
            # remote app over gRPC (reference abci/client/grpc_client.go)
            from tendermint_tpu.abci.client.grpc import GRPCClient

            self.app = None
            self.proxy_app = GRPCClient(config.base.proxy_app)
        else:
            raise ValueError(f"unknown abci transport {config.base.abci!r}")

        # -- event bus + indexer --------------------------------------------
        self.event_bus = EventBus()
        if config.tx_index.indexer == "kv":
            self.tx_indexer = KVTxIndexer(
                make_db("tx_index", config),
                index_all_keys=config.tx_index.index_all_keys or not config.tx_index.index_keys,
                index_keys=set(
                    k.strip() for k in config.tx_index.index_keys.split(",") if k.strip()
                ),
            )
        else:
            self.tx_indexer = NullTxIndexer()
        self.indexer_service = IndexerService(self.tx_indexer, self.event_bus)

        self._state_at_boot = state
        self.priv_validator = priv_validator

        # -- mempool / evidence / exec (wired in on_start after handshake) --
        self.mempool = Mempool(
            config.mempool,
            self.proxy_app,
            # crypto-free priority bound (docs/ingest.md): a full pool
            # rejects un-outranking floods before the app round trip
            priority_hint=getattr(self.app, "admission_priority_hint", None),
        )
        self.evidence_pool = EvidencePool(
            make_db("evidence", config), self.state_store, self.block_store
        )
        self.block_exec = BlockExecutor(
            self.state_store,
            self.proxy_app,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            exec_parallel=config.base.exec_parallel,
            exec_batch_txs=config.base.exec_batch_txs,
        )
        # app-zoo device seams for DeliverBatch (docs/execution.md): a
        # local app exposing batch_verifier gets the shared pipelined
        # provider (SigCache-warm from admission); one exposing
        # batch_hasher gets a device tx-key hasher for value digests
        if getattr(self.app, "batch_verifier", False) is None:
            self.app.batch_verifier = self.crypto_provider

        # -- batched ingest (ingest/batcher.py; docs/ingest.md) -------------
        # The mempool's admission front door: concurrent broadcast_tx_* /
        # gossip CheckTx calls coalesce into bundles — tx keys hash in one
        # device SHA-256 call, signature rows (apps exposing
        # admission_sig_rows, e.g. payments) pre-verify through the
        # pipelined provider's SigCache. The dispatch task starts lazily
        # on the first submission (needs the running loop).
        self.ingest = None
        if config.base.ingest_enabled:
            from tendermint_tpu.ingest import IngestBatcher
            from tendermint_tpu.ingest.hashing import TxKeyHasher

            self.ingest = IngestBatcher(
                self.mempool,
                # mesh-aware tx-key hasher: leaf SHA-256 shards across the
                # router's admitted devices (single-device when no mesh)
                hasher=TxKeyHasher(
                    block_on_compile=False, router=self.mesh_router
                ),
                verifier=self.crypto_provider,
                sig_extractor=getattr(self.app, "admission_sig_rows", None),
                bundle_txs=config.base.ingest_bundle_txs,
                flush_s=config.base.ingest_flush_ms / 1000.0,
                hash_threshold=config.base.ingest_hash_threshold,
                logger=self.logger,
            )
        if getattr(self.app, "batch_hasher", False) is None and self.ingest is not None:
            self.app.batch_hasher = self.ingest.hasher

        self.consensus_state: Optional[ConsensusState] = None
        self.consensus_reactor: Optional[ConsensusReactor] = None
        self.bc_reactor: Optional[BlockchainReactor] = None
        self.mempool_reactor = MempoolReactor(
            config.mempool, self.mempool, ingest=self.ingest
        )
        self.evidence_reactor = EvidenceReactor(self.evidence_pool)

        # -- p2p -----------------------------------------------------------
        # connection filters run BEFORE the secret handshake (reference
        # node.go:416-483 MultiplexTransportConnFilters; the duplicate-
        # IP filter is registered iff allow_duplicate_ip is false, :425)
        conn_filters = []
        if not config.p2p.allow_duplicate_ip:
            from tendermint_tpu.p2p.transport import conn_duplicate_ip_filter

            conn_filters.append(conn_duplicate_ip_filter)
        self.transport = Transport(
            node_key,
            self._make_node_info,
            handshake_timeout_s=config.p2p.handshake_timeout_ms / 1000.0,
            dial_timeout_s=config.p2p.dial_timeout_ms / 1000.0,
            conn_filters=conn_filters,
            # chaos wrapper (reference p2p/fuzz.go wiring): every
            # upgraded conn rides a FuzzedConnection when test_fuzz is
            # on, seeded from the chaos rig's one knob (TM_FAULTS_SEED)
            # so a fuzz-found failure replays deterministically
            fuzz_config=(
                config.p2p.test_fuzz_config if config.p2p.test_fuzz else None
            ),
            fuzz_seed=_faults.global_seed(),
        )
        self.switch = Switch(self.transport, config=config.p2p)

        self.rpc_server = None  # attached by configure_rpc when rpc is enabled

        # metrics (reference MetricsProvider node/node.go:126-140)
        from tendermint_tpu.utils.metrics import (
            ConsensusMetrics,
            MempoolMetrics,
            MetricsServer,
            P2PMetrics,
            Registry,
            StateMetrics,
        )

        from tendermint_tpu.utils.metrics import (
            BLSMetrics,
            ByzMetrics,
            CryptoMetrics,
            EngineMetrics,
            ExecMetrics,
            HealthMetrics,
            IngestMetrics,
            LightServeMetrics,
            MerkleMetrics,
            MeshMetrics,
            StallMetrics,
            TraceMetrics,
        )

        self.metrics_registry = Registry()
        ns = config.instrumentation.namespace
        self.consensus_metrics = ConsensusMetrics(self.metrics_registry, ns)
        self.p2p_metrics = P2PMetrics(self.metrics_registry, ns)
        self.mempool_metrics = MempoolMetrics(self.metrics_registry, ns)
        self.state_metrics = StateMetrics(self.metrics_registry, ns)
        self.crypto_metrics = CryptoMetrics(self.metrics_registry, ns)
        self.merkle_metrics = MerkleMetrics(self.metrics_registry, ns)
        self.trace_metrics = TraceMetrics(self.metrics_registry, ns)
        self.health_metrics = HealthMetrics(self.metrics_registry, ns)
        # consensus stall autopsy (consensus/flightrec.py StallTracker):
        # fed from the watchdog height probe through the metrics pump
        self.stall_metrics = StallMetrics(self.metrics_registry, ns)
        self.stall_tracker = None  # built in on_start with the cs
        self._breaker_last = {}  # (trips, recoveries) per breaker, pump-diffed
        # byzantine-defense family (p2p PeerGuard + consensus backstop):
        # tendermint_byz_* malformed/floods/future-drops/quarantines
        self.byz_metrics = ByzMetrics(self.metrics_registry, ns)
        self._quarantines_last = 0  # pump-diffed into peer.quarantine events
        self.lightserve_metrics = LightServeMetrics(self.metrics_registry, ns)
        self.ingest_metrics = IngestMetrics(self.metrics_registry, ns)
        self.bls_metrics = BLSMetrics(self.metrics_registry, ns)
        # batched block-execution telemetry (state/execution.py
        # exec_stats): tendermint_exec_* batches/conflicts/rows
        self.exec_metrics = ExecMetrics(self.metrics_registry, ns)
        # direct handle for the batch-size histogram (the ingest
        # bundle-size pattern: distributions can't ride snapshot deltas)
        self.block_exec.exec_metrics = self.exec_metrics
        # unified engine telemetry (models/telemetry.py protocol): the
        # cross-engine tendermint_engine_* family + the engines RPC
        self.engine_metrics = EngineMetrics(self.metrics_registry, ns)
        # mesh runtime telemetry (parallel/topology.py router stats):
        # per-device rows, breaker states, shard imbalance
        self.mesh_metrics = MeshMetrics(self.metrics_registry, ns)
        if self.ingest is not None:
            # direct handle for the bundle-size histogram (distributions
            # can't be rebuilt from snapshot deltas, the LightServe
            # bisection-depth pattern)
            self.ingest.metrics = self.ingest_metrics
        # batched light-client verification service (lightserve/):
        # constructed in on_start (it reads the block store), None when
        # lightserve_enabled is off
        self.lightserve = None
        self.lightserve_server = None
        self._block_exec_metrics_attach()
        self.metrics_server = None
        if config.instrumentation.prometheus:
            raw = config.instrumentation.prometheus_listen_addr
            if raw.startswith(":"):
                raw = "0.0.0.0" + raw
            addr = NetAddress.parse(raw)
            self.metrics_server = MetricsServer(self.metrics_registry, addr.host, addr.port)

    def _build_crypto_mesh(self, want: int):
        """Mesh over the first `want` local JAX devices, or None (logged)
        when the host has fewer. The batch axis is the only sharded axis:
        verdicts come back per row and the host tallies the quorum
        (SURVEY §5.8)."""
        try:
            import jax

            from tendermint_tpu.parallel import make_mesh

            devs = jax.devices()
            if len(devs) < want:
                self.logger.error(
                    "crypto_mesh_devices exceeds available devices; "
                    "falling back to single-device",
                    want=want, have=len(devs),
                )
                return None
            return make_mesh(devs[:want])
        except Exception as e:  # backend init failure: single-device path
            self.logger.error("crypto mesh unavailable", err=repr(e))
            return None

    def _block_exec_metrics_attach(self) -> None:
        self.block_exec._metrics = self.state_metrics

    def engine_telemetry(self) -> dict:
        """{engine: engine_stats()} over every live device engine — the
        unified telemetry protocol (models/telemetry.py). Feeds the
        tendermint_engine_* family, the ``engines`` RPC route, and the
        height ledger's per-height engine deltas. Engines that never
        engaged (no merkle hasher built, no BLS row seen, ingest off)
        simply don't appear."""
        from tendermint_tpu.crypto import merkle as _merkle
        from tendermint_tpu.models.telemetry import collect_engine_stats

        engines = [
            self.crypto_provider,
            _merkle,  # module-level wrapper: hasher + host counts + seam breaker
            getattr(self.bls_provider, "_engine", None),
        ]
        if self.ingest is not None:
            engines.append(self.ingest.hasher)
        return collect_engine_stats(engines)

    def _make_node_info(self) -> NodeInfo:
        from tendermint_tpu.blockchain.reactor import BLOCKCHAIN_CHANNEL
        from tendermint_tpu.consensus.reactor import (
            DATA_CHANNEL,
            STATE_CHANNEL,
            VOTE_CHANNEL,
            VOTE_SET_BITS_CHANNEL,
        )
        from tendermint_tpu.evidence.reactor import EVIDENCE_CHANNEL
        from tendermint_tpu.mempool.reactor import MEMPOOL_CHANNEL

        from tendermint_tpu.p2p.pex.reactor import PEX_CHANNEL

        la = self.transport.listen_addr
        channels = [
            BLOCKCHAIN_CHANNEL,
            STATE_CHANNEL,
            DATA_CHANNEL,
            VOTE_CHANNEL,
            VOTE_SET_BITS_CHANNEL,
            MEMPOOL_CHANNEL,
            EVIDENCE_CHANNEL,
        ]
        if self.config.p2p.pex:
            channels.insert(0, PEX_CHANNEL)
        return NodeInfo(
            node_id=self.node_key.id,
            listen_addr=f"{la.host}:{la.port}" if la else "",
            network=self.genesis_doc.chain_id,
            version=TM_CORE_SEMVER,
            channels=bytes(channels),
            moniker=self.config.base.moniker,
            tx_index="on" if self.config.tx_index.indexer != "null" else "off",
            rpc_address=self.config.rpc.laddr,
        )

    # -- lifecycle ---------------------------------------------------------

    async def on_start(self) -> None:
        """Reference OnStart node/node.go:760 (plus the NewNode steps that
        must run inside the event loop: app conns, handshake)."""
        from tendermint_tpu.privval.signer import SignerClient

        # Warm the device verifier in the background so the first live
        # commits hit compiled executables (VerifierModel.warmup logs
        # per-bucket compile seconds; the persistent cache makes this
        # near-instant after the first boot on a machine). Includes the
        # bucket for THIS chain's validator-set size — a 10k-validator
        # chain must not cold-start its bucket on the first live commit.
        if hasattr(self.crypto_provider, "warmup"):
            n_vals = self._state_at_boot.validators.size()
            self.crypto_provider.warmup(sizes=(16, 1024, n_vals), background=True)
        if hasattr(self.crypto_provider, "register_valset"):
            # pre-build THIS chain's per-valset cached tables so the
            # first live commit rides the tabled pipeline immediately
            key, all_pk, ed = self._state_at_boot.validators.batch_cache()
            if bool(ed.all()) and len(all_pk):
                self.crypto_provider.register_valset(key, all_pk)
        # Warm the BLS device buckets only when this chain's validator
        # set actually holds BLS keys — an all-ed25519 chain (and every
        # test rig) never pays a BLS kernel compile.
        if self.config.base.bls_device:
            _, bls_mask = self._state_at_boot.validators.bls_cache()
            if bool(bls_mask.any()):
                self.bls_provider.warmup(
                    sizes=(self.config.base.bls_device_rows,), background=True
                )
        # Warm the merkle engine's bucket for THIS chain's validator-set
        # hash only when the set is big enough to ever ride the device —
        # small chains (and test rigs) never pay a merkle compile.
        if self._merkle_enabled:
            n_vals = self._state_at_boot.validators.size()
            if n_vals >= self.config.base.merkle_device_threshold:
                from tendermint_tpu.crypto import merkle as _merkle

                _merkle.hasher_warmup(sizes=(n_vals,), background=True)

        if isinstance(self.priv_validator, SignerClient):
            # remote signer: listen and wait for it to dial in
            # (reference createAndStartPrivValidatorSocketClient node/node.go:500)
            await self.priv_validator.start()
            await self.priv_validator.wait_for_signer()

        await self.proxy_app.start()
        await self.event_bus.start()
        await self.indexer_service.start()

        # ABCI handshake: replay blocks into the app as needed
        handshaker = Handshaker(
            self.state_store, self._state_at_boot, self.block_store, self.genesis_doc,
            logger=self.logger,
        )
        await handshaker.handshake(self.proxy_app)
        state = self.state_store.load()
        self.evidence_pool.state = state

        # decide fast sync: only if we have peers to sync from and we are
        # not the sole validator (reference onlyValidatorIsUs node/node.go:314)
        fast_sync = self.config.base.fast_sync and not self._only_validator_is_us(state)

        self.consensus_state = ConsensusState(
            config=self.config.consensus,
            state=state,
            block_exec=self.block_exec,
            block_store=self.block_store,
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            priv_validator=self.priv_validator,
            event_bus=self.event_bus,
            wal=BaseWAL(self.config.consensus.wal_file()),
            metrics=self.consensus_metrics,
            # cross-node trace identity: peers link their spans back to
            # this id in a merged trace (docs/tracing.md)
            node_id=self.node_key.id[:12],
            flightrec_events=self.config.base.flightrec_events,
        )
        # crash-survivable recorder tail next to the WAL: the black box
        # persists at every height's ENDHEIGHT fsync boundary
        self.consensus_state.flightrec.attach_tail(
            self.config.consensus.wal_file() + ".flightrec"
        )
        # height ledger ← engine telemetry: each committed height's
        # report carries the engine-counter deltas over that height
        # ("verify-bundle queue+execute" attribution, consensus/ledger.py)
        from tendermint_tpu.models.telemetry import flatten_engine_counters

        self.consensus_state.ledger.engines_fn = (
            lambda: flatten_engine_counters(self.engine_telemetry())
        )
        self.consensus_metrics.fast_syncing.set(1 if fast_sync else 0)
        if not self.config.consensus.create_empty_blocks:
            self.mempool.enable_txs_available()
            self.spawn(self._txs_available_pump())
        self.consensus_reactor = ConsensusReactor(
            self.consensus_state, wait_sync=fast_sync
        )
        # engine selection (reference fast_sync.version, config.go:714):
        # v0 = requester/pool engine; v1 = event-driven FSM engine
        # (blockchain/v1.py, reference blockchain/v1/reactor_fsm.go);
        # v2 = scheduler/processor engine with batched cross-height
        # verification (the TPU-first generation, default)
        if self.config.fastsync.version == "v0":
            from tendermint_tpu.blockchain.reactor_v0 import BlockchainReactorV0

            bc_cls = BlockchainReactorV0
        elif self.config.fastsync.version == "v1":
            from tendermint_tpu.blockchain.reactor_v1 import BlockchainReactorV1

            bc_cls = BlockchainReactorV1
        else:
            bc_cls = BlockchainReactor
        bc_kwargs = {}
        if bc_cls is not BlockchainReactor:
            # v0/v1 engines take the pipelined verify window's depth
            # (the v2 engine batches cross-height on its own) and the
            # watchdog deadline on awaited commit-verify futures
            bc_kwargs = dict(
                verify_depth=self.config.base.crypto_pipeline_depth,
                provider=self.crypto_provider,
                verify_deadline_s=self._future_deadline_s,
            )
        self.bc_reactor = bc_cls(
            state,
            self.block_exec,
            self.block_store,
            fast_sync=fast_sync,
            consensus_reactor=self.consensus_reactor,
            **bc_kwargs,
        )
        self.switch.add_reactor("blockchain", self.bc_reactor)
        self.switch.add_reactor("consensus", self.consensus_reactor)
        self.switch.add_reactor("mempool", self.mempool_reactor)
        self.switch.add_reactor("evidence", self.evidence_reactor)
        if self.config.p2p.pex:
            from tendermint_tpu.p2p.pex import AddrBook, PEXReactor

            self.addr_book = AddrBook(
                self.config.p2p.addr_book_path(), strict=self.config.p2p.addr_book_strict
            )
            seeds = [
                NetAddress.parse(a.strip())
                for a in self.config.p2p.seeds.split(",")
                if a.strip()
            ]
            self.pex_reactor = PEXReactor(
                self.addr_book, seeds=seeds, seed_mode=self.config.p2p.seed_mode
            )
            self.switch.add_reactor("pex", self.pex_reactor)
        else:
            self.addr_book = None
            self.pex_reactor = None

        # -- lightserve: the node as a verify-server for thin clients ------
        # (lightserve/service.py; docs/light-service.md). Sources headers
        # straight from the local block/state stores, coalesces the
        # fleet's commit checks into device bundles THROUGH the node's
        # own pipelined provider, and shares verified headers across all
        # clients. Started before RPC so its routes are servable the
        # moment the port is open.
        if self.config.base.lightserve_enabled:
            from tendermint_tpu.lightserve.aggregator import RequestAggregator
            from tendermint_tpu.lightserve.server import make_lightserve_server
            from tendermint_tpu.lightserve.service import LightServeService, NodeSource
            from tendermint_tpu.light.store import TrustedStore

            agg = RequestAggregator(
                provider=self.crypto_provider,
                bundle_rows=self.config.base.lightserve_bundle_rows,
                flush_s=self.config.base.lightserve_flush_ms / 1000.0,
            )
            if self.watchdog is not None:
                agg.attach_watchdog(self.watchdog)
            self.lightserve = LightServeService(
                self.genesis_doc.chain_id,
                NodeSource(self),
                TrustedStore(make_db("lightserve", self.config)),
                aggregator=agg,
                metrics=self.lightserve_metrics,
                logger=self.logger,
            )
            if self.config.base.lightserve_laddr:
                self.lightserve_server = make_lightserve_server(
                    self.lightserve, self.config.base.lightserve_laddr
                )
                await self.lightserve_server.start()

        # RPC first, then p2p (reference :760 comment: "we may expose the
        # RPC without starting the switch")
        if self.rpc_server is not None:
            await self.rpc_server.start()
        if self.config.rpc.grpc_laddr:
            from tendermint_tpu.rpc.grpc_api import GRPCBroadcastServer

            self.grpc_server = GRPCBroadcastServer(self, self.config.rpc.grpc_laddr)
            await self.grpc_server.start()
        else:
            self.grpc_server = None
        if self.metrics_server is not None:
            await self.metrics_server.start()
        self.prof_server = None
        if self.config.base.prof_laddr:
            from tendermint_tpu.utils.prof import ProfServer

            raw = self.config.base.prof_laddr.replace("tcp://", "")
            if raw.startswith(":"):
                raw = "127.0.0.1" + raw
            host, port = raw.rsplit(":", 1)
            self.prof_server = ProfServer(host, int(port))
            await self.prof_server.start()
        self.spawn(self._metrics_pump())

        # -- watchdog: supervise what is now running -------------------------
        if self.watchdog is not None:
            cs = self.consensus_state
            loop = asyncio.get_running_loop()

            def _reopen_wal() -> None:
                # serialized with the loop's own writers/start —
                # BaseWAL open + tail-repair from the watchdog THREAD
                # could race consensus startup's wal.start() (is_running
                # flips before on_start opens the head) and corrupt the
                # head; the _fp re-check drops the restart if the loop
                # won that race
                def _do():
                    if cs.is_running and cs.wal is not None and cs.wal._fp is None:
                        cs.wal.start()

                loop.call_soon_threadsafe(_do)

            # WAL group: a closed/failed head while consensus runs is a
            # dead worker; restart re-opens (and tail-repairs) the head
            self.watchdog.register_worker(
                "consensus.wal",
                lambda: not cs.is_running or cs.wal is None
                or getattr(cs.wal, "_fp", object()) is not None,
                _reopen_wal,
            )
            stall_ms = self.config.base.watchdog_height_stall_ms
            if stall_ms > 0:
                # consensus-aware stall autopsy: the probe's stall edge
                # snapshots a full diagnosis (quorum arithmetic, silent
                # validators, peers/breakers/engines) served by the
                # dump_debug RPC + tendermint_stall_* family
                from tendermint_tpu.consensus.flightrec import StallTracker

                self.stall_tracker = StallTracker(
                    cs, context_fn=self._stall_context, logger=self.logger
                )
                self.watchdog.register_progress(
                    "consensus.height", cs.height, stall_after_s=stall_ms / 1000.0,
                    on_stall=self.stall_tracker.on_stall,
                    on_recover=self.stall_tracker.on_recover,
                )
            # metrics/trace pump: push-style heartbeat, stalled when
            # silent for 5 pump intervals
            self.watchdog.register_heartbeat("node.metrics_pump", stall_after_s=10.0)
            self.watchdog.start()

        addr = NetAddress.parse(self.config.p2p.laddr)
        await self.transport.listen(addr.host, addr.port)
        if self.addr_book is not None:
            self.addr_book.add_our_address(self.transport.listen_addr)
        await self.switch.start()

        persistent = [
            NetAddress.parse(a.strip())
            for a in self.config.p2p.persistent_peers.split(",")
            if a.strip()
        ]
        if persistent:
            self.switch.dial_peers_async(persistent, persistent=True)

    async def _txs_available_pump(self) -> None:
        """Forward mempool txs-available into consensus (reference
        node wires mempool.TxsAvailable() into cs)."""
        import asyncio

        ev = self.mempool.txs_available()
        while True:
            await ev.wait()
            ev.clear()
            if self.consensus_state is not None:
                self.consensus_state.handle_txs_available()

    async def _metrics_pump(self) -> None:
        """Periodic gauges that aren't event-driven (peers, mempool)."""
        import asyncio

        while True:
            self.p2p_metrics.peers.set(len(self.switch.peers))
            self.mempool_metrics.size.set(self.mempool.size())
            if self.bc_reactor is not None:
                self.consensus_metrics.fast_syncing.set(1 if self.bc_reactor.fast_sync else 0)
            stats = getattr(self.crypto_provider, "stats", None)
            if stats is not None:
                self.crypto_metrics.update(stats())
            from tendermint_tpu.crypto import merkle as _merkle
            from tendermint_tpu.utils import trace as _trace

            self.merkle_metrics.update(_merkle.device_stats())
            self.trace_metrics.update(_trace.get_tracer().stats())
            from tendermint_tpu.utils import faultinject as _faults
            from tendermint_tpu.utils import watchdog as _watchdog

            breaker_snap = _watchdog.breaker_stats()
            self.health_metrics.update(
                self.watchdog.stats() if self.watchdog is not None else None,
                breaker_snap,
                _faults.stats(),
            )
            if self.stall_tracker is not None:
                self.stall_metrics.update(self.stall_tracker.stats())
            # byzantine-defense family: guard snapshot + the consensus
            # handler backstop counter; quarantine edges become
            # peer.quarantine flight-recorder events (same diffing
            # discipline as the breaker edges below)
            guard_stats = self.switch.guard.stats()
            self.byz_metrics.update(
                guard_stats,
                self.consensus_state.byz_rejects if self.consensus_state is not None else 0,
            )
            if (
                guard_stats["quarantines"] > self._quarantines_last
                and self.consensus_state is not None
            ):
                self.consensus_state.flightrec.record(
                    "peer.quarantine",
                    self.consensus_state.rs.height,
                    self.consensus_state.rs.round,
                    tuple(guard_stats["quarantined_peers"][:4]),
                )
            self._quarantines_last = guard_stats["quarantines"]
            # breaker trip/readmit edges into the flight recorder: the
            # breaker hot path gains no branch — the pump diffs the
            # monotonic trip/recovery totals it already collects
            if self.consensus_state is not None:
                rec = self.consensus_state.flightrec
                rs = self.consensus_state.rs
                for name, bs in breaker_snap.items():
                    prev = self._breaker_last.get(name, (0, 0))
                    cur = (bs.get("trips", 0), bs.get("recoveries", 0))
                    if cur[0] > prev[0]:
                        rec.record("breaker.trip", rs.height, rs.round, name)
                    if cur[1] > prev[1]:
                        rec.record("breaker.readmit", rs.height, rs.round, name)
                    self._breaker_last[name] = cur
            if self.lightserve is not None:
                self.lightserve_metrics.update(self.lightserve.stats())
            self.bls_metrics.update(self.bls_provider.stats())
            if self.mesh_router is not None:
                self.mesh_metrics.update(self.mesh_router.stats())
            # unified engine family: one labeled view over every engine
            # implementing the telemetry protocol (docs/metrics.md)
            self.engine_metrics.update(self.engine_telemetry())
            # lane counters move regardless of the ingest front-end —
            # the QoS lane lives in the mempool (docs/metrics.md)
            self.ingest_metrics.update(
                self.ingest.stats() if self.ingest is not None else {},
                getattr(self.mempool, "lane_stats", dict)(),
            )
            self.exec_metrics.update(self.block_exec.exec_stats())
            if self.watchdog is not None:
                self.watchdog.heartbeat("node.metrics_pump")
            await asyncio.sleep(2.0)

    def peer_gossip_ages(self) -> list:
        """Per-peer connectivity + last-gossip age (seconds since the
        last consensus message) for the stall autopsy: distinguishes
        'peers went silent' from 'peers gossiping but short of quorum'."""
        import time as _time

        from tendermint_tpu.consensus.reactor import PEER_STATE_KEY

        now = _time.time()
        out = []
        for pid, peer in list(self.switch.peers.items()):
            ps = peer.get(PEER_STATE_KEY)
            row = {"peer_id": pid, "outbound": bool(getattr(peer, "outbound", False))}
            if ps is not None:
                row["last_gossip_age_s"] = round(now - ps.last_msg_at, 3)
                row["height"] = ps.rs.height
                row["round"] = ps.rs.round
            out.append(row)
        return out

    def _stall_context(self) -> dict:
        """Node-level extras attached to every stall diagnosis
        (consensus/flightrec.py diagnose kwargs)."""
        from tendermint_tpu.utils import watchdog as _watchdog

        return {
            "peers": self.peer_gossip_ages(),
            "breakers": _watchdog.breaker_stats(),
            "engines": self.engine_telemetry(),
            "mempool_size": self.mempool.size() if self.mempool is not None else None,
            # quarantined-for-malformed-traffic peers distinguish "the
            # net went hostile" from "peers went silent" in a diagnosis
            "quarantined": self.switch.guard.stats()["quarantined_peers"],
        }

    def _only_validator_is_us(self, state: State) -> bool:
        if self.priv_validator is None:
            return False
        if state.validators.size() != 1:
            return False
        addr, _ = state.validators.get_by_index(0)
        return addr == self.priv_validator.get_pub_key().address()

    async def on_stop(self) -> None:
        # watchdog first: nothing may be "restarted" mid-teardown
        if self.watchdog is not None:
            self.watchdog.stop()
        await self.switch.stop()
        # lightserve before the pipeline: its aggregator feeds specs into
        # the pipelined provider, so it must drain first
        if self.lightserve_server is not None:
            await self.lightserve_server.stop()
        if self.lightserve is not None:
            self.lightserve.stop()
        # ingest before the pipeline: its bundles pre-verify through the
        # pipelined provider, so the funnel must drain first
        if self.ingest is not None:
            await self.ingest.stop()
        # drain the pipelined verify dispatcher: every already-submitted
        # future completes before its threads exit (crypto/pipeline.py)
        stop_pipeline = getattr(self.crypto_provider, "stop", None)
        if stop_pipeline is not None:
            stop_pipeline(drain=True)
        if getattr(self, "prof_server", None) is not None:
            await self.prof_server.stop()
        if getattr(self, "grpc_server", None) is not None:
            await self.grpc_server.stop()
        if self.rpc_server is not None:
            await self.rpc_server.stop()
        if self.consensus_state is not None:
            # final black-box flush: whatever the ring holds beyond the
            # last ENDHEIGHT boundary survives for offline autopsy
            self.consensus_state.flightrec.sync_tail()
            self.consensus_state.flightrec.close_tail()
        await self.indexer_service.stop()
        await self.event_bus.stop()
        await self.proxy_app.stop()

    # -- accessors (used by RPC) -------------------------------------------

    def is_listening(self) -> bool:
        return self.transport.listen_addr is not None


def default_new_node(config: Config, app=None, logger=None) -> Node:
    """Reference DefaultNewNode node/node.go:90: load node key, privval,
    genesis from the config-rooted files."""
    node_key = load_or_gen_node_key(config.base.node_key_file())
    if config.base.priv_validator_laddr:
        from tendermint_tpu.privval.signer import SignerClient

        pv = SignerClient(config.base.priv_validator_laddr)
    else:
        pv = load_or_gen_file_pv(
            config.base.priv_validator_key_file(),
            config.base.priv_validator_state_file(),
            key_type=config.base.priv_validator_key_type,
        )
    genesis = GenesisDoc.from_file(config.base.genesis_file())
    return Node(config, genesis, pv, node_key, app=app, logger=logger)
