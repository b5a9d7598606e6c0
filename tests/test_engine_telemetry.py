"""Unified engine telemetry (models/telemetry.py): the engine_stats()
protocol on all four engines, the bucket/breaker/queue-wait views, the
tendermint_engine_* family fed from snapshots, and the flattened
counters the height ledger diffs per height."""

import numpy as np
import pytest

from tendermint_tpu.models.telemetry import (
    QUEUE_WAIT_BUCKETS_MS,
    QueueWaitHist,
    breaker_view,
    bucket_counts,
    bucket_entry,
    bucket_view,
    collect_engine_stats,
    flatten_engine_counters,
)

_PROTOCOL_KEYS = {
    "engine", "device_rows", "host_rows", "buckets", "breakers",
    "queue_wait_ms", "counters",
}


def _assert_protocol(st, engine):
    assert _PROTOCOL_KEYS <= set(st), st.keys()
    assert st["engine"] == engine
    assert isinstance(st["device_rows"], float)
    assert isinstance(st["host_rows"], float)
    for b in st["buckets"].values():
        assert b["state"] in ("ready", "compiling", "failed", "cold")
    for br in st["breakers"].values():
        assert {"state", "state_code", "trips", "recoveries"} <= set(br)


# -- primitives --------------------------------------------------------------


def test_queue_wait_hist_buckets_and_snapshot():
    h = QueueWaitHist()
    h.observe_ms(0.3)   # bucket 0 (<=0.5)
    h.observe_ms(4.0)   # <=5
    h.observe_ms(9999)  # +Inf overflow
    s = h.snapshot()
    assert s["count"] == 3
    assert s["sum_ms"] == pytest.approx(10003.3)
    assert len(s["counts"]) == len(QUEUE_WAIT_BUCKETS_MS) + 1
    assert s["counts"][0] == 1 and s["counts"][-1] == 1
    assert sum(s["counts"]) == 3


def test_bucket_views_and_counts():
    class E:
        def __init__(self, ready=False, compiling=False, failed=False, compile_s=None):
            self.ready, self.compiling, self.failed = ready, compiling, failed
            self.compile_s = compile_s

    entries = {
        "a": E(ready=True, compile_s=1.5),
        "b": E(compiling=True),
        "c": E(failed=True),
        "d": E(),
    }
    view = bucket_view(entries)
    assert view["a"] == {"state": "ready", "compile_s": 1.5}
    assert view["b"]["state"] == "compiling"
    assert view["c"]["state"] == "failed"  # failed beats everything
    assert view["d"]["state"] == "cold"
    assert bucket_entry(entries["a"])["state"] == "ready"
    tally = bucket_counts({"buckets": view})
    assert tally == {"ready": 1, "compiling": 1, "failed": 1, "cold": 1}


def test_breaker_view():
    from tendermint_tpu.utils.watchdog import CircuitBreaker

    b = CircuitBreaker("telemetry.test", failure_threshold=1)
    b.record_failure()
    view = breaker_view(b, None)
    assert list(view) == ["telemetry.test"]
    assert view["telemetry.test"]["state"] == "open"
    assert view["telemetry.test"]["state_code"] == 2
    assert view["telemetry.test"]["trips"] == 1


def test_flatten_engine_counters():
    flat = flatten_engine_counters(
        {
            "pipeline": {
                "device_rows": 10, "host_rows": 2,
                "counters": {"cache_hits": 5, "note": "text-ignored"},
                "queue_wait_ms": {"count": 3, "sum_ms": 12.0, "counts": [3]},
            },
            "broken": "not-a-dict",
        }
    )
    assert flat == {
        "pipeline.device_rows": 10.0,
        "pipeline.host_rows": 2.0,
        "pipeline.cache_hits": 5.0,
        "pipeline.queue_waits": 3.0,
        "pipeline.queue_wait_sum_ms": 12.0,
    }


def test_collect_engine_stats_skips_and_errors():
    class Good:
        def engine_stats(self):
            return {"engine": "good", "device_rows": 1.0}

    class Silent:
        def engine_stats(self):
            return None  # present but never engaged

    class Broken:
        def engine_stats(self):
            raise RuntimeError("boom")

    out = collect_engine_stats([Good(), Silent(), Broken(), None, object()])
    assert set(out) == {"good", "Broken"}
    assert "error" in out["Broken"]


# -- the four engines --------------------------------------------------------


def test_pipeline_engine_stats():
    import bench
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache

    with PipelinedVerifier(CPUBatchVerifier(), cache=SigCache()) as pv:
        pk, mg, sg = bench.make_batch(8, seed=11)
        assert pv.verify_batch(pk, mg, sg).all()
        st = pv.engine_stats()
    _assert_protocol(st, "pipeline")
    # a host-only inner provider: every row it served is a HOST row —
    # handing a row to the provider does not make it a device row
    assert st["device_rows"] == 0.0
    assert st["host_rows"] == 8.0
    assert st["counters"]["dispatched_bundles"] >= 1
    # the queue-wait histogram observed every bundle, tracing OFF
    assert st["queue_wait_ms"]["count"] >= 1
    assert st["queue_wait_ms"]["sum_ms"] >= 0


def _signed_commit(privs, chain_id="seam-chain", absent=()):
    """A +2/3 commit of ``privs`` (any key types), one vote each."""
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.types.block import BlockID, CommitSig, PartSetHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x42" * 32, PartSetHeader(1, b"\x43" * 32))
    vs = VoteSet(chain_id, 5, 0, PRECOMMIT_TYPE, vals)
    for idx, val in enumerate(vals.validators):
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=5, round=0, block_id=bid,
            timestamp_ns=1234 + idx, validator_address=val.address, validator_index=idx,
        )
        v.signature = by_addr[val.address].sign(v.sign_bytes(chain_id))
        assert vs.add_vote(v)
    commit = vs.make_commit()
    for i in absent:
        commit.signatures[i] = CommitSig.absent()
    return vals, bid, commit


def test_pipeline_engine_stats_seam_counters():
    """The verify seam's counts (crypto/batch.SEAM_COUNTS) under
    engine_stats()["counters"]: an all-ed25519 commit is packed from
    columns with 0 fix-up rows; a set with a secp256k1 key shows that row
    on the other side."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    eds = [Ed25519PrivKey.from_secret(f"seam-{i}".encode()) for i in range(7)]
    with PipelinedVerifier(CPUBatchVerifier(), cache=SigCache()) as pv:
        def delta(since):
            now = pv.engine_stats()["counters"]
            return tuple(
                now[k] - since[k]
                for k in ("seam_column_rows", "seam_packed_rows", "seam_fixup_rows")
            )

        start = pv.engine_stats()["counters"]
        vals, bid, commit = _signed_commit(eds, absent=(2,))
        vals.verify_commit("seam-chain", bid, 5, commit, provider=pv)
        assert delta(start) == (7, 6, 0)

        start = pv.engine_stats()["counters"]
        vals, bid, commit = _signed_commit(eds[:3] + [Secp256k1PrivKey.from_secret(b"seam-secp")])
        vals.verify_commit("seam-chain", bid, 5, commit, provider=pv)
        assert delta(start) == (4, 4, 1)
        assert pv.stats()["seam_fixup_rows"] == pv.engine_stats()["counters"]["seam_fixup_rows"]
        # the cached-table path's counts are published beside them
        # (crypto/batch.TABLED_COUNTS; a CPU provider moves none)
        for k in ("tabled_slot_rows", "tabled_slot_pad", "tabled_gathered_rows"):
            assert pv.stats()[k] == pv.engine_stats()["counters"][k] >= 0


def test_pipeline_publishes_overlapped_rows():
    """A bundle of submitted commits of one set reaches the inner
    provider as row groups (crypto/batch.RowGroups): the rows of every
    group after a bundle's first are ``seam_overlapped_rows``, beside
    the other seam counts in engine_stats(), stats() and the metrics."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.lightserve import core
    from tendermint_tpu.types.validator_set import CommitVerifySpec
    from tendermint_tpu.utils.metrics import CryptoMetrics, Registry
    from tests.seam_helpers import GroupStub

    eds = [Ed25519PrivKey.from_secret(f"seam-{i}".encode()) for i in range(7)]
    vals, bid, commit = _signed_commit(eds, absent=(2,))
    stub = GroupStub(1)
    with PipelinedVerifier(stub, cache=SigCache()) as pv:
        start = pv.engine_stats()["counters"]
        specs = [CommitVerifySpec(vals, "seam-chain", bid, 5, commit) for _ in range(6)]
        assert core.verify_specs(specs, provider=pv) == [None] * 6
        # however the dispatcher cut the six submits into bundles: the
        # first group of each call is packed before anything runs
        later = sum(rows for _, k, rows, _ in stub.of("take") if k)
        assert later == 6 * (6 - len([t for t in stub.of("take") if not t[1]]))
        now = pv.engine_stats()["counters"]
        assert now["seam_overlapped_rows"] - start["seam_overlapped_rows"] == later
        assert now["seam_packed_rows"] - start["seam_packed_rows"] == 36
        assert pv.stats()["seam_overlapped_rows"] == now["seam_overlapped_rows"]
        cm = CryptoMetrics(Registry())
        cm.update(pv.stats())
        assert cm.seam_overlapped_rows.value == now["seam_overlapped_rows"]


@pytest.mark.parametrize(
    "name",
    ["seam_multiset_rows", "table_keys_built", "table_keys_loaded", "table_keys_reused",
     "table_keys_evicted", "table_slabs", "table_slab_columns", "generic_rows",
     "generic_pad_rows", "generic_windows", "generic_launches", "generic_kernel_rows",
     "h2d_bytes"],
)
def test_pipeline_publishes_key_pool_and_multiset_counts(name):
    """What a node reads to tell "my window straddled a set change and
    still rode the tables" and what the key pool built, reused and
    evicted, what the generic family launched and the launches' H2D
    bytes: the process's counts (crypto/batch.SEAM_COUNTS, TABLE_COUNTS,
    GENERIC_COUNTS, H2D_COUNTS) under
    engine_stats()["counters"], stats() and tendermint_crypto_<name>_total,
    one value everywhere."""
    from tendermint_tpu.crypto.batch import (
        GENERIC_COUNTS, H2D_COUNTS, SEAM_COUNTS, TABLE_COUNTS, CPUBatchVerifier,
    )
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.utils.metrics import CryptoMetrics, Registry

    counts = {
        "seam": SEAM_COUNTS, "table": TABLE_COUNTS, "generic": GENERIC_COUNTS, "h2d": H2D_COUNTS,
    }[name.split("_")[0]]
    counts.add(**{name.split("_", 1)[1]: 3})
    want = counts.snapshot()[name]
    assert want >= 3
    with PipelinedVerifier(CPUBatchVerifier(), cache=SigCache()) as pv:
        assert pv.engine_stats()["counters"][name] == pv.stats()[name] == want
        reg = Registry()
        cm = CryptoMetrics(reg)
        cm.update(pv.stats())
        assert getattr(cm, name).value == want
        assert f"tendermint_crypto_{name}_total {want}" in reg.expose_text()


def _row_case_warm_blocking(v, batch):
    pk, mg, sg = batch
    assert v.verify_batch(pk, mg, sg).all()
    return (len(pk), 0)


def _row_case_cold_nonblocking(v, batch):
    # stub the background compile (a daemon XLA-compile thread killed at
    # interpreter exit aborts the process — tests/test_tpu_provider.py)
    v.model._compile_async = lambda *a: None
    pk, mg, sg = batch
    assert v.verify_batch(pk, mg, sg).all()
    ok, tally = v.verify_commit_batch(
        pk, mg, sg, np.full(len(pk), 5, np.int64), np.ones(len(pk), bool)
    )
    assert ok.all() and tally == 5 * len(pk)
    return (0, 2 * len(pk))


def _row_case_sub_min_device_batch(v, batch):
    pk, mg, sg = (a[:1] for a in batch)
    assert v.verify_batch(pk, mg, sg).all()
    return (0, 1)


def _row_case_device_fault(v, batch):
    from tendermint_tpu.utils import faultinject as faults

    faults.arm("device.verify", "raise")
    try:
        with pytest.raises(faults.InjectedFault):
            v.verify_batch(*batch)
    finally:
        faults.disarm("device.verify")
    return (0, 0)


@pytest.mark.parametrize(
    "block_on_compile,case",
    [
        (True, _row_case_warm_blocking),
        (False, _row_case_cold_nonblocking),
        (True, _row_case_sub_min_device_batch),
        (True, _row_case_device_fault),
    ],
    ids=["warm-blocking", "cold-nonblocking", "sub-min-device-batch", "device-fault"],
)
def test_verifier_row_counts_say_where_rows_ran(block_on_compile, case):
    """device_rows / host_rows count what a device executable verified
    and what the host served — counted by the provider that did the
    work, and published unchanged by the pipeline's engine_stats()."""
    import bench
    from tendermint_tpu.crypto.batch import TPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache

    v = TPUBatchVerifier(block_on_compile=block_on_compile)
    want = case(v, bench.make_batch(4, seed=12))
    assert v.row_counts.snapshot() == want
    with PipelinedVerifier(v, cache=SigCache()) as pv:
        st = pv.engine_stats()
    assert (st["device_rows"], st["host_rows"]) == tuple(map(float, want))


def test_pipeline_engine_stats_mixed_arity_bucket_keys():
    """The wrapped model's _entries mixes 3-tuple plain-bucket keys with
    6-tuple tabled/templated keys (models/verifier.py
    _tabled_bucket_entry) — engine_stats must label both, not unpack a
    fixed arity (the live-node regression: a node whose verifier had
    built a tabled entry made the engines RPC return an error stanza)."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.models.telemetry import bucket_entry

    class _E:
        fn = object()
        compile_s = 0.5
        failed = False

    class _Model:
        _entries = {
            ("fixed", 64, 96): _E(),
            ("tabled-tpl", 64, 0, 8, 128, 2): _E(),
        }
        _valset_tables = {}
        tables_breaker = None

    inner = CPUBatchVerifier()
    inner.model = _Model()  # .model is read through the wrapped inner
    with PipelinedVerifier(inner, cache=SigCache()) as pv:
        st = pv.engine_stats()
    assert set(st["buckets"]) == {
        "fn:fixed/64/96", "fn:tabled-tpl/64/0/8/128/2",
    }
    for b in st["buckets"].values():
        assert b == bucket_entry(_E())


@pytest.mark.parametrize(
    "ready,building,failed,want",
    [(True, False, False, "ready"), (False, True, False, "compiling"), (False, False, True, "failed"),
     (False, False, False, None)],
)
def test_pipeline_publishes_the_key_pool_as_a_tables_bucket(ready, building, failed, want):
    """A node's boot is warm when its tables are: the key pool
    (models/verifier._KeyPool) shows as one ``tables:pool`` bucket —
    ready once it holds keys and no background build runs — beside the
    whole-set entries of a mesh; an untouched pool shows nothing."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
    from tendermint_tpu.models.verifier import _KeyPool

    class _Model:
        _entries = {}
        _valset_tables = {}
        tables_breaker = None
        block_on_compile = True

    model = _Model()
    pool = model.key_pool = _KeyPool(model)
    pool._used, pool.building, pool.failed, pool.build_s = int(ready), building, failed, 1.5
    inner = CPUBatchVerifier()
    inner.model = model
    with PipelinedVerifier(inner, cache=SigCache()) as pv:
        buckets = pv.engine_stats()["buckets"]
    if want is None:
        assert buckets == {}
    else:
        assert buckets == {"tables:pool": {"state": want, "compile_s": 1.5}}


def test_txhash_engine_stats_device_and_host_split():
    from tendermint_tpu.ingest.hashing import TxKeyHasher, host_keys

    hs = TxKeyHasher(block_on_compile=True)
    txs = [bytes([i]) * 20 for i in range(8)]
    # below threshold: host path
    assert hs.keys_or_host(txs, threshold=100) == host_keys(txs)
    # above threshold: device path (blocking compile on CPU XLA)
    assert hs.keys_or_host(txs, threshold=1) == host_keys(txs)
    st = hs.engine_stats()
    _assert_protocol(st, "txhash")
    assert st["host_rows"] == 8.0
    assert st["device_rows"] == 8.0
    assert any(b["state"] == "ready" for b in st["buckets"].values())
    assert "ingest.hash.compile" in st["breakers"]


def test_merkle_engine_stats_and_module_wrapper():
    from tendermint_tpu.crypto import merkle as cm
    from tendermint_tpu.models.hasher import MerkleHasher

    h = MerkleHasher(block_on_compile=True)
    st = h.engine_stats()
    _assert_protocol(st, "merkle")
    assert "merkle.compile" in st["breakers"]
    # module wrapper: None when the process never built a hasher
    prev = cm._HASHER
    try:
        cm._HASHER = None
        assert cm.engine_stats() is None
        cm._HASHER = h
        wrapped = cm.engine_stats()
        _assert_protocol(wrapped, "merkle")
        # the SEAM's host counters and runtime breaker merged in
        assert "host_roots" in wrapped["counters"]
        assert "merkle.device" in wrapped["breakers"]
    finally:
        cm._HASHER = prev


def test_bls_engine_stats():
    from tendermint_tpu.models.bls import BLSEngine

    e = BLSEngine(block_on_compile=False)
    st = e.engine_stats()
    _assert_protocol(st, "bls")
    assert "bls.compile" in st["breakers"]
    assert st["counters"]["device_rows"] == 0


# -- the exported family ------------------------------------------------------


def test_engine_metrics_family_and_queue_wait_delta():
    from tendermint_tpu.analysis.metrics_exposition import validate_metrics_text
    from tendermint_tpu.utils.metrics import EngineMetrics, Registry

    qw = QueueWaitHist()
    qw.observe_ms(2.0)

    def stats(dev, host):
        return {
            "pipeline": {
                "engine": "pipeline",
                "device_rows": dev, "host_rows": host,
                "buckets": {
                    "a": {"state": "ready", "compile_s": 2.0},
                    "b": {"state": "failed", "compile_s": None},
                },
                "breakers": {"x": {"state": "open", "state_code": 2, "trips": 1, "recoveries": 0}},
                "queue_wait_ms": qw.snapshot(),
                "counters": {},
            }
        }

    r = Registry()
    em = EngineMetrics(r)
    em.update(stats(10, 1))
    qw.observe_ms(3.0)
    em.update(stats(25, 1))
    text = r.expose_text()
    assert 'tendermint_engine_device_rows_total{engine="pipeline"} 25.0' in text
    assert 'tendermint_engine_host_rows_total{engine="pipeline"} 1.0' in text
    assert 'tendermint_engine_buckets_ready{engine="pipeline"} 1.0' in text
    assert 'tendermint_engine_buckets_failed{engine="pipeline"} 1.0' in text
    assert 'tendermint_engine_breaker_state_max{engine="pipeline"} 2.0' in text
    # two queue-wait observations total, merged via raw bucket deltas
    assert 'tendermint_engine_queue_wait_seconds_count{engine="pipeline"} 2' in text
    # a fully-linted exposition (histogram monotonicity, label quoting)
    assert validate_metrics_text(text) == []
    # an engine error stanza is skipped, not a crash
    em.update({"pipeline": {"error": "boom"}})


def test_histogram_add_raw_guards():
    from tendermint_tpu.utils.metrics import Histogram

    h = Histogram("t_raw", buckets=(1, 2))
    h.add_raw([1, 0, 2], 5.0, 3)
    with pytest.raises(ValueError):
        h.add_raw([1, 2], 1.0, 1)  # wrong layout
    with pytest.raises(ValueError):
        h.add_raw([1, 0, -1], 1.0, 0)  # negative increment
    lines = "\n".join(h._sample_lines())
    assert 't_raw_count 3' in lines


def test_live_harness_node_exposes_engine_family():
    """End-to-end: a committing node's engine telemetry flows into the
    tendermint_engine_* family and the exposition stays lint-clean."""
    import asyncio
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import cs_harness as h

    from tendermint_tpu.analysis.metrics_exposition import validate_metrics_text
    from tendermint_tpu.crypto.batch import get_default_provider
    from tendermint_tpu.utils.metrics import EngineMetrics, Registry

    async def go():
        genesis, privs = h.make_genesis(2)
        nodes = [await h.make_node(genesis, pv) for pv in privs]
        h.wire_loopback(nodes)
        for n in nodes:
            await n.cs.start()
        try:
            await h.wait_for_height(nodes, 2, timeout_s=60)
        finally:
            await h.stop_network(nodes)
        r = Registry()
        em = EngineMetrics(r)
        em.update(collect_engine_stats([get_default_provider()]))
        text = r.expose_text()
        assert "tendermint_engine_" in text
        assert validate_metrics_text(text) == []

    asyncio.run(go())
