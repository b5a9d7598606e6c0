"""Degraded-topology semantics of the mesh runtime (parallel/topology).

The mesh must only ever make the hot path faster, never different:

- the degenerate 1-device topology is byte-identical to the unmeshed
  path across every engine seam;
- a tripped per-device breaker sheds its shard to the survivors at the
  NEXT bundle with verdicts intact;
- the half-open probe re-admits a recovered device;
- sub-``mesh_min_rows`` bundles never enter the collective path (and
  never consume probe tokens).

Router/breaker semantics run on LOGICAL host lanes (no XLA); the
placement legs use the conftest's virtual CPU devices. The satellite
``sharded_valset_cap`` boundary (MAX_SHARDED_VALSET divided per-device
when a mesh is live) is pinned at the bottom.
"""

import numpy as np
import pytest

from tests.mesh_helpers import signed_batch

from tendermint_tpu.crypto.batch import CPUBatchVerifier, MeshRoutedVerifier
from tendermint_tpu.parallel import DeviceTopology, MeshRouter
from tendermint_tpu.utils.watchdog import CircuitBreaker


def _logical_router(n=4, min_rows=4, threshold=1, cooldown=3600.0):
    topo = DeviceTopology.logical(n)
    # deterministic breakers: one failure trips, cooldown controlled
    # per test (3600 s == "never within this test" unless overridden)
    topo.breakers = [
        CircuitBreaker(
            f"mesh.device{i}", failure_threshold=threshold, cooldown_s=cooldown
        )
        for i in range(n)
    ]
    return MeshRouter(topo, min_rows=min_rows)


# -- (d) the collective threshold -------------------------------------------


def test_sub_threshold_bundles_never_collective():
    r = _logical_router(min_rows=256)
    plan = r.plan(255)
    assert not plan.collective and plan.slots == []
    assert r.plan(256).collective
    st = r.stats()
    assert st["single_bundles"] == 1 and st["collective_bundles"] == 1


def test_sub_threshold_bundles_never_touch_breakers():
    """Small bundles must not consume the half-open probe token — a
    recovering device's one probe belongs to a real collective."""
    r = _logical_router(min_rows=64, cooldown=0.0)
    b = r.topology.breakers[1]
    b.force_open()
    for _ in range(5):
        assert not r.plan(8).collective
    # the probe token is still there for the first real collective
    assert b.state() == "open"
    plan = r.plan(64)
    assert plan.collective
    probe_slots = [s for s in plan.slots if s.probe]
    assert [s.index for s in probe_slots] == [1]


def test_single_device_topology_is_never_collective():
    r = MeshRouter(DeviceTopology.logical(1), min_rows=1)
    assert not r.plan(100_000).collective
    assert r.stats()["collective_bundles"] == 0


def test_min_rows_override_per_engine():
    """plan(min_rows=...) lets a high-cost-per-row engine (BLS) mesh
    below the router default."""
    r = _logical_router(min_rows=256)
    assert not r.plan(16).collective
    assert r.plan(16, min_rows=8).collective


# -- (b) shed-to-survivors with verdicts intact -----------------------------


def test_tripped_breaker_sheds_shard_to_survivors_verdicts_intact():
    r = _logical_router(n=4, min_rows=4)
    v = MeshRoutedVerifier(CPUBatchVerifier(), r)
    n = 64
    pk, mg, sg = signed_batch(n, seed=31)
    sg[5, 0] ^= 1
    sg[33, 1] ^= 2
    powers = np.arange(1, n + 1, dtype=np.int64)
    counted = np.ones(n, dtype=bool)
    counted[7] = False
    want_ok, want_tally = CPUBatchVerifier().verify_commit_batch(
        pk, mg, sg, powers, counted
    )

    ok, tally = v.verify_commit_batch(pk, mg, sg, powers, counted)
    np.testing.assert_array_equal(ok, want_ok)
    assert tally == want_tally
    assert r.stats()["collective_bundles"] == 1
    rows_before = r.stats()["device_rows"][2]
    assert rows_before == 16  # 64 rows over 4 lanes

    # chip 2 goes sick: the NEXT bundle re-shards across the survivors
    r.topology.breakers[2].force_open()
    ok2, tally2 = v.verify_commit_batch(pk, mg, sg, powers, counted)
    np.testing.assert_array_equal(ok2, want_ok)
    assert tally2 == want_tally
    st = r.stats()
    assert st["admitted"] == 3 and st["sheds"] == 1
    assert st["device_rows"][2] == rows_before  # shed chip saw no rows
    assert st["collective_bundles"] == 2


def test_all_shed_degrades_to_single_path():
    r = _logical_router(n=2, min_rows=2)
    v = MeshRoutedVerifier(CPUBatchVerifier(), r)
    for b in r.topology.breakers:
        b.force_open()
    pk, mg, sg = signed_batch(16, seed=32)
    ok = v.verify_batch(pk, mg, sg)
    np.testing.assert_array_equal(ok, CPUBatchVerifier().verify_batch(pk, mg, sg))
    st = r.stats()
    assert st["collective_bundles"] == 0 and st["admitted"] == 0


# -- (c) half-open probe re-admission ---------------------------------------


def test_half_open_probe_readmits_recovered_device():
    r = _logical_router(n=4, min_rows=4)
    v = MeshRoutedVerifier(CPUBatchVerifier(), r)
    pk, mg, sg = signed_batch(32, seed=33)
    want = CPUBatchVerifier().verify_batch(pk, mg, sg)

    sick = r.topology.breakers[1]
    sick.force_open()
    np.testing.assert_array_equal(v.verify_batch(pk, mg, sg), want)
    assert r.stats()["admitted"] == 3

    # cooldown elapses: the next plan hands device 1 the half-open
    # probe, the bundle succeeds, and the breaker closes
    sick._cooldown_s = 0.0
    plan = r.plan(32)
    assert [s.index for s in plan.slots] == [0, 1, 2, 3]
    assert [s.probe for s in plan.slots] == [False, True, False, False]
    r.complete(plan)
    assert sick.state() == "closed"
    st = r.stats()
    assert st["admitted"] == 4 and st["readmits"] == 1


def test_failed_probe_reopens_and_resheds():
    r = _logical_router(n=4, min_rows=4, cooldown=0.0)
    sick = r.topology.breakers[3]
    sick.force_open()
    plan = r.plan(32)  # probe admitted straight away (cooldown 0)
    assert any(s.probe and s.index == 3 for s in plan.slots)

    def dispatch(s):
        if s.index == 3:
            raise RuntimeError("still sick")
        return np.ones(s.rows, dtype=bool)

    with pytest.raises(RuntimeError):
        r.run(plan, dispatch, np.concatenate)
    assert sick.state() == "open"
    # healthy earlier slots were credited, not blamed
    assert r.topology.breakers[0].state() == "closed"
    assert r.stats()["shard_failures"] == 1


def test_run_failure_attribution_blames_only_the_failing_slot():
    r = _logical_router(n=4, min_rows=4)

    plan = r.plan(16)

    def dispatch(s):
        if s.index == 1:
            raise RuntimeError("boom")
        return np.zeros(s.rows, dtype=bool)

    with pytest.raises(RuntimeError):
        r.run(plan, dispatch, np.concatenate)
    states = [b.state() for b in r.topology.breakers]
    assert states == ["closed", "open", "closed", "closed"]


# -- (a) degenerate 1-device topology: byte-identical engines ---------------


@pytest.fixture(scope="module")
def one_dev_router():
    jax = pytest.importorskip("jax")
    devs = jax.devices()
    return MeshRouter(
        DeviceTopology(devs[:1], platform=devs[0].platform), min_rows=1
    )


def test_one_device_mesh_verifier_bit_identical(one_dev_router):
    from tendermint_tpu.crypto.batch import TPUBatchVerifier

    pk, mg, sg = signed_batch(64, seed=21)
    sg[7, 0] ^= 1
    meshed = TPUBatchVerifier(block_on_compile=True, router=one_dev_router)
    plain = TPUBatchVerifier(block_on_compile=True)
    np.testing.assert_array_equal(
        meshed.verify_batch(pk, mg, sg), plain.verify_batch(pk, mg, sg)
    )


def test_one_device_mesh_txkey_hasher_bit_identical(one_dev_router):
    from tendermint_tpu.ingest.hashing import TxKeyHasher

    txs = [bytes([i % 251]) * ((i % 48) + 1) for i in range(300)]
    meshed = TxKeyHasher(block_on_compile=True, router=one_dev_router)
    plain = TxKeyHasher(block_on_compile=True)
    assert meshed.keys(txs) == plain.keys(txs)


def test_one_device_mesh_merkle_hasher_bit_identical(one_dev_router):
    from tendermint_tpu.models.hasher import MerkleHasher

    items = [bytes([i % 256, (i * 7) % 256]) * 16 for i in range(64)]
    meshed = MerkleHasher(block_on_compile=True, router=one_dev_router)
    plain = MerkleHasher(block_on_compile=True)
    got = meshed.root(items)
    assert got is not None and got == plain.root(items)


def test_one_device_mesh_bls_takes_identical_path(one_dev_router):
    """With one device the BLS mesh seam must decline (non-collective
    plan) before any device work — verify_rows is the engine's
    existing path, so the 1-device contract is identity by
    construction. (Multi-device BLS verdict parity is the slow leg
    below; the pairing kernel is a one-minute XLA:CPU compile.)"""
    from tendermint_tpu.models.bls import BLSEngine

    eng = BLSEngine(block_on_compile=False, router=one_dev_router)
    rows = [(None, None, None)] * 16  # never touched: plan declines first
    assert eng._mesh_verify(rows) is None
    assert one_dev_router.stats()["collective_bundles"] == 0


@pytest.mark.slow
def test_mesh_bls_verdicts_bit_identical():
    """BLS pairing rows sharded over a 2-device mesh: verdict vector
    identical to the known per-row truth (bad row stays bad, in
    place), router records the collective."""
    jax = pytest.importorskip("jax")
    from tendermint_tpu.models.bls import BLSEngine
    from tendermint_tpu.ops import ref_bls12 as B

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("need 2 devices")
    r = MeshRouter(
        DeviceTopology(devs[:2], platform=devs[0].platform), min_rows=2
    )
    n = 16
    sks = [B.keygen(b"mesh-%d" % i) for i in range(n)]
    pks = [B.sk_to_pk(s) for s in sks]
    hms = [B.hash_to_curve_g2(b"mesh-msg-%d" % i, B.DST_SIG) for i in range(n)]
    sigs = [B.g2_mul(s, h) for s, h in zip(sks, hms)]
    bad = (3, 11)  # one per shard half
    for i in bad:
        sigs[i] = B.g2_mul(12345 + i, B.G2_GEN)
    rows = list(zip(pks, hms, sigs))
    eng = BLSEngine(block_on_compile=True, router=r)
    ok = eng.verify_rows(rows)
    assert ok is not None
    want = [i not in bad for i in range(n)]
    assert list(ok) == want
    assert r.stats()["collective_bundles"] == 1


# -- satellite: MAX_SHARDED_VALSET divides per-device on a mesh -------------


def test_sharded_valset_cap_divides_by_mesh_size(cpu_mesh, monkeypatch):
    import tendermint_tpu.models.verifier as V

    monkeypatch.setattr(V, "MAX_SHARDED_VALSET", 1 << 10)
    unmeshed = V.VerifierModel(block_on_compile=True)
    meshed = V.VerifierModel(mesh=cpu_mesh, block_on_compile=True)
    assert unmeshed.sharded_valset_cap() == 1 << 10
    assert meshed.sharded_valset_cap() == (1 << 10) // 8


def test_tables_entry_honors_per_device_cap(cpu_mesh, monkeypatch):
    """At the boundary: a valset over the per-device cap must DECLINE
    the tabled path on a mesh model (generic pipeline takes over)
    while the same set still tables on the single-device model."""
    import tendermint_tpu.models.verifier as V

    monkeypatch.setattr(V, "MAX_TABLED_VALSET", 8)
    monkeypatch.setattr(V, "MAX_SHARDED_VALSET", 128)
    built = []
    monkeypatch.setattr(
        V.VerifierModel,
        "_build_tables",
        lambda self, e, key, pks: built.append(key) or setattr(e, "ready", True),
    )
    meshed = V.VerifierModel(mesh=cpu_mesh, block_on_compile=True)
    plain = V.VerifierModel(block_on_compile=True)
    # 8-device mesh: per-device cap is 128//8 = 16
    pk_over = np.zeros((17, 32), dtype=np.uint8)   # > 16: meshed declines
    pk_at = np.zeros((16, 32), dtype=np.uint8)     # == 16: meshed accepts
    assert meshed._tables_entry(b"over", pk_over) is None
    assert plain._tables_entry(b"over", pk_over) is not None
    assert meshed._tables_entry(b"at", pk_at) is not None
    assert built  # the accepting paths actually built (stubbed) tables
