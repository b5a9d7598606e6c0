"""Per-valset cached-table verify path (round 3): VerifierModel's
cached path on materialized rows — build, bucket warm-up, fallbacks,
the windowed stream, the on-disk table cache. The stage kernels are in
test_tabled_kernels.py, the commit-shaped (templated, provider-level)
path in test_tabled_templated.py, sharded tables and cross-height
batches in test_tabled_batches.py.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tendermint_tpu.crypto.batch import TABLE_COUNTS
from tendermint_tpu.ops import ref_ed25519 as ref
from tests.tabled_helpers import arrs, sign_rows


def _grew(before: dict, after: dict, name: str) -> int:
    return after[f"table_{name}"] - before[f"table_{name}"]


def test_verifier_model_rows_cached_and_fallback():
    from tendermint_tpu.models.verifier import VerifierModel

    pks, msgs, sigs = sign_rows(12, seed=13)
    sigs[5] = bytes(64)
    pk, mg, sg = arrs(pks, msgs, sigs)
    want = np.array([ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)])

    m = VerifierModel(block_on_compile=True)
    key = b"valset-key-1"
    idx = np.arange(12, dtype=np.int32)
    ok = m.verify_rows_cached(key, pk, idx, mg, sg)
    assert ok is not None
    np.testing.assert_array_equal(ok, want)
    # warm second call, subset rows
    sub = np.array([0, 5, 7], dtype=np.int32)
    ok2 = m.verify_rows_cached(key, pk, sub, mg[sub], sg[sub])
    np.testing.assert_array_equal(ok2, want[sub])


def test_verifier_model_nonblocking_cold_returns_none():
    from tendermint_tpu.models.verifier import VerifierModel

    pks, msgs, sigs = sign_rows(4, seed=17)
    pk, mg, sg = arrs(pks, msgs, sigs)
    m = VerifierModel(block_on_compile=False)
    out = m.verify_rows_cached(b"k2", pk, np.arange(4, dtype=np.int32), mg, sg)
    assert out is None  # cold: background build kicked off, caller falls back
    # wait for the background build + stage compile, then it serves
    import time

    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        out = m.verify_rows_cached(b"k2", pk, np.arange(4, dtype=np.int32), mg, sg)
        if out is not None:
            break
        time.sleep(0.25)
    assert out is not None and out.all()


def test_failed_table_build_latches_to_generic_fallback(monkeypatch):
    """A table build that raises (e.g. device OOM) must surface as the
    None-fallback contract — never an exception into commit
    verification — and must NOT be retried on every verify."""
    from tendermint_tpu.models.verifier import VerifierModel

    pks, msgs, sigs = sign_rows(8, seed=29)
    pk, mg, sg = arrs(pks, msgs, sigs)
    idx = np.arange(8, dtype=np.int32)

    m = VerifierModel(block_on_compile=True)
    calls = []
    program = m._program

    def boom(tag):
        if tag != "t-build":
            return program(tag)
        calls.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED (simulated)")

    monkeypatch.setattr(m, "_program", boom)
    assert m.verify_rows_cached(b"doomed", pk, idx, mg, sg) is None
    assert m.verify_rows_cached(b"doomed", pk, idx, mg, sg) is None
    assert len(calls) == 1, "doomed build retried"
    assert m.key_pool.failed and len(m.key_pool) == 0


def test_windowed_cached_path_boundary_controls(monkeypatch):
    """The >MAX_DEVICE_ROWS streaming path: shrink the window so CI
    drives full windows + tail with invalid rows planted on both sides
    of every boundary (in-repo reproduction of the 17k-row drive)."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 16)
    pks, msgs, sigs = sign_rows(16, seed=23)
    pk16, mg16, sg16 = arrs(pks, msgs, sigs)
    n = 42  # 2 full windows of 16 + tail of 10
    rng = np.random.default_rng(5)
    idx = rng.integers(0, 16, size=n).astype(np.int32)
    mg = mg16[idx].copy()
    sg = sg16[idx].copy()
    bad = [0, 15, 16, 31, 32, 41]  # straddle every window boundary
    for b in bad:
        sg[b, 7] ^= 0x08
    m = vmod.VerifierModel(block_on_compile=True)
    ok = m.verify_rows_cached(b"win-test", pk16, idx, mg, sg)
    assert ok is not None and ok.shape == (n,)
    want = np.ones(n, dtype=bool)
    want[bad] = False
    np.testing.assert_array_equal(ok, want)

    # non-blocking with a cold tail bucket: nothing dispatches, the
    # caller falls back (no wasted window work)
    m2 = vmod.VerifierModel(block_on_compile=False)
    with vmod._compile_threads_lock:
        before = set(vmod._compile_threads)
    assert m2.verify_rows_cached(b"win-test-2", pk16, idx, mg, sg) is None
    # the background key fetch that call started reads the 16 keys back
    # from the table files: joined here, so that its process-wide
    # TABLE_COUNTS never land inside a later test's reading
    with vmod._compile_threads_lock:
        mine = [t for t in vmod._compile_threads if t not in before]
    for t in mine:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in mine)


def test_tables_persist_to_disk_and_reload(tmp_path, monkeypatch):
    """Restart path: the built split tables are pure deterministic data,
    so a fresh model (fresh process analog) must LOAD them from disk —
    no build program — and verify identically. This is what holds the
    tabled cold start under the <5s restart budget (the t-build
    executable alone measured 15.9s to load at 10k validators)."""
    from tendermint_tpu.models.verifier import VerifierModel

    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path))
    pks, msgs, sigs = sign_rows(12, seed=31)
    sigs[3] = bytes(64)
    pk, mg, sg = arrs(pks, msgs, sigs)
    want = np.array([ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)])
    idx = np.arange(12, dtype=np.int32)
    key = b"persist-valset"

    m1 = VerifierModel(block_on_compile=True)
    c0 = TABLE_COUNTS.snapshot()
    ok1 = m1.verify_rows_cached(key, pk, idx, mg, sg)
    c1 = TABLE_COUNTS.snapshot()
    assert (_grew(c0, c1, "keys_built"), _grew(c0, c1, "keys_loaded")) == (12, 0)
    assert any(f.endswith(".npz") for f in os.listdir(tmp_path))

    m2 = VerifierModel(block_on_compile=True)
    ok2 = m2.verify_rows_cached(key, pk, idx, mg, sg)
    c2 = TABLE_COUNTS.snapshot()
    assert (_grew(c1, c2, "keys_built"), _grew(c1, c2, "keys_loaded")) == (0, 12)
    assert m2.key_pool.dispatches == 0  # pure data from disk, no build program
    np.testing.assert_array_equal(ok1, want)
    np.testing.assert_array_equal(ok2, want)


def test_tables_disk_corruption_falls_back_to_build(tmp_path, monkeypatch):
    from tendermint_tpu.models.verifier import VerifierModel

    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path))
    pks, msgs, sigs = sign_rows(8, seed=37)
    pk, mg, sg = arrs(pks, msgs, sigs)
    idx = np.arange(8, dtype=np.int32)
    key = b"corrupt-valset"

    m1 = VerifierModel(block_on_compile=True)
    assert m1.verify_rows_cached(key, pk, idx, mg, sg).all()
    (blob,) = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    with open(os.path.join(tmp_path, blob), "wb") as fh:
        fh.write(b"not a table blob")

    m2 = VerifierModel(block_on_compile=True)
    ok = m2.verify_rows_cached(key, pk, idx, mg, sg)
    assert m2.key_pool.dispatches == 1 and len(m2.key_pool) == 8  # rebuilt, not crashed
    assert ok is not None and ok.all()


def test_tables_disk_pubkey_mismatch_rebuilds(tmp_path, monkeypatch):
    """A persisted blob under a reused valset key must NOT be trusted
    when the pubkeys differ: a row is read back only from under its own
    key bytes (a wrong table silently flips signature-verification
    results)."""
    from tendermint_tpu.models.verifier import VerifierModel

    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path))
    key = b"reused-valset-key"
    pks1, msgs1, sigs1 = sign_rows(8, seed=41)
    pk1, mg1, sg1 = arrs(pks1, msgs1, sigs1)
    idx = np.arange(8, dtype=np.int32)

    m1 = VerifierModel(block_on_compile=True)
    assert m1.verify_rows_cached(key, pk1, idx, mg1, sg1).all()
    assert m1.key_pool.dispatches == 1

    # same key, DIFFERENT pubkeys: the persisted blob must be rejected
    pks2, msgs2, sigs2 = sign_rows(8, seed=43)
    pk2, mg2, sg2 = arrs(pks2, msgs2, sigs2)
    m2 = VerifierModel(block_on_compile=True)
    c0 = TABLE_COUNTS.snapshot()
    ok = m2.verify_rows_cached(key, pk2, idx, mg2, sg2)
    c1 = TABLE_COUNTS.snapshot()
    assert (_grew(c0, c1, "keys_built"), _grew(c0, c1, "keys_loaded")) == (8, 0)  # rebuilt, not loaded
    assert ok is not None and ok.all()


def test_oversized_valset_skips_tabled_path(monkeypatch):
    """Sets beyond MAX_SHARDED_VALSET must ride the generic pipeline:
    the 50k-ingest eval measured the huge-table path ~50x slower end
    to end (HBM-resident 2GB tables + huge-shape compiles). Sets
    between the two caps go SHARDED (test_tabled_batches.py)
    — only past the sharded cap does the tabled path decline."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setattr(vmod, "MAX_TABLED_VALSET", 8)
    monkeypatch.setattr(vmod, "MAX_SHARDED_VALSET", 8)
    pks, msgs, sigs = sign_rows(12, seed=51)
    pk, mg, sg = arrs(pks, msgs, sigs)
    m = vmod.VerifierModel(block_on_compile=True)
    out = m.verify_rows_cached(b"big-valset", pk, np.arange(12, dtype=np.int32), mg, sg)
    assert out is None  # caller falls back to the generic path
    assert b"big-valset" not in m._valset_tables and len(m.key_pool) == 0  # nothing was built


def test_small_sparse_batch_against_one_table_rides_gathered_pair():
    """A batch the table dwarfs — a sparse vote drain, out of validator
    order or in it — is served by the gathered pair, not declined and
    not sent to the slots: a table holds at most MAX_TABLED_VALSET
    rows, where gathers are fine (larger sets ride bounded shards)."""
    from tendermint_tpu.models import verifier as vmod

    pks, msgs, sigs = sign_rows(80, seed=53)
    pk, mg, sg = arrs(pks, msgs, sigs)
    m = vmod.VerifierModel(block_on_compile=True)
    from tendermint_tpu.crypto.batch import TABLED_COUNTS

    c0 = TABLED_COUNTS.snapshot()
    # full-set call (slot order: 80 rows in 256 slots against the 256
    # bucket) builds the 80-row (pad 256) tables
    ok = m.verify_rows_cached(b"gather-valset", pk, np.arange(80, dtype=np.int32), mg, sg)
    assert ok is not None and ok.all()
    c1 = TABLED_COUNTS.snapshot()
    assert (c1["tabled_slot_rows"] - c0["tabled_slot_rows"], c1["tabled_slot_pad"] - c0["tabled_slot_pad"]) == (80, 176)
    sub = np.array([5, 2, 9], dtype=np.int32)
    # the gathered path engages for a sparse vote batch out of order
    # (three runs) and in order (one run: 256 slots for a 16-row
    # bucket, beyond _SLOT_GATHER_RATIO)
    for rows in (sub, np.sort(sub)):
        out = m.verify_rows_cached(b"gather-valset", pk, rows, mg[rows], sg[rows])
        assert out is not None and out.all()
    c2 = TABLED_COUNTS.snapshot()
    assert c2["tabled_gathered_rows"] - c1["tabled_gathered_rows"] == 6
    assert c2["tabled_slot_rows"] == c1["tabled_slot_rows"]


def test_tables_disk_cache_bounded(tmp_path, monkeypatch):
    from tendermint_tpu.models import aot_cache

    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("TM_TABLES_CACHE_KEEP", "2")
    monkeypatch.setattr(aot_cache, "_TABLES_KEEP", 2)
    t = np.zeros((64, 2, 8, 60), dtype=np.int32)
    a = np.ones(64, dtype=bool)
    for i in range(4):
        aot_cache.save_tables(np.full((64, 32), i, dtype=np.uint8), t, a)
    left = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(left) == 2
    # by bytes, not by count: a few one-key files of later changes do
    # not push the set's own file out
    for i in range(4, 8):
        aot_cache.save_tables(np.full((1, 32), i, dtype=np.uint8), t[:1], a[:1])
    left = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert sum(f.endswith("-64.npz") for f in left) >= 1 and sum(f.endswith("-1.npz") for f in left) == 4
