"""Per-valset cached-table verify path (round 3): sharded tables for
valsets past MAX_TABLED_VALSET, and verify_commits_batched over many
heights of one valset (the fast-sync / light-client shape) or of
several. Programs of their own shapes, so a file of their own: under
the tier-1 run's --dist loadfile a file is one worker's.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tests.tabled_helpers import arrs, sign_rows


def test_sharded_tables_large_valset(monkeypatch, tmp_path):
    """Valsets past MAX_TABLED_VALSET ride SHARDED tables (equal-size
    shards, per-shard bounded gathers in one program) instead of
    falling to the generic pipeline. Shrunk constants drive the real
    code path on CPU: 20 validators, 8-row shards. Verdicts must match
    the materialized/templated single-table semantics bit for bit, and
    the shards must round-trip the disk cache (re-split on load)."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(vmod, "MAX_TABLED_VALSET", 8)
    monkeypatch.setattr(vmod, "MAX_SHARDED_VALSET", 64)

    v = 20
    pks, msgs, sigs = sign_rows(v, msg_len=160, seed=31)
    pk, mg16, sg16 = arrs(pks, msgs, sigs)
    rng = np.random.default_rng(9)
    n = 33  # rows spanning all shards, with duplicates
    idx = rng.integers(0, v, size=n).astype(np.int32)
    mg = mg16[idx].copy()
    sg = sg16[idx].copy()
    bad = [0, 7, 8, 20, 32]
    for b in bad:
        sg[b, 5] ^= 0x10
    m = vmod.VerifierModel(block_on_compile=True)
    ok = m.verify_rows_cached(b"sharded-valset", pk, idx, mg, sg)
    assert ok is not None, "sharded path unavailable"
    e = m._valset_tables[b"sharded-valset"]
    assert e.shards is not None and len(e.shards) == 8  # v_pad 64 / 8
    want = np.ones(n, dtype=bool)
    want[bad] = False
    np.testing.assert_array_equal(ok, want)

    # templated source over the same sharded entry
    templates = mg.copy()
    templates[:, 93:101] = 0
    ts8 = mg[:, 93:101].copy()
    ok_t = m.verify_rows_cached_templated(
        b"sharded-valset", pk, idx, templates,
        np.arange(n, dtype=np.int32), ts8, sg,
    )
    assert ok_t is not None
    np.testing.assert_array_equal(ok_t, want)

    # disk round-trip: a fresh model loads and RE-SPLITS the shards
    m2 = vmod.VerifierModel(block_on_compile=True)
    ok2 = m2.verify_rows_cached(b"sharded-valset", pk, idx, mg, sg)
    assert ok2 is not None
    e2 = m2._valset_tables[b"sharded-valset"]
    assert e2.source == "disk" and e2.shards is not None and len(e2.shards) == 8
    np.testing.assert_array_equal(ok2, want)

    # past MAX_SHARDED_VALSET: tabled path declines (generic fallback)
    monkeypatch.setattr(vmod, "MAX_SHARDED_VALSET", 16)
    m3 = vmod.VerifierModel(block_on_compile=True)
    assert m3.verify_rows_cached(b"sharded-valset-2", pk, idx, mg, sg) is None


def test_cross_height_batch_rides_cached_tables():
    """verify_commits_batched over heights sharing one valset (the
    fast-sync / light-client sequential shape) must route through the
    per-valset cached tables and accept/reject exactly like the CPU
    provider per height."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier, TPUBatchVerifier
    from tendermint_tpu.types.validator_set import (
        CommitVerifySpec,
        verify_commits_batched,
    )
    from tests.light_helpers import CHAIN_ID, gen_chain, keys, valset

    headers, valsets = gen_chain(10)
    # corrupt height 4's commit
    cs = headers[4].commit.signatures[1]
    cs.signature = cs.signature[:12] + bytes([cs.signature[12] ^ 2]) + cs.signature[13:]

    def specs():
        return [
            CommitVerifySpec(
                valsets[h], CHAIN_ID, headers[h].commit.block_id,
                h, headers[h].commit,
            )
            for h in range(1, 10)
        ]

    tpu = TPUBatchVerifier(block_on_compile=True, min_device_batch=2)
    res_tpu = verify_commits_batched(specs(), provider=tpu)
    res_cpu = verify_commits_batched(specs(), provider=CPUBatchVerifier())
    assert len(tpu.model.key_pool) == len(valsets[1]), "cached tables not used"
    for h, (a, b) in enumerate(zip(res_tpu, res_cpu), start=1):
        assert (a is None) == (b is None), (h, a, b)
    assert res_tpu[3] is not None  # height 4 rejected
    assert sum(1 for r in res_tpu if r is None) == 8


def test_cross_height_batch_mixed_valsets_fall_back_correctly():
    """Specs spanning DIFFERENT validator sets cannot share one table
    cache — the batch must take the generic route and still
    accept/reject per spec exactly like the CPU provider."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier, TPUBatchVerifier
    from tendermint_tpu.types.validator_set import (
        CommitVerifySpec,
        verify_commits_batched,
    )
    from tests.light_helpers import CHAIN_ID, gen_chain, keys

    gen2 = keys(4, tag="mixed-gen2")
    headers, valsets = gen_chain(8, key_changes={5: gen2})
    cs = headers[6].commit.signatures[2]
    cs.signature = cs.signature[:5] + bytes([cs.signature[5] ^ 1]) + cs.signature[6:]

    def specs():
        return [
            CommitVerifySpec(
                valsets[h], CHAIN_ID, headers[h].commit.block_id,
                h, headers[h].commit,
            )
            for h in range(1, 8)
        ]

    tpu = TPUBatchVerifier(block_on_compile=True, min_device_batch=2)
    res_tpu = verify_commits_batched(specs(), provider=tpu)
    res_cpu = verify_commits_batched(specs(), provider=CPUBatchVerifier())
    for h, (a, b) in enumerate(zip(res_tpu, res_cpu), start=1):
        assert (a is None) == (b is None), (h, a, b)
    assert res_tpu[5] is not None  # corrupted height 6 rejected
    assert sum(1 for r in res_tpu if r is None) == 6
