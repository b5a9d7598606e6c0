"""A chain whose validator set changes from height to height stays on
the cached key tables: one table a validator KEY, not one a set.

Three layers, smallest first. The seam alone (pure numpy, no device):
which keys a group of commits names and where each row lands among
them. The key pool (models/verifier._KeyPool) on the CPU at 16-row
buckets: what it builds, reuses and evicts. Then verify_commits_batched
and light.verify_chain over chains with one, two and every-height set
changes — against the direct serial calls, against the plain reference
(perfbench/reference/chain_sets.py) on seeded keys, through a host stub
that takes row groups and through the TPU provider held to the CPU.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto.batch import (
    SEAM_COUNTS, TABLE_COUNTS, TABLED_COUNTS, CPUBatchVerifier, GroupKeys, RowGroups,
)
from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey
from tendermint_tpu.light import verifier as light
from tendermint_tpu.light.types import SignedHeader
from tendermint_tpu.lightserve import loadgen
from tendermint_tpu.models import verifier as vmod
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.validator_set import (
    CommitVerifySpec, _SpecRows, verify_commits_batched,
)
from tests.seam_helpers import GroupStub, seam_counts, seam_grew

V = 7  # validators a set: the quorum falls on row 4 (power 10 each)
NOW = loadgen.T0 + 1000 * loadgen.BLOCK_NS
PERIOD = 10**18


def _chain(heights: int, change_at, keys=None):
    """Heights 1..n; at each height of ``change_at`` the oldest key
    leaves and a fresh one joins (the set is re-sorted by address)."""
    ks = keys or loadgen.keys(V + heights, tag="churn")
    cur, changes, nxt = ks[:V], {}, V
    for h in range(2, heights + 1):
        if h in change_at:
            cur = cur[1:] + [ks[nxt]]
            nxt += 1
            changes[h] = cur
    return loadgen.make_chain(heights, key_changes=changes, base_keys=ks[:V])


CHANGES = {"one set": (), "one change": (4,), "two changes": (3, 6), "every height": range(2, 99)}


def _specs(headers, valsets, lo=2):
    out = []
    for h in range(lo, len(headers) + 1):
        sh = headers[h]
        out.append(CommitVerifySpec(valsets[h], loadgen.CHAIN_ID, sh.commit.block_id, h, sh.commit))
    return out


def _fresh(specs, edit=None):
    """The same specs over Commit objects nothing has read yet;
    ``edit(j, sigs)`` may change spec j's signature list first."""
    out = []
    for j, s in enumerate(specs):
        sigs = list(s.commit.signatures)
        if edit is not None:
            edit(j, sigs)
        out.append(CommitVerifySpec(
            s.valset, s.chain_id, s.block_id, s.height,
            Commit(s.commit.height, s.commit.round, s.commit.block_id, sigs),
            mode=s.mode, trust_level=s.trust_level,
        ))
    return out


def _forge(at, slot):
    def edit(j, sigs):
        if j == at:
            cs = sigs[slot]
            sigs[slot] = CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp_ns, bytes(64))
    return edit


def _direct(spec):
    try:
        (s,) = _fresh([spec])
        s.valset.verify_commit(s.chain_id, s.block_id, s.height, s.commit, provider=CPUBatchVerifier())
    except Exception as e:
        return type(e).__name__, str(e)
    return None


def _texts(results):
    return [None if e is None else (type(e).__name__, str(e)) for e in results]


# -- the seam alone: keys and places, pure numpy -----------------------------------


@pytest.mark.parametrize("changes", sorted(CHANGES))
@pytest.mark.parametrize("per", [1, 3, 8])
def test_every_row_lands_on_its_own_keys_column(changes, per):
    """row_idx of a group indexes the group's own keys: the key there
    is the key the seam would have handed the generic kernel."""
    headers, valsets = _chain(9, CHANGES[changes])
    specs = _specs(headers, valsets)
    rows = _SpecRows(specs, [None] * len(specs))
    seg = 0
    while rows.left:
        sets = {s.valset.batch_cache()[0] for s in specs[len(specs) - rows.left :][:per]}
        keys = rows.keys(per)
        assert isinstance(keys, GroupKeys)
        assert len(np.unique(keys.pubkeys, axis=0)) == len(keys.pubkeys) == V + len(sets) - 1
        idx, _tpl, _ti, _t8, sg = rows.take(per)
        want = np.concatenate(rows.pk[seg:])
        np.testing.assert_array_equal(keys.pubkeys[idx], want)
        assert len(sg) == len(idx)
        for s_idx, n in ((s[2], s[5]) for s in rows.segments[seg:]):
            run, idx = idx[:n], idx[n:]
            assert (np.diff(run) > 0).all()  # a commit's rows: one increasing run
            if len(sets) == 1:
                np.testing.assert_array_equal(run, s_idx)  # one set: the validator's index, as before
        seg = len(rows.segments)


def test_one_set_names_its_own_matrix_and_key():
    headers, valsets = _chain(6, ())
    specs = _specs(headers, valsets)
    rows = _SpecRows(specs, [None] * len(specs))
    key, pk, _ = valsets[2].batch_cache()
    keys = rows.keys(4)
    assert keys.digest == key and keys.pubkeys is pk
    assert rows.ed25519_sets() == 1


def test_the_same_sets_name_the_same_digest_and_other_sets_another():
    headers, valsets = _chain(9, CHANGES["every height"])
    a = _SpecRows(_specs(headers, valsets), [None] * 8)
    b = _SpecRows(_specs(headers, valsets), [None] * 8)
    assert a.keys(4).digest == b.keys(4).digest
    assert a.keys(4).digest != a.keys(3).digest
    assert a.ed25519_sets() == 8


def test_one_set_lists_give_the_slot_plan_of_before(monkeypatch):
    """The planner sees what it saw: the validator indices of whole
    commits of one set, against that set's bucket."""
    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 64)
    headers, valsets = _chain(9, ())
    specs = _specs(headers, valsets)
    rows = _SpecRows(specs, [None] * len(specs))
    idx = rows.take(4)[0]
    old = np.concatenate([np.asarray(s[2], dtype=np.int32) for s in rows.segments])
    got, want = vmod.plan_slots(idx, 16), vmod.plan_slots(old, 16)
    np.testing.assert_array_equal(got.slots, want.slots)
    assert got.launches == want.launches == ((0, 4 * V, 4),)


class _Counted(RowGroups):
    """``sizes[j]``: distinct keys the next j+1 commits' sets name."""

    def __init__(self, commits, distinct):
        self.left, self.distinct, self.taken = commits, distinct, []

    def keys(self, commits):
        n = min(commits, self.left)
        return GroupKeys(bytes([n]), np.zeros((self.distinct(n), 32), dtype=np.uint8))

    def take(self, commits):
        n = min(commits, self.left)
        self.left -= n
        self.taken.append(n)
        return (
            np.zeros(0, np.int32), np.zeros((0, 160), np.uint8), np.zeros(0, np.int32),
            np.zeros((0, 8), np.uint8), np.zeros((0, 64), np.uint8),
        )


@pytest.mark.parametrize(
    "name,distinct,taken,tables",
    [
        ("a key a height: 16 sets of 1,000 name 1,015 keys, one bucket", lambda n: 999 + n, [16] * 4, [1024] * 4),
        ("one set", lambda n: 1000, [16] * 4, [1024] * 4),
        ("two keys a height pass the bucket at 16 commits: 8 a launch", lambda n: 998 + 2 * n, [8] * 8, [1024] * 8),
        ("a set a height with no key shared: a commit a launch", lambda n: 1000 * n, [1] * 64, [1024] * 64),
        ("10,000 validators: one commit, whatever changes", lambda n: 9999 + n, [1] * 64, [10240] * 64),
    ],
)
def test_distinct_keys_past_a_bucket_lower_the_commits_a_launch(name, distinct, taken, tables):
    """_group_pieces alone, no device: a group is as many commits as
    the bucket of their sets' distinct keys leaves room for."""
    model = vmod.VerifierModel()
    asked = []

    def entry(digest, pubkeys):
        asked.append(vmod._bucket(len(pubkeys), 1))
        return vmod._pool_view(np.zeros((asked[-1], 1)), None, None, asked[-1])

    model._tables_entry = entry
    src = _Counted(64, distinct)
    own = GroupKeys(b"own", np.zeros((1000, 32), dtype=np.uint8))
    assert all(p is not None for p in model._group_pieces(own, src))
    assert src.taken == taken and asked == tables


def test_sparse_rows_of_several_sets_stay_gathered():
    """_SLOT_GATHER_RATIO decides from the same two numbers: slots
    launched against gathered rows, whatever keys the slots hold."""
    few = np.concatenate([np.arange(0, 1015, 40) + k for k in range(4)])  # 26 rows a commit of 1,024 slots
    assert vmod.plan_slots(few, 1024) is None
    full = np.concatenate([np.arange(k, 1000 + k) for k in range(4)])  # 1,000 rows a commit, indices shifting
    plan = vmod.plan_slots(full, 1024)
    assert plan is not None and plan.launches == ((0, 4000, 4),)


# -- the key pool ------------------------------------------------------------------------


def _pk(n, tag="pool"):
    return np.array(
        [np.frombuffer(k.pub_key().bytes(), dtype=np.uint8) for k in loadgen.keys(n, tag=tag)]
    )


def _keys_of(pk, tag=b""):
    import hashlib

    return GroupKeys(hashlib.sha256(tag + pk.tobytes()).digest(), pk)


def _grew(before):
    after = TABLE_COUNTS.snapshot()
    return {k[len("table_"):]: after[k] - before[k] for k in after}


def _column(pool, key_row):
    """The key's table as the pool holds it, in the stages' form."""
    tables = pool._whole.tables if pool.tables is None else pool.tables
    return np.asarray(tables)[pool._col[key_row.tobytes()]].reshape(16, 8, 60)


@pytest.fixture(scope="module")
def pk12():
    return _pk(12)


@pytest.fixture(autouse=True)
def _own_table_files(tmp_path, monkeypatch):
    """No test reads back a key table another test wrote."""
    monkeypatch.setenv("TM_TABLES_CACHE_DIR", str(tmp_path / "tables"))


@pytest.mark.parametrize("key", [3, 4, 5])
def test_a_keys_table_is_the_same_whichever_set_it_was_first_seen_in(pk12, key):
    import jax

    from tendermint_tpu.ops import ed25519 as E

    a, b = vmod.VerifierModel().key_pool, vmod.VerifierModel().key_pool
    assert a.view(_keys_of(pk12[:6])) is not None and a.view(_keys_of(pk12[3:9])) is not None
    assert b.view(_keys_of(pk12[3:9])) is not None
    alone = np.asarray(jax.jit(E.build_valset_tables)(vmod.VerifierModel._pad(None, pk12[key : key + 1], 16))[0])[0]
    np.testing.assert_array_equal(_column(a, pk12[key]), _column(b, pk12[key]))
    np.testing.assert_array_equal(_column(a, pk12[key]), alone)


@pytest.mark.parametrize("fresh", [0, 1, 3])
def test_new_keys_build_in_one_dispatch_and_pooled_keys_build_nothing(pk12, fresh):
    pool = vmod.VerifierModel().key_pool
    assert pool.view(_keys_of(pk12[:6])) is not None
    before, dispatches = TABLE_COUNTS.snapshot(), pool.dispatches
    nxt = np.concatenate([pk12[2:6], pk12[6 : 6 + fresh]])
    e = pool.view(_keys_of(nxt))
    assert e is not None and int(e.tables.shape[0]) == 16
    grew = _grew(before)
    assert grew["keys_built"] == fresh and grew["keys_reused"] == len(nxt)
    assert pool.dispatches - dispatches == (1 if fresh else 0)
    assert len(pool) == 6 + fresh
    # the operand holds the keys asked for, in the order asked
    np.testing.assert_array_equal(np.asarray(e.pk_dev)[: len(nxt)], nxt)
    for i, row in enumerate(nxt):
        np.testing.assert_array_equal(np.asarray(e.tables)[i], _column(pool, row))


def test_a_set_that_is_the_pool_as_it_lies_takes_no_copy(pk12):
    pool = vmod.VerifierModel().key_pool
    before = TABLE_COUNTS.snapshot()
    e = pool.view(_keys_of(pk12[:6]))
    assert e is pool._whole and pool.tables is None and e.pk_dev is pool.pk  # the build itself
    assert pool.view(_keys_of(pk12[:6])) is e
    assert _grew(before)["slabs"] == 0 and pool.nbytes() == 16 * vmod.TABLE_KEY_BYTES
    other = pool.view(_keys_of(pk12[1:6]))  # not from column 0: a slab
    assert other is not e and tuple(other.tables.shape) == tuple(e.tables.shape)
    assert _grew(before)["slabs"] == 1 and _grew(before)["slab_columns"] == 16
    np.testing.assert_array_equal(np.asarray(other.tables)[:5], np.asarray(e.tables)[1:6])
    assert pool.view(_keys_of(pk12[:6])).tables.shape == e.tables.shape  # still all of the pool


def test_eviction_under_the_byte_bound_spares_the_call_in_hand(pk12, monkeypatch):
    monkeypatch.setattr(vmod, "MAX_TABLE_BYTES", 8 * vmod.TABLE_KEY_BYTES)
    pool = vmod.VerifierModel().key_pool
    first = pool.view(_keys_of(pk12[:6]))
    held = np.asarray(first.tables).copy()
    assert pool.view(_keys_of(pk12[4:6])) is not None  # keys 4 and 5 used last
    before = TABLE_COUNTS.snapshot()
    e = pool.view(_keys_of(pk12[2:10]))  # 4 new keys, 2 over the bound; 2..5 are this call's
    assert e is not None and _grew(before)["keys_evicted"] == 2 and len(pool) == 8
    assert {k for k in pool._col} == {r.tobytes() for r in pk12[2:10]}  # 0 and 1 went, not 2 or 3
    np.testing.assert_array_equal(np.asarray(e.pk_dev)[:8], pk12[2:10])
    np.testing.assert_array_equal(np.asarray(first.tables), held)  # an operand handed out stays as it was
    assert pool.view(_keys_of(pk12[:9])) is None  # more keys than the bound holds: the generic path's
    assert pool.view(_keys_of(pk12[2:10])) is not None


def test_table_files_are_kept_by_key_not_by_set(pk12, tmp_path, monkeypatch):
    """A restarted model reads back the keys it finds, whichever build
    wrote them, and builds only the rest; no file is written for a set
    whose keys are all known."""
    import os

    tmp_path = tmp_path / "tables"
    a = vmod.VerifierModel().key_pool
    assert a.view(_keys_of(pk12[:6])) is not None and a.view(_keys_of(pk12[3:9])) is not None
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 2 and files[0].split("-")[-1] != files[1].split("-")[-1]  # 6 keys, then 3
    assert a.view(_keys_of(pk12[1:8])) is not None and sorted(os.listdir(tmp_path)) == files
    b = vmod.VerifierModel().key_pool
    before = TABLE_COUNTS.snapshot()
    assert b.view(_keys_of(pk12[4:11])) is not None  # 4..8 on disk, 9 and 10 new
    grew = _grew(before)
    assert grew["keys_loaded"] == 5 and grew["keys_built"] == 2
    for row in pk12[4:9]:
        np.testing.assert_array_equal(_column(a, row), _column(b, row))


# -- the whole path: verdicts --------------------------------------------------------------

_CASES = {
    "accepted": None,
    "forged before quorum, first commit": _forge(0, 2),
    "forged before quorum, after a change": _forge(5, 1),
    "forged after quorum, after a change": _forge(4, 6),
}


@pytest.mark.parametrize("case", sorted(_CASES))
@pytest.mark.parametrize("changes", sorted(CHANGES))
def test_batched_results_are_the_direct_calls_over_changing_sets(changes, case):
    headers, valsets = _chain(9, CHANGES[changes])
    specs = _fresh(_specs(headers, valsets), _CASES[case])
    want = [_direct(s) for s in specs]
    assert any(want) == ("before quorum" in case)
    stub = GroupStub(3)
    before = seam_counts()
    got = verify_commits_batched(_fresh(specs), provider=stub)
    assert _texts(got) == want
    assert [e[0] for e in stub.events] == ["take", "launch"] * 3  # groups, no eager call
    rows = sum(t[2] for t in stub.of("take"))
    grew = seam_grew(before)
    assert grew["overlapped_rows"] == sum(t[2] for t in stub.of("take")[1:])
    assert grew["multiset_rows"] == (rows if len({s.valset.batch_cache()[0] for s in specs}) > 1 else 0)
    assert stub.row_counts.snapshot() == (rows, 0)
    sets = [len({s.valset.batch_cache()[0] for s in specs[k : k + 3]}) for k in (0, 3, 6)]
    assert [len(k.pubkeys) for k in stub.group_keys] == [V + n - 1 for n in sets]


def test_a_commit_signed_in_the_previous_heights_order_is_rejected_as_serially():
    """Height 5's signatures in height 4's index order: after the
    change the indices shifted, so rows sit under other validators."""
    headers, valsets = _chain(7, (5,))
    specs = _specs(headers, valsets)
    at = 3  # height 5
    order = [valsets[4]._addr_index.get(v.address) for v in valsets[5].validators]
    moved = [i for i, o in enumerate(order) if o is not None and o != i]
    assert moved, "the new key must shift someone"

    def edit(j, sigs):
        if j == at:
            src = list(sigs)
            for i, o in enumerate(order):
                if o is not None:
                    s = src[i]
                    sigs[o] = CommitSig(s.block_id_flag, valsets[5].validators[o].address, s.timestamp_ns, s.signature)

    bad = _fresh(specs, edit)
    want = [_direct(s) for s in bad]
    assert want[at] is not None and want[at][0] == "ErrInvalidCommitSignature"
    assert _texts(verify_commits_batched(_fresh(bad), provider=GroupStub(3))) == want
    assert _texts(verify_commits_batched(_fresh(bad), provider=CPUBatchVerifier())) == want


def test_a_broken_next_validators_hash_link_fails_before_any_signature():
    headers, valsets = _chain(7, (4,))
    stale = dict(valsets)
    stale[4] = valsets[3]  # the set of before the change handed over with height 4
    stub = GroupStub(3)
    with pytest.raises(light.ErrInvalidHeader):
        light.verify_chain(
            loadgen.CHAIN_ID, headers[1], valsets[1], [(headers[h], stale[h]) for h in range(2, 8)],
            PERIOD, now_ns=NOW, provider=stub,
        )
    assert stub.events == []


def test_a_secp256k1_key_in_one_set_falls_back_with_the_same_verdicts():
    ks = loadgen.keys(V + 2, tag="churn")
    odd = Secp256k1PrivKey.from_secret(b"odd-one")
    headers, valsets = loadgen.make_chain(
        6, key_changes={4: ks[1:V] + [odd]}, base_keys=ks[:V],
    )
    specs = _fresh(_specs(headers, valsets), _forge(4, 1))
    want = [_direct(s) for s in specs]
    assert want[4] is not None
    stub = GroupStub(3)
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    assert stub.of("take") == [] and stub.of("arrays") == []  # not the tables' shape
    assert [e[0] for e in stub.events] == ["batch"]


def test_a_trusting_spec_in_the_list_goes_eagerly_with_the_same_verdicts():
    from fractions import Fraction

    headers, valsets = _chain(7, (3, 5))
    specs = _specs(headers, valsets)
    sh = headers[6]
    specs.append(CommitVerifySpec(
        valsets[2], loadgen.CHAIN_ID, sh.commit.block_id, 6, sh.commit,
        mode="trusting", trust_level=Fraction(1, 3),
    ))
    want = _texts(verify_commits_batched(_fresh(specs), provider=CPUBatchVerifier()))
    assert want == [None] * len(specs)
    stub = GroupStub(3)
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    assert stub.of("take") == []  # no groups: everything packed first
    assert stub.events[0][0] == "arrays"  # and offered to the tables as one eager batch
    assert seam_grew(before)["overlapped_rows"] == 0


@pytest.mark.parametrize("decline_at", [0, 1, 2])
def test_a_provider_declining_mid_list_leaves_every_row_to_the_generic_path_once(decline_at):
    headers, valsets = _chain(9, CHANGES["every height"])
    specs = _fresh(_specs(headers, valsets), _forge(5, 1))
    want = [_direct(s) for s in specs]
    stub = GroupStub(3, decline_at=decline_at)
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    total = 8 * V
    assert stub.events[-2:] == [("rows", total), ("batch", total)]
    assert stub.row_counts.snapshot() == (0, total)
    assert seam_grew(before)["multiset_rows"] == 0


# -- the device path, held to the CPU ----------------------------------------------------


@pytest.fixture(scope="module")
def tpu_small():
    """The TPU provider on the CPU with 64-row launches: 4 commits of a
    16-key bucket a launch."""
    from tendermint_tpu.crypto.batch import make_provider

    saved = vmod.MAX_DEVICE_ROWS, vmod.MAX_TABLED_VALSET
    vmod.MAX_DEVICE_ROWS = vmod.MAX_TABLED_VALSET = 64
    yield make_provider("tpu", block_on_compile=True)
    vmod.MAX_DEVICE_ROWS, vmod.MAX_TABLED_VALSET = saved


@pytest.mark.parametrize("changes", ["one set", "two changes", "every height"])
def test_verify_chain_rides_the_key_tables_over_changing_sets(tpu_small, changes):
    """light.verify_chain through the TPU provider: every row in slot
    order on the tables (12 commits: three launches of 4), later groups
    packed under the launches, one key table a distinct key."""
    headers, valsets = _chain(13, CHANGES[changes])
    forged = _forge(6, 6)  # past the quorum point: accepted, one row rejected

    def request():
        chain = []
        for j, h in enumerate(range(1, 14)):
            sigs = list(headers[h].commit.signatures)
            forged(j - 1, sigs)
            c = headers[h].commit
            chain.append(SignedHeader(headers[h].header, Commit(c.height, c.round, c.block_id, sigs)))
        return chain

    distinct = {bytes(r) for h in range(2, 14) for r in valsets[h].batch_cache()[1]}
    known = set(tpu_small.model.key_pool._col)
    counts = [c.snapshot() for c in (TABLE_COUNTS, TABLED_COUNTS, SEAM_COUNTS)]
    dev0, host0 = tpu_small.row_counts.snapshot()
    for _ in range(2):  # the second request finds every key pooled
        chain = request()
        light.verify_chain(
            loadgen.CHAIN_ID, chain[0], valsets[1], [(chain[h - 1], valsets[h]) for h in range(2, 14)],
            PERIOD, now_ns=NOW, provider=tpu_small,
        )
    table, tabled, seam = (
        {k: v - c0[k] for k, v in c.snapshot().items()}
        for c, c0 in zip((TABLE_COUNTS, TABLED_COUNTS, SEAM_COUNTS), counts)
    )
    rows = 2 * 12 * V
    dev, host = tpu_small.row_counts.snapshot()
    assert (dev - dev0, host - host0) == (rows, 0)
    assert tabled["tabled_slot_rows"] == rows and tabled["tabled_gathered_rows"] == 0
    assert table["table_keys_built"] == len(distinct - known)  # once a key, whichever sets it is in
    assert seam["seam_overlapped_rows"] > 0
    assert seam["seam_multiset_rows"] == (0 if changes == "one set" else rows)
    assert set(tpu_small.model.key_pool._col) >= distinct


def test_the_device_rejects_what_the_host_rejects_over_changing_sets(tpu_small):
    headers, valsets = _chain(9, CHANGES["every height"])
    specs = _fresh(_specs(headers, valsets), _forge(5, 1))
    want = [_direct(s) for s in specs]
    assert want[5] is not None
    assert _texts(verify_commits_batched(_fresh(specs), provider=tpu_small)) == want


# -- against the plain reference, on seeded keys ------------------------------------------


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("churn", [0, 1, 2])
def test_verdict_and_rows_equal_the_plain_references(seed, churn):
    """The benchmark's generator and reference (perfbench/: nothing of
    the program) against verify_chain through a provider that takes
    groups: the verdict, and every present row's verdict."""
    from perfbench.entries import program_objects as po
    from perfbench.generators import signed_chain_sets
    from perfbench.reference import chain_sets

    config = {
        "validators": 16, "voting_power": 10, "key_type": "ed25519", "sign_bytes_len": 160,
        "heights": 10, "valset_change_per_height": churn, "chain_id": "t1-churn",
        "block_time_ns": 10**9,
    }
    params = {
        "trusted_headers": 1, "absent_share": [0.0, 0.1], "nil_share": 0.0,
        "tampered": [{"where": "after_quorum", "every": 4}],
    }
    data = signed_chain_sets.generate(config, params, seed)
    vals = [po.validator_set(s) for s in data["sets"]]
    chain = [
        SignedHeader(po.header(el["header"]), po.decoded_commit(v, el["commit"]))
        for v, el in zip(vals, data["chain"])
    ]
    commits = chain_sets.commit_answers(
        data["sets"][1:], data["chain_id"], [el["commit"] for el in data["chain"][1:]]
    )
    want = chain_sets.chain_answer(
        data["sets"], data["chain_id"], data["chain"], commits, 10**18, data["now_ns"]
    )
    assert want["verdict"] == ("accept",) and not want["rows"].all()

    class Recording(GroupStub):
        def verify_rows_cached_templated(self, *a, **kw):
            self.answer = super().verify_rows_cached_templated(*a, **kw)
            return self.answer

    stub = Recording(4)
    outcome = None
    try:
        light.verify_chain(
            data["chain_id"], chain[0], vals[0], list(zip(chain[1:], vals[1:])),
            10**18, now_ns=data["now_ns"], provider=stub,
        )
    except Exception as e:
        outcome = e
    assert po.verdict(outcome) == want["verdict"]
    np.testing.assert_array_equal(stub.answer, want["rows"])
    assert len({tuple(s["pubkeys"]) for s in data["sets"]}) == (10 * bool(churn) + 1)
