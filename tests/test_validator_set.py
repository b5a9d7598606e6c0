"""ValidatorSet: proposer priority distribution, updates, verify_commit.

Mirrors types/validator_set_test.go (proposer-priority properties,
update semantics) and the VerifyCommit acceptance matrix.
"""

from fractions import Fraction

import pytest

from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (
    ErrInvalidCommitSignature,
    ErrNotEnoughVotingPower,
    ValidatorSet,
)
from tendermint_tpu.types.vote import Vote


def make_vals(powers):
    privs = [Ed25519PrivKey.from_secret(f"val{i}".encode()) for i in range(len(powers))]
    vals = [Validator(p.pub_key(), pw) for p, pw in zip(privs, powers)]
    vs = ValidatorSet(vals)
    by_addr = {p.pub_key().address(): p for p in privs}
    return vs, by_addr


def make_commit(vs, by_addr, chain_id="test-chain", height=5, round_=0, bad_idx=None,
                nil_idx=None, absent_idx=None):
    block_id = BlockID(hash=b"\x42" * 32, parts=PartSetHeader(total=1, hash=b"\x43" * 32))
    sigs = []
    for i, val in enumerate(vs.validators):
        if absent_idx is not None and i in absent_idx:
            sigs.append(CommitSig.absent())
            continue
        is_nil = nil_idx is not None and i in nil_idx
        vote_bid = BlockID() if is_nil else block_id
        vote = Vote(
            vote_type=PRECOMMIT_TYPE,
            height=height,
            round=round_,
            block_id=vote_bid,
            timestamp_ns=1000 + i,
            validator_address=val.address,
            validator_index=i,
        )
        priv = by_addr[val.address]
        sig = priv.sign(vote.sign_bytes(chain_id))
        if bad_idx is not None and i in bad_idx:
            sig = bytes(64)
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_NIL if is_nil else BLOCK_ID_FLAG_COMMIT,
                validator_address=val.address,
                timestamp_ns=1000 + i,
                signature=sig,
            )
        )
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs), block_id


class TestProposerRotation:
    def test_proposer_frequency_proportional_to_power(self):
        vs, _ = make_vals([1, 2, 3])
        counts = {}
        for _ in range(600):
            p = vs.get_proposer()
            counts[p.address] = counts.get(p.address, 0) + 1
            vs.increment_proposer_priority(1)
        by_power = sorted(
            (vs.validators[i].voting_power, counts.get(vs.validators[i].address, 0))
            for i in range(3)
        )
        # frequencies should be proportional to voting power: 100/200/300
        for power, cnt in by_power:
            assert abs(cnt - power * 100) <= 3

    def test_single_validator_always_proposer(self):
        vs, _ = make_vals([10])
        addr = vs.validators[0].address
        for _ in range(5):
            assert vs.get_proposer().address == addr
            vs.increment_proposer_priority(1)

    def test_priorities_stay_centered_and_bounded(self):
        vs, _ = make_vals([1, 1, 1, 1000])
        total = vs.total_voting_power()
        for _ in range(200):
            vs.increment_proposer_priority(1)
            ps = [v.proposer_priority for v in vs.validators]
            assert max(ps) - min(ps) <= 2 * total + total  # window bound

    def test_copy_increment_does_not_mutate(self):
        vs, _ = make_vals([1, 2, 3])
        before = [(v.address, v.proposer_priority) for v in vs.validators]
        vs.copy_increment_proposer_priority(3)
        after = [(v.address, v.proposer_priority) for v in vs.validators]
        assert before == after


class TestUpdates:
    def test_add_validator(self):
        vs, _ = make_vals([10, 10])
        new_priv = Ed25519PrivKey.from_secret(b"newval")
        vs.update_with_change_set([Validator(new_priv.pub_key(), 5)])
        assert vs.size() == 3
        assert vs.total_voting_power() == 25
        # new validator starts with lowest priority (not immediately proposer)
        _, v = vs.get_by_address(new_priv.pub_key().address())
        assert v.voting_power == 5

    def test_remove_validator(self):
        vs, _ = make_vals([10, 10, 10])
        victim = vs.validators[0]
        vs.update_with_change_set([Validator(victim.pub_key, 0)])
        assert vs.size() == 2
        assert not vs.has_address(victim.address)

    def test_update_power(self):
        vs, _ = make_vals([10, 10])
        target = vs.validators[1]
        vs.update_with_change_set([Validator(target.pub_key, 42)])
        _, v = vs.get_by_address(target.address)
        assert v.voting_power == 42
        assert vs.total_voting_power() == 52

    def test_remove_nonexistent_fails(self):
        vs, _ = make_vals([10])
        ghost = Ed25519PrivKey.from_secret(b"ghost")
        with pytest.raises(ValueError):
            vs.update_with_change_set([Validator(ghost.pub_key(), 0)])

    def test_empty_set_fails(self):
        vs, _ = make_vals([10])
        with pytest.raises(ValueError):
            vs.update_with_change_set([Validator(vs.validators[0].pub_key, 0)])

    def test_hash_changes_with_set(self):
        vs, _ = make_vals([10, 20])
        h1 = vs.hash()
        vs.update_with_change_set([Validator(vs.validators[0].pub_key, 11)])
        assert vs.hash() != h1


class TestVerifyCommit:
    def test_valid_commit(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        vs.verify_commit("test-chain", bid, 5, commit)

    def test_wrong_height(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        with pytest.raises(Exception):
            vs.verify_commit("test-chain", bid, 6, commit)

    def test_wrong_block_id(self):
        vs, by_addr = make_vals([1] * 4)
        commit, _ = make_commit(vs, by_addr)
        other = BlockID(hash=b"\x99" * 32, parts=PartSetHeader(1, b"\x98" * 32))
        with pytest.raises(Exception):
            vs.verify_commit("test-chain", other, 5, commit)

    def test_insufficient_power(self):
        vs, by_addr = make_vals([1] * 4)
        # two nil votes -> only 2/4 for block, not > 2/3
        commit, bid = make_commit(vs, by_addr, nil_idx={2, 3})
        with pytest.raises(ErrNotEnoughVotingPower):
            vs.verify_commit("test-chain", bid, 5, commit)

    def test_bad_signature_rejected(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr, bad_idx={1})
        with pytest.raises(ErrInvalidCommitSignature):
            vs.verify_commit("test-chain", bid, 5, commit)

    def test_bad_sig_after_quorum_ignored(self):
        """Reference early-return semantics: an invalid signature after
        quorum is crossed must NOT fail verification."""
        vs, by_addr = make_vals([1] * 4)
        # First 3 of 4 give quorum (3 > 2/3*4=2.66); corrupt the last.
        commit, bid = make_commit(vs, by_addr, bad_idx={3})
        vs.verify_commit("test-chain", bid, 5, commit)

    def test_absent_votes_ok_with_quorum(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr, absent_idx={0})
        vs.verify_commit("test-chain", bid, 5, commit)

    def test_wrong_chain_id(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        with pytest.raises(ErrInvalidCommitSignature):
            vs.verify_commit("other-chain", bid, 5, commit)

    def test_trusting_one_third(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        vs.verify_commit_trusting("test-chain", bid, 5, commit, Fraction(1, 3))

    def test_trusting_unknown_validators_skipped(self):
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        # Verify against a larger set that contains the signers plus others
        extra = [Ed25519PrivKey.from_secret(f"x{i}".encode()) for i in range(2)]
        all_vals = [Validator(v.pub_key, v.voting_power) for v in vs.validators]
        all_vals += [Validator(p.pub_key(), 1) for p in extra]
        big = ValidatorSet(all_vals)
        big.verify_commit_trusting("test-chain", bid, 5, commit, Fraction(1, 3))

    def test_trusting_wrong_block_id_rejected(self):
        """verify_commit_trusting must run verifyCommitBasic (review
        finding: mismatched header/commit pairs must not pass)."""
        vs, by_addr = make_vals([1] * 4)
        commit, _ = make_commit(vs, by_addr)
        other = BlockID(hash=b"\x99" * 32, parts=PartSetHeader(1, b"\x98" * 32))
        with pytest.raises(Exception):
            vs.verify_commit_trusting("test-chain", other, 5, commit, Fraction(1, 3))
        with pytest.raises(Exception):
            vs.verify_commit_trusting(
                "test-chain", commit.block_id, 6, commit, Fraction(1, 3)
            )

    def test_oversized_signature_rejected(self):
        """65-byte signature must not be truncated into a valid 64-byte
        prefix (commit-hash malleability)."""
        vs, by_addr = make_vals([1] * 4)
        commit, bid = make_commit(vs, by_addr)
        commit.signatures[0].signature = commit.signatures[0].signature + b"\x00"
        with pytest.raises(Exception):
            vs.verify_commit("test-chain", bid, 5, commit)

    def test_decode_rejects_duplicate_addresses(self):
        vs, _ = make_vals([3, 5])
        from tendermint_tpu.codec.binary import Writer

        w = Writer()
        w.write_uvarint(2)
        enc = vs.validators[0].encode()
        w.write_bytes(enc).write_bytes(enc)
        w.write_bool(False)
        with pytest.raises(ValueError):
            ValidatorSet.decode(w.bytes())


class TestEncoding:
    def test_roundtrip(self):
        vs, _ = make_vals([3, 5, 7])
        data = vs.encode()
        vs2 = ValidatorSet.decode(data)
        assert vs == vs2
        assert vs2.hash() == vs.hash()


def test_sign_bytes_matrix_equals_scalar_path():
    """Commit.sign_bytes_matrix must be byte-identical to per-index
    vote_sign_bytes for every flag combination (commit/nil/absent)."""

    from tests.light_helpers import CHAIN_ID, gen_chain

    headers, valsets = lh_chain = gen_chain(2)
    commit = headers[1].commit
    # mutate flags: make row 1 nil, row 2 absent (4 validators)
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_ABSENT,
        BLOCK_ID_FLAG_NIL,
    )

    commit.signatures[1].block_id_flag = BLOCK_ID_FLAG_NIL
    commit.signatures[2].block_id_flag = BLOCK_ID_FLAG_ABSENT
    commit.signatures[2].validator_address = b""
    commit.signatures[2].signature = b""

    mat = commit.sign_bytes_matrix(CHAIN_ID)
    for i, cs in enumerate(commit.signatures):
        if cs.absent_():
            assert not mat[i].any()
            continue
        want = commit.vote_sign_bytes(CHAIN_ID, i)
        got = bytes(bytearray(mat[i]))
        assert got == want, f"row {i} flag {cs.block_id_flag}"


def test_commit_batch_arrays_vectorized_equivalence():
    """The vectorized _commit_batch_arrays must produce exactly what the
    direct per-row construction would."""

    from tests.light_helpers import CHAIN_ID, gen_chain

    headers, valsets = gen_chain(3)
    commit = headers[2].commit
    vals = valsets[2]
    idxs, vals_idx, pk, mg, sg, powers, counted, ed, tpl = vals._commit_batch_arrays(
        CHAIN_ID, commit, by_address=False
    )
    assert ed.all()  # all-ed25519 set
    assert idxs.tolist() == list(range(4))
    templates, tmpl_idx, ts8 = tpl
    for r, i in enumerate(idxs):
        cs = commit.signatures[i]
        assert bytes(bytearray(mg[r])) == commit.vote_sign_bytes(CHAIN_ID, i)
        assert bytes(bytearray(sg[r])) == cs.signature.ljust(64, b"\x00")
        assert bytes(bytearray(pk[r])) == vals.validators[i].pub_key.bytes()
        assert powers[r] == vals.validators[i].voting_power
        # templated parts materialize to the same row (host-side splice)
        row = templates[tmpl_idx[r]].copy()
        row[93:101] = ts8[r]
        assert bytes(bytearray(row)) == commit.vote_sign_bytes(CHAIN_ID, i)
    # cache invalidation: power change must drop _dev_arrays
    vals._device_arrays()
    assert vals._dev_arrays is not None
    from tendermint_tpu.types.validator import Validator

    changed = vals.validators[0].copy()
    changed.voting_power = 99
    vals.update_with_change_set([changed])
    assert vals._dev_arrays is None
    pk2, powers2, ed2 = vals._device_arrays()
    assert 99 in powers2


def test_mixed_key_type_commit_verification():
    """A validator set containing a secp256k1 key verifies commits
    correctly: ed25519 rows go through the batch provider, the secp row
    through its own key type (reference accepts any registered key type,
    types/validator_set.go:641). Regression: non-32-byte pubkeys must
    never be silently truncated into the ed25519 batch."""
    import pytest

    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import (
        ErrInvalidCommitSignature,
        ValidatorSet,
    )
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    chain_id = "mixed-key-chain"
    eds = [Ed25519PrivKey.from_secret(f"mixed-{i}".encode()) for i in range(3)]
    secp = Secp256k1PrivKey.from_secret(b"mixed-secp")
    privs = eds + [secp]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}

    block_id = BlockID(b"\x42" * 32, PartSetHeader(1, b"\x43" * 32))
    vs = VoteSet(chain_id, 5, 0, PRECOMMIT_TYPE, vals)
    for idx, val in enumerate(vals.validators):
        priv = by_addr[val.address]
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=5, round=0, block_id=block_id,
            timestamp_ns=1234, validator_address=val.address,
            validator_index=idx,
        )
        v.signature = priv.sign(v.sign_bytes(chain_id))
        assert vs.add_vote(v), f"vote {idx} ({type(priv).__name__}) rejected"
    commit = vs.make_commit()

    # full verification accepts the mixed commit
    vals.verify_commit(chain_id, block_id, 5, commit)

    # tampering the secp row's signature is DETECTED (not masked by
    # truncation into an always-failing ed25519 row after quorum)
    secp_idx = next(
        i for i, val in enumerate(vals.validators)
        if len(val.pub_key.bytes()) != 32
    )
    import copy

    commit = copy.deepcopy(commit)  # a verified commit is immutable: tamper with a copy
    sig = bytearray(commit.signatures[secp_idx].signature)
    sig[-1] ^= 1
    commit.signatures[secp_idx].signature = bytes(sig)
    with pytest.raises(ErrInvalidCommitSignature):
        vals.verify_commit(chain_id, block_id, 5, commit)


def test_random_update_sequences_maintain_invariants():
    """Reference TestValSetUpdatesBasicTestsExecute / randValset flavor:
    random sequences of add/update/remove keep the set's invariants —
    sorted unique addresses, total power = sum of powers, priorities
    centered (|avg| bounded) and within the rescale window, proposer
    stability under copy."""
    import random

    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import (
        PRIORITY_WINDOW_SIZE_FACTOR,
        ValidatorSet,
    )

    rng = random.Random(4242)
    keys = [Ed25519PrivKey.from_secret(b"inv%d" % i) for i in range(24)]

    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys[:6]])
    member_idx = set(range(6))

    for step in range(60):
        changes = []
        # removals (power 0) — keep at least 2 members
        removable = sorted(member_idx)
        rng.shuffle(removable)
        for i in removable[: rng.randrange(0, 2)]:
            if len(member_idx) - len(changes) > 2:
                changes.append(Validator(keys[i].pub_key(), 0))
        removed = {f.pub_key.bytes() for f in changes}
        # power updates for current members
        for i in sorted(member_idx):
            if rng.random() < 0.3 and keys[i].pub_key().bytes() not in removed:
                changes.append(
                    Validator(keys[i].pub_key(), rng.randrange(1, 1000))
                )
        # additions
        outside = [i for i in range(len(keys)) if i not in member_idx]
        rng.shuffle(outside)
        for i in outside[: rng.randrange(0, 3)]:
            changes.append(Validator(keys[i].pub_key(), rng.randrange(1, 1000)))
        if not changes:
            continue
        vals.update_with_change_set(changes)
        member_idx = {
            i for i in range(len(keys))
            if vals.has_address(keys[i].pub_key().address())
        }

        # -- invariants ---------------------------------------------------
        addrs = [v.address for v in vals.validators]
        assert addrs == sorted(addrs), f"step {step}: unsorted"
        assert len(set(addrs)) == len(addrs), f"step {step}: duplicate"
        assert vals.total_voting_power() == sum(
            v.voting_power for v in vals.validators
        )
        assert all(v.voting_power > 0 for v in vals.validators)
        # priorities within the rescale window
        prios = [v.proposer_priority for v in vals.validators]
        window = PRIORITY_WINDOW_SIZE_FACTOR * vals.total_voting_power()
        assert max(prios) - min(prios) <= window, f"step {step}: window"
        # proposer is a member and stable across copy
        p = vals.get_proposer()
        assert vals.has_address(p.address)
        assert vals.copy().get_proposer().address == p.address
        # rotation over a full cycle visits high-power validators
    # weighted rotation sanity: over many increments every validator
    # proposes at least once (reference TestProposerSelection3 flavor)
    seen = set()
    for _ in range(len(vals.validators) * 50):
        vals.increment_proposer_priority(1)
        seen.add(vals.get_proposer().address)
    assert seen == {v.address for v in vals.validators}


# -- the column form against a per-row oracle --------------------------------
#
# Commit.columns() reads a commit's slots once into arrays, and the
# structural check, the pack and both replays work on those. Each is held
# here to the plain per-row loop it replaced, written out below.

import copy
import random

import numpy as np

from tendermint_tpu.crypto.batch import SEAM_COUNTS
from tendermint_tpu.crypto.keys import is_batch_ed25519
from tendermint_tpu.types.block import MAX_SIGNATURE_SIZE
from tendermint_tpu.types.validator_set import ErrInvalidCommit

COL_CHAIN = "col-chain"
COL_BID = BlockID(hash=b"\x42" * 32, parts=PartSetHeader(total=1, hash=b"\x43" * 32))


def _raw_sigs(vs, rng, absent=0.2, nil=0.15):
    """Slots of mixed kinds with random bytes for signatures: what the
    structural check and the pack read never depends on their validity."""
    sigs = []
    for v in vs.validators:
        u = rng.random()
        if u < absent:
            sigs.append(CommitSig.absent())
        else:
            flag = BLOCK_ID_FLAG_NIL if u < absent + nil else BLOCK_ID_FLAG_COMMIT
            sigs.append(CommitSig(flag, v.address, 10**18 + rng.randrange(10**12), rng.randbytes(64)))
    return sigs


def _oracle_validate_basic(commit):
    for i, cs in enumerate(commit.signatures):
        err = cs.validate_basic()
        if err:
            return f"wrong CommitSig #{i}: {err}"
    return None


def _oracle_pack(vs, commit, by_address):
    idxs, vals_idx, sg, counted, ed = [], [], [], [], []
    for i, cs in enumerate(commit.signatures):
        if cs.absent_():
            continue
        if len(cs.signature) > MAX_SIGNATURE_SIZE:
            raise ErrInvalidCommit(f"signature #{i} too big ({len(cs.signature)})")
        if by_address:
            vi, val = vs.get_by_address(cs.validator_address)
            if val is None:
                continue
        else:
            vi = i
        idxs.append(i)
        vals_idx.append(vi)
        sg.append(cs.signature[:64].ljust(64, b"\x00"))
        counted.append(cs.for_block())
        ed.append(is_batch_ed25519(vs.validators[vi].pub_key) and len(cs.signature) == 64)
    return idxs, vals_idx, sg, counted, ed


def _oracle_replay_full(vs, commit, ok, idxs, powers, counted):
    needed = vs.total_voting_power() * 2 // 3
    talled = 0
    for r, i in enumerate(idxs):
        if talled > needed:
            return
        if not ok[r]:
            raise ErrInvalidCommitSignature(
                f"wrong signature #{i} ({commit.signatures[i].validator_address.hex()})"
            )
        if counted[r]:
            talled += int(powers[r])
    if talled > needed:
        return
    raise ErrNotEnoughVotingPower(f"have {talled}, need > {needed}")


def _oracle_replay_trusting(vs, ok, idxs, vals_idx, powers, counted, trust_level):
    needed = vs.total_voting_power() * trust_level.numerator // trust_level.denominator
    talled = 0
    seen = set()
    for r, i in enumerate(idxs):
        if talled > needed:
            return
        vi = vals_idx[r]
        if vi in seen:
            raise ErrInvalidCommit(f"double vote from validator index {vi}")
        seen.add(vi)
        if not ok[r]:
            raise ErrInvalidCommitSignature(f"wrong signature #{i}")
        if counted[r]:
            talled += int(powers[r])
    if talled > needed:
        return
    raise ErrNotEnoughVotingPower(f"have {talled}, need > {needed}")


def _outcome(f, *args):
    try:
        f(*args)
    except Exception as e:  # the verdict IS the exception's type and text
        return type(e).__name__, str(e)
    return None


def _set(i, **fields):
    def edit(sigs):
        for k, v in fields.items():
            setattr(sigs[i], k, v)
    return edit


def _both(*edits):
    def edit(sigs):
        for e in edits:
            e(sigs)
    return edit


_VB_CASES = {
    "valid": (lambda sigs: None, None),
    "flag-0": (_set(3, block_id_flag=0), "wrong CommitSig #3: unknown BlockIDFlag: 0"),
    "flag-4": (_set(3, block_id_flag=4), "wrong CommitSig #3: unknown BlockIDFlag: 4"),
    "flag-300": (_set(3, block_id_flag=300), "wrong CommitSig #3: unknown BlockIDFlag: 300"),
    "flag-negative": (_set(3, block_id_flag=-1), "wrong CommitSig #3: unknown BlockIDFlag: -1"),
    "absent-with-address": (
        _set(1, block_id_flag=BLOCK_ID_FLAG_ABSENT, signature=b""),
        "wrong CommitSig #1: validator address is present for absent CommitSig",
    ),
    "absent-with-signature": (
        _set(1, block_id_flag=BLOCK_ID_FLAG_ABSENT, validator_address=b""),
        "wrong CommitSig #1: signature is present for absent CommitSig",
    ),
    "absent-with-both": (
        _set(1, block_id_flag=BLOCK_ID_FLAG_ABSENT),
        "wrong CommitSig #1: validator address is present for absent CommitSig",
    ),
    "address-19": (
        _set(5, validator_address=b"\x07" * 19),
        "wrong CommitSig #5: expected ValidatorAddress size 20",
    ),
    "address-empty": (
        _set(5, validator_address=b""), "wrong CommitSig #5: expected ValidatorAddress size 20",
    ),
    "address-300": (
        _set(5, validator_address=b"\x07" * 300),
        "wrong CommitSig #5: expected ValidatorAddress size 20",
    ),
    "signature-missing": (_set(0, signature=b""), "wrong CommitSig #0: signature is missing"),
    "signature-97": (
        _set(7, signature=b"\x01" * (MAX_SIGNATURE_SIZE + 1)),
        "wrong CommitSig #7: signature too big",
    ),
    "signature-300": (_set(7, signature=b"\x01" * 300), "wrong CommitSig #7: signature too big"),
    "signature-96-is-fine": (_set(7, signature=b"\x01" * MAX_SIGNATURE_SIZE), None),
    "two-faults-address-first": (
        _set(2, validator_address=b"\x07" * 21, signature=b""),
        "wrong CommitSig #2: expected ValidatorAddress size 20",
    ),
    "two-faults-flag-first": (
        _set(2, block_id_flag=9, validator_address=b"", signature=b"\x01" * 200),
        "wrong CommitSig #2: unknown BlockIDFlag: 9",
    ),
    "two-rows-lower-wins": (
        _both(_set(6, signature=b""), _set(2, signature=b"\x01" * 120)),
        "wrong CommitSig #2: signature too big",
    ),
    "last-row": (_set(7, validator_address=b"\x07"), "wrong CommitSig #7: expected ValidatorAddress size 20"),
}


@pytest.mark.parametrize("case", sorted(_VB_CASES))
def test_columns_validate_basic_matches_per_row_loop(case):
    """The same string for the same first failing index and the same
    first failing check as CommitSig.validate_basic row by row."""
    edit, want = _VB_CASES[case]
    vs, _ = make_vals([1] * 8)
    sigs = _raw_sigs(vs, random.Random(1), absent=0.0)
    edit(sigs)
    commit = Commit(5, 0, COL_BID, sigs)
    assert commit.validate_basic() == want
    assert _oracle_validate_basic(commit) == want


@pytest.mark.parametrize("seed", range(6))
def test_columns_validate_basic_random_faults(seed):
    rng = random.Random(seed)
    vs, _ = make_vals([1] * 40)
    faults = [
        dict(block_id_flag=0), dict(block_id_flag=77), dict(block_id_flag=1000),
        dict(validator_address=b""), dict(validator_address=b"\x01" * 32),
        dict(signature=b""), dict(signature=b"\x02" * 97), dict(signature=b"\x02" * 1000),
        dict(block_id_flag=BLOCK_ID_FLAG_ABSENT), dict(block_id_flag=BLOCK_ID_FLAG_NIL),
    ]
    for _ in range(40):
        sigs = _raw_sigs(vs, rng)
        for _ in range(rng.randrange(0, 4)):
            cs = sigs[rng.randrange(len(sigs))]
            for k, v in rng.choice(faults).items():
                setattr(cs, k, v)
        commit = Commit(5, 0, COL_BID, sigs)
        assert commit.validate_basic() == _oracle_validate_basic(commit)


def _assert_pack_equals_oracle(vs, commit, by_address):
    want = _outcome(_oracle_pack, vs, commit, by_address)
    if want is not None:
        assert _outcome(vs._commit_batch_arrays, COL_CHAIN, commit, by_address) == want
        return None
    idxs, vals_idx, sg, counted, ed = _oracle_pack(vs, commit, by_address)
    got = vs._commit_batch_arrays(COL_CHAIN, commit, by_address)
    g_idxs, g_vals_idx, g_pk, g_mg, g_sg, g_powers, g_counted, g_ed, (tmpl, tmpl_idx, ts8) = got
    n = len(idxs)
    assert (g_idxs.dtype, g_idxs.tolist()) == (np.int64, idxs)
    assert (g_vals_idx.dtype, g_vals_idx.tolist()) == (np.int64, vals_idx)
    assert (g_sg.dtype, g_sg.shape) == (np.uint8, (n, 64))
    assert [bytes(row) for row in g_sg] == sg
    assert (g_counted.dtype, g_counted.tolist()) == (np.bool_, counted)
    assert (g_ed.dtype, g_ed.tolist()) == (np.bool_, ed)
    assert (g_pk.dtype, g_pk.shape) == (np.uint8, (n, 32))
    assert (g_powers.dtype, g_powers.tolist()) == (
        np.int64, [vs.validators[vi].voting_power for vi in vals_idx]
    )
    assert (g_mg.dtype, g_mg.shape) == (np.uint8, (n, 160))
    assert (tmpl.dtype, tmpl.shape) == (np.uint8, (2, 160))
    assert (tmpl_idx.dtype, tmpl_idx.shape) == (np.int32, (n,))
    assert (ts8.dtype, ts8.shape) == (np.uint8, (n, 8))
    for r, (i, vi) in enumerate(zip(idxs, vals_idx)):
        assert bytes(g_mg[r]) == commit.vote_sign_bytes(COL_CHAIN, i)
        row = tmpl[tmpl_idx[r]].copy()
        row[93:101] = ts8[r]
        assert bytes(row) == commit.vote_sign_bytes(COL_CHAIN, i)
        if ed[r]:
            assert bytes(g_pk[r]) == vs.validators[vi].pub_key.bytes()
    return got


@pytest.mark.parametrize("by_address", [False, True], ids=["by-index", "by-address"])
@pytest.mark.parametrize("seed", range(4))
def test_columns_pack_matches_per_row_loop(seed, by_address):
    """idxs, vals_idx, sg, counted, ed and tpl — values, shapes and
    dtypes — on commits that mix absent, nil and for-block slots."""
    rng = random.Random(100 + seed)
    vs, _ = make_vals([rng.randrange(1, 50) for _ in range(33)])
    for absent in (0.0, 0.2, 1.0):
        commit = Commit(5, 0, COL_BID, _raw_sigs(vs, rng, absent=absent))
        _assert_pack_equals_oracle(vs, commit, by_address)


@pytest.mark.parametrize("length", [0, 1, 63, 65, 80, 96])
def test_columns_pack_off_width_signature_leaves_the_batch(length):
    """An ed25519 row whose signature is not 64 bytes is clamped or
    padded in sg and leaves the ed mask (it is verified by its own key
    type, which refuses the length); its neighbours stay where they were."""
    vs, _ = make_vals([1] * 6)
    sigs = _raw_sigs(vs, random.Random(7), absent=0.0)
    sigs[1] = CommitSig.absent()
    sigs[3].signature = bytes(range(1, length + 1))
    commit = Commit(5, 0, COL_BID, sigs)
    got = _assert_pack_equals_oracle(vs, commit, False)
    assert got[7].tolist() == [True, True, False, True, True]
    assert bytes(got[4][2]) == bytes(range(1, length + 1))[:64].ljust(64, b"\x00")
    assert bytes(got[4][3]) == sigs[4].signature


def test_columns_pack_refuses_first_oversized_signature():
    vs, _ = make_vals([1] * 6)
    sigs = _raw_sigs(vs, random.Random(8), absent=0.0)
    sigs[0] = CommitSig.absent()
    sigs[4].signature = b"\x01" * 300
    sigs[2].signature = b"\x01" * (MAX_SIGNATURE_SIZE + 1)
    for by_address in (False, True):
        commit = Commit(5, 0, COL_BID, sigs)
        with pytest.raises(ErrInvalidCommit, match=r"^signature #2 too big \(97\)$"):
            vs._commit_batch_arrays(COL_CHAIN, commit, by_address)
        _assert_pack_equals_oracle(vs, commit, by_address)


def test_columns_pack_by_address_skips_unknown_signers():
    """The trusting mode looks signers up by address in THIS set: the
    commit is another set's, in its order, and strangers are dropped."""
    vs, _ = make_vals([3, 5, 7, 9])
    stranger = Ed25519PrivKey.from_secret(b"stranger").pub_key().address()
    sigs = _raw_sigs(vs, random.Random(9), absent=0.0)
    sigs = [sigs[2], CommitSig(BLOCK_ID_FLAG_COMMIT, stranger, 5, b"\x05" * 64), sigs[0],
            CommitSig.absent(), sigs[3], sigs[2]]
    commit = Commit(5, 0, COL_BID, sigs)
    before = SEAM_COUNTS.snapshot()
    got = _assert_pack_equals_oracle(vs, commit, True)
    after = SEAM_COUNTS.snapshot()
    assert got[0].tolist() == [0, 2, 4, 5]
    assert got[1].tolist() == [2, 0, 3, 2]
    assert got[5].tolist() == [vs.validators[vi].voting_power for vi in (2, 0, 3, 2)]
    assert after["seam_packed_rows"] - before["seam_packed_rows"] == 4
    assert after["seam_fixup_rows"] - before["seam_fixup_rows"] == 1  # the stranger


_FULL_REPLAY_CASES = {
    # nine present rows of power 10: 90 in all, more than 60 needed,
    # so the 7th for-block row carries the tally past the quorum
    "all-sound": ({}, None),
    "forged-before-quorum": ({"bad": [2]}, ("ErrInvalidCommitSignature", "wrong signature #3 (")),
    "forged-on-the-quorum-row": ({"bad": [6]}, ("ErrInvalidCommitSignature", "wrong signature #7 (")),
    "forged-after-quorum": ({"bad": [7, 8]}, None),
    "quorum-on-the-last-row": ({"nil": [3, 5]}, None),
    "quorum-on-the-last-row-forged": (
        {"nil": [3, 5], "bad": [8]}, ("ErrInvalidCommitSignature", "wrong signature #9 (")
    ),
    "forged-nil-row-before-quorum": (
        {"nil": [3, 5], "bad": [5]}, ("ErrInvalidCommitSignature", "wrong signature #6 (")
    ),
    "one-signer-short": ({"nil": [0, 4, 8]}, ("ErrNotEnoughVotingPower", "have 60, need > 60")),
    "no-for-block-row": ({"nil": list(range(9))}, ("ErrNotEnoughVotingPower", "have 0, need > 60")),
}


@pytest.mark.parametrize("case", sorted(_FULL_REPLAY_CASES))
def test_replay_full_matches_the_early_return_loop(case):
    spec, want = _FULL_REPLAY_CASES[case]
    vs, _ = make_vals([10] * 10)
    sigs = _raw_sigs(vs, random.Random(3), absent=0.0, nil=0.0)
    sigs[0] = CommitSig.absent()  # rows are slots 1..9: row r is signature r + 1
    commit = Commit(5, 0, COL_BID, sigs)
    idxs = np.arange(1, 10)
    vs9, _ = make_vals([10] * 9)
    powers = np.full(9, 10, dtype=np.int64)
    counted = np.ones(9, dtype=bool)
    counted[spec.get("nil", [])] = False
    ok = np.ones(9, dtype=bool)
    ok[spec.get("bad", [])] = False
    got = _outcome(vs9._replay_commit_full, commit, ok, idxs, powers, counted)
    assert got == _outcome(_oracle_replay_full, vs9, commit, ok, idxs.tolist(), powers, counted)
    if want is None:
        assert got is None
    else:
        assert got[0] == want[0] and got[1].startswith(want[1])
        if want[0] == "ErrInvalidCommitSignature":
            i = int(got[1].split("#")[1].split()[0])
            assert got[1] == f"wrong signature #{i} ({sigs[i].validator_address.hex()})"


def test_replay_full_of_no_rows():
    vs, _ = make_vals([10] * 3)
    commit = Commit(5, 0, COL_BID, [CommitSig.absent()] * 3)
    empty = np.zeros(0, dtype=np.int64)
    got = _outcome(vs._replay_commit_full, commit, empty.astype(bool), empty, empty, empty.astype(bool))
    assert got == ("ErrNotEnoughVotingPower", "have 0, need > 20")


_TRUSTING_REPLAY_CASES = {
    # nine rows of power 10 against a set of 90 at trust level 1/3:
    # more than 30 needed, so the 4th for-block row is the last visited
    "all-sound": ({}, None),
    "duplicate-before-quorum": ({"dup": {2: 0}}, ("ErrInvalidCommit", "double vote from validator index 0")),
    "duplicate-on-the-quorum-row": ({"dup": {3: 1}}, ("ErrInvalidCommit", "double vote from validator index 1")),
    "duplicate-after-quorum": ({"dup": {4: 0, 8: 2}}, None),
    "forged-before-quorum": ({"bad": [1]}, ("ErrInvalidCommitSignature", "wrong signature #11")),
    "forged-after-quorum": ({"bad": [4, 7]}, None),
    "duplicate-and-forged-same-row": (
        {"dup": {2: 1}, "bad": [2]}, ("ErrInvalidCommit", "double vote from validator index 1")
    ),
    "forged-then-duplicate": ({"bad": [1], "dup": {2: 0}}, ("ErrInvalidCommitSignature", "wrong signature #11")),
    "duplicate-then-forged": (
        {"dup": {1: 0}, "bad": [2]}, ("ErrInvalidCommit", "double vote from validator index 0")
    ),
    "third-vote-of-one-validator": (
        {"dup": {1: 0, 2: 0}}, ("ErrInvalidCommit", "double vote from validator index 0")
    ),
    "nil-rows-move-the-quorum-row": (
        {"nil": [0, 1], "dup": {5: 4}}, ("ErrInvalidCommit", "double vote from validator index 4")
    ),
    "one-signer-short": (
        {"nil": [0, 1, 2, 3, 4, 5]}, ("ErrNotEnoughVotingPower", "have 30, need > 30")
    ),
}


@pytest.mark.parametrize("case", sorted(_TRUSTING_REPLAY_CASES))
def test_replay_trusting_matches_the_early_return_loop(case):
    spec, want = _TRUSTING_REPLAY_CASES[case]
    vs, _ = make_vals([10] * 9)
    idxs = np.arange(10, 19)  # the commit is another set's: its own slot numbers
    vals_idx = np.arange(9)
    for r, vi in spec.get("dup", {}).items():
        vals_idx[r] = vi
    powers = np.full(9, 10, dtype=np.int64)
    counted = np.ones(9, dtype=bool)
    counted[spec.get("nil", [])] = False
    ok = np.ones(9, dtype=bool)
    ok[spec.get("bad", [])] = False
    level = Fraction(1, 3)
    got = _outcome(vs._replay_commit_trusting, ok, idxs, vals_idx, powers, counted, level)
    assert got == want
    assert got == _outcome(
        _oracle_replay_trusting, vs, ok, idxs.tolist(), vals_idx.tolist(), powers, counted, level
    )


@pytest.mark.parametrize("seed", range(8))
def test_replays_match_the_loops_on_random_rows(seed):
    """Random powers, nil rows, forged rows and repeated signers: both
    replays give the loops' verdict, text included, wherever the faults
    fall about the quorum row."""
    rng = random.Random(900 + seed)
    for _ in range(150):
        n_vals = rng.randrange(1, 24)
        vs, _ = make_vals([rng.randrange(1, 100) for _ in range(n_vals)])
        sigs = _raw_sigs(vs, rng, absent=0.0, nil=0.0)
        commit = Commit(5, 0, COL_BID, sigs)
        keep = sorted(rng.sample(range(n_vals), rng.randrange(0, n_vals + 1)))
        idxs = np.asarray(keep, dtype=np.int64)
        n = len(keep)
        counted = np.asarray([rng.random() < 0.8 for _ in keep], dtype=bool)
        ok = np.asarray([rng.random() < 0.93 for _ in keep], dtype=bool)
        all_powers = np.asarray([v.voting_power for v in vs.validators], dtype=np.int64)
        powers = all_powers[idxs]
        assert _outcome(vs._replay_commit_full, commit, ok, idxs, powers, counted) == _outcome(
            _oracle_replay_full, vs, commit, ok, keep, powers, counted
        )
        vals_idx = idxs.copy()
        for _ in range(rng.randrange(0, 3)):
            if n >= 2:
                a, b = rng.sample(range(n), 2)
                vals_idx[a] = vals_idx[b]
        powers = all_powers[vals_idx] if n else powers
        level = rng.choice([Fraction(1, 3), Fraction(1, 2), Fraction(2, 3), Fraction(1, 1)])
        assert _outcome(
            vs._replay_commit_trusting, ok, idxs, vals_idx, powers, counted, level
        ) == _outcome(
            _oracle_replay_trusting, vs, ok, keep, vals_idx.tolist(), powers, counted, level
        )


def test_columns_die_with_the_commit():
    """No memo outlives a Commit: every fresh Commit over one shared
    CommitSig list reads its own columns (the counter says so), a Commit
    reads them once, and a deep copy starts without them — so a copy with
    a tampered signature is packed, and refused, from the tampered bytes."""
    vs, by_addr = make_vals([1] * 6)
    commit, bid = make_commit(vs, by_addr, absent_idx={1})
    shared = commit.signatures
    before = SEAM_COUNTS.snapshot()
    for _ in range(2):
        fresh = Commit(5, 0, bid, shared)
        vs.verify_commit("test-chain", bid, 5, fresh)
    twice = SEAM_COUNTS.snapshot()
    assert twice["seam_column_rows"] - before["seam_column_rows"] == 2 * 6
    assert twice["seam_packed_rows"] - before["seam_packed_rows"] == 2 * 5
    assert twice["seam_fixup_rows"] == before["seam_fixup_rows"]
    vs.verify_commit("test-chain", bid, 5, fresh)
    again = SEAM_COUNTS.snapshot()
    assert again["seam_column_rows"] == twice["seam_column_rows"]
    assert again["seam_packed_rows"] - twice["seam_packed_rows"] == 5

    forged = copy.deepcopy(fresh)
    assert not hasattr(forged, "_cols_cache") and hasattr(fresh, "_cols_cache")
    sig = bytearray(forged.signatures[0].signature)
    sig[5] ^= 0x40
    forged.signatures[0].signature = bytes(sig)
    packed = vs._commit_batch_arrays("test-chain", forged, by_address=False)
    assert bytes(packed[4][0]) == bytes(sig)
    with pytest.raises(ErrInvalidCommitSignature, match="wrong signature #0"):
        vs.verify_commit("test-chain", bid, 5, forged)
    assert SEAM_COUNTS.snapshot()["seam_column_rows"] - again["seam_column_rows"] == 6
    vs.verify_commit("test-chain", bid, 5, fresh)  # the original stands


# -- the overlapped seam: a spec list's rows taken a group at a time --------
#
# verify_commits_batched hands a same-set chain to a provider that takes
# row groups as a lazy source (crypto/batch.RowGroups): group k+1 is
# packed only when the provider comes back for it, launch k dispatched.
# tests/seam_helpers.GroupStub is such a provider on the host; what is
# pinned is counts and orders of events, and that every result is the
# direct call's.

from tendermint_tpu.types.validator_set import CommitVerifySpec, verify_commits_batched
from tests.seam_helpers import GroupStub, seam_counts, seam_grew

_PER = 3  # commits the stub takes at a time


def _chain_specs(n, faults=None, vs=None, by_addr=None):
    """n full-mode specs of one 7-validator set (power 10 each: the
    quorum falls on row 4, rows 5 and 6 are never visited).
    ``faults[j]``: ("forge", i) a zeroed signature in slot i,
    ("height",) a spec that asks for another height than its commit's,
    ("short", i) slot i's signature cut to 63 bytes."""
    if vs is None:
        vs, by_addr = make_vals([10] * 7)
    specs = []
    for j in range(n):
        fault = (faults or {}).get(j, ())
        commit, bid = make_commit(
            vs, by_addr, height=5 + j, absent_idx={j % 7} if j % 2 else None,
            bad_idx={fault[1]} if fault[:1] == ("forge",) else None,
        )
        if fault[:1] == ("short",):
            cs = commit.signatures[fault[1]]
            commit.signatures[fault[1]] = CommitSig(
                cs.block_id_flag, cs.validator_address, cs.timestamp_ns, cs.signature[:63]
            )
        height = 5 + j + (100 if fault == ("height",) else 0)
        specs.append(CommitVerifySpec(vs, "test-chain", bid, height, commit))
    return specs


def _fresh(specs):
    """The same specs over Commit objects nothing has read yet."""
    return [
        CommitVerifySpec(
            s.valset, s.chain_id, s.block_id, s.height,
            Commit(s.commit.height, s.commit.round, s.commit.block_id, list(s.commit.signatures)),
            mode=s.mode, trust_level=s.trust_level,
        )
        for s in specs
    ]


def _direct(spec):
    """What the direct method call makes of the spec: None or
    (exception type, text)."""
    (s,) = _fresh([spec])
    if s.mode == "trusting":
        return _outcome(
            s.valset.verify_commit_trusting, s.chain_id, s.block_id, s.height, s.commit, s.trust_level
        )
    return _outcome(s.valset.verify_commit, s.chain_id, s.block_id, s.height, s.commit)


def _texts(results):
    return [None if e is None else (type(e).__name__, str(e)) for e in results]


def _rows_of(specs, want):
    """Present rows of the specs that pass their pre-checks."""
    return [
        sum(not cs.absent_() for cs in s.commit.signatures)
        for s, w in zip(specs, want)
        if w is None or w[0] != "ErrInvalidCommit"
    ]


_OVERLAP_CASES = {
    "accepted chain": (9, {}),
    "forged before quorum, first group": (8, {1: ("forge", 2)}),
    "forged before quorum, middle group": (8, {4: ("forge", 2)}),
    "forged before quorum, last group": (8, {7: ("forge", 2)}),
    "forged after quorum, first group": (8, {0: ("forge", 6)}),
    "forged after quorum, middle group": (8, {3: ("forge", 6)}),
    "forged after quorum, last group": (8, {6: ("forge", 6)}),
    "pre-checks fail mid-list": (8, {4: ("height",)}),
    "a whole group fails its pre-checks": (8, {3: ("height",), 4: ("height",), 5: ("height",)}),
    "not a multiple of the group": (7, {2: ("forge", 0), 6: ("forge", 1)}),
    "a list of one": (1, {}),
    "a list of one, forged": (1, {0: ("forge", 3)}),
}


@pytest.mark.parametrize("case", sorted(_OVERLAP_CASES))
def test_overlapped_seam_results_are_the_direct_calls(case):
    """(a) and (b): one result a spec, the direct call's exception type
    and text; group k+1 has its columns read only after launch k; the
    counter reads the rows of groups 2..n."""
    n, faults = _OVERLAP_CASES[case]
    specs = _chain_specs(n, faults)
    want = [_direct(s) for s in specs]
    assert [w is not None for w in want] == [
        j in faults and faults[j] != ("forge", 6) for j in range(n)
    ]

    stub = GroupStub(_PER)
    before = seam_counts()
    got = verify_commits_batched(_fresh(specs), provider=stub)
    grew = seam_grew(before)
    assert _texts(got) == want

    groups = -(-n // _PER)
    takes = stub.of("take")
    assert [e[0] for e in stub.events] == ["take", "launch"] * groups  # no eager call
    # when group k is handed over, only the commits up to its end have
    # had columns() read: the launches before it were on their way
    assert [t[3] for t in takes] == [7 * min((k + 1) * _PER, n) for k in range(groups)]
    rows = _rows_of(specs, want)
    assert sum(t[2] for t in takes) == sum(rows) == grew["packed_rows"]
    assert grew["overlapped_rows"] == sum(t[2] for t in takes[1:])
    assert grew["column_rows"] == 7 * n and grew["fixup_rows"] == 0
    assert stub.row_counts.snapshot() == (sum(rows), 0)


@pytest.mark.parametrize("decline_at", [0, 1, 2])
def test_overlapped_seam_provider_declines(decline_at):
    """(c): the provider answers None at group 0 or later; the generic
    path sees every row exactly once, the results are the direct
    calls', and no row is counted twice."""
    faults = {1: ("forge", 2), 4: ("height",), 7: ("forge", 6)}
    specs = _chain_specs(8, faults)
    want = [_direct(s) for s in specs]
    total = sum(_rows_of(specs, want))

    stub = GroupStub(_PER, decline_at=decline_at)
    before = seam_counts()
    got = verify_commits_batched(_fresh(specs), provider=stub)
    assert _texts(got) == want
    assert len(stub.of("take")) == decline_at + 1 and len(stub.of("launch")) == decline_at
    # the rest is packed by the seam, then ONE eager batch: the cached
    # path's materialized form, then the generic kernel — not the
    # templated form the provider has just declined
    assert stub.events[2 * decline_at + 1 :] == [("rows", total), ("batch", total)]
    assert stub.row_counts.snapshot() == (0, total)
    grew = seam_grew(before)
    assert grew["packed_rows"] == total and grew["column_rows"] == 7 * 8


def test_overlapped_seam_short_signature_met_mid_list():
    """A non-64-byte signature is seen only when its commit is packed:
    the source declines at that group and the whole list takes the
    path of lists with rows off the common shape."""
    specs = _chain_specs(8, {4: ("short", 2), 6: ("forge", 1)})
    want = [_direct(s) for s in specs]
    assert want[4] == ("ErrInvalidCommitSignature", want[4][1]) and "#2" in want[4][1]
    stub = GroupStub(_PER)
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    total = sum(_rows_of(specs, want))
    assert [t[2] for t in stub.of("take")] == [sum(_rows_of(specs[:3], want[:3])), None]
    assert stub.of("batch") == [("batch", total - 1)]  # the short row verifies by itself
    assert stub.row_counts.snapshot() == (0, total - 1)
    assert seam_grew(before)["fixup_rows"] == 1


def _two_sets():
    vs, by_addr = make_vals([10] * 7)
    privs = [Ed25519PrivKey.from_secret(f"other{i}".encode()) for i in range(7)]
    other = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    return _chain_specs(4, {1: ("forge", 2)}, vs, by_addr) + _chain_specs(
        4, {2: ("forge", 0)}, other, {p.pub_key().address(): p for p in privs}
    )


def _with_trusting_spec():
    specs = _chain_specs(5, {3: ("forge", 1)})
    s = specs[2]
    specs[2] = CommitVerifySpec(
        s.valset, s.chain_id, s.block_id, s.height, s.commit,
        mode="trusting", trust_level=Fraction(1, 3),
    )
    total = sum(_rows_of(specs, [None] * 5))
    return specs, [("arrays", total), ("rows", total), ("batch", total)]


def _with_secp_key():
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    privs = [Ed25519PrivKey.from_secret(f"val{i}".encode()) for i in range(6)]
    privs.append(Secp256k1PrivKey.from_secret(b"seam-secp"))
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    specs = _chain_specs(4, {1: ("forge", 0)}, vs, {p.pub_key().address(): p for p in privs})
    secp_rows = sum(
        not cs.absent_() and len(vs.validators[i].pub_key.bytes()) != 32
        for s in specs for i, cs in enumerate(s.commit.signatures)
    )
    return specs, [("batch", sum(_rows_of(specs, [None] * 4)) - secp_rows)]


def test_a_list_over_two_ed25519_sets_is_taken_as_groups_with_their_own_keys():
    """A list that straddles a change of set stays a chain: each group
    names the distinct keys of its commits' sets (7, both sets' 14, 7)
    and its rows index them; the results are the direct calls'."""
    specs = _two_sets()
    want = [_direct(s) for s in specs]
    assert [w is not None for w in want] == [j in (1, 6) for j in range(8)]
    stub = GroupStub(_PER)
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    assert [e[0] for e in stub.events] == ["take", "launch"] * 3
    assert [len(k.pubkeys) for k in stub.group_keys] == [7, 14, 7]
    rows = sum(_rows_of(specs, want))
    grew = seam_grew(before)
    assert grew["multiset_rows"] == rows == sum(t[2] for t in stub.of("take"))
    assert grew["overlapped_rows"] == sum(t[2] for t in stub.of("take")[1:])


@pytest.mark.parametrize(
    "build", [_with_trusting_spec, _with_secp_key],
    ids=["a trusting spec", "a non-ed25519 key"],
)
def test_lists_that_are_no_same_set_chain_take_the_eager_path(build):
    """(d): nothing is taken as groups, nothing counts as overlapped,
    the eager calls are the ones made before there were groups."""
    specs, eager_calls = build()
    want = [_direct(s) for s in specs]
    assert any(w is not None for w in want)
    stub = GroupStub(_PER)
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    assert stub.events == eager_calls
    assert seam_grew(before)["overlapped_rows"] == 0


def test_provider_that_takes_no_groups_gets_one_eager_batch():
    """A provider without ``takes_row_groups`` (the CPU provider, a
    pipeline, a mesh router) is handed arrays, as before."""
    specs = _chain_specs(7, {5: ("forge", 2)})
    want = [_direct(s) for s in specs]
    stub = GroupStub(_PER)
    stub.takes_row_groups = False
    before = seam_counts()
    assert _texts(verify_commits_batched(_fresh(specs), provider=stub)) == want
    total = sum(_rows_of(specs, want))
    assert stub.events == [("arrays", total), ("rows", total), ("batch", total)]
    assert seam_grew(before)["overlapped_rows"] == 0
