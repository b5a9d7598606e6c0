"""Light client: verifier rules, bisection, witnesses, backwards.

Mirrors reference lite2/verifier_test.go (table-driven adjacent /
non-adjacent cases) and lite2/client_test.go (bisection, trust options,
witness conflict).
"""

import asyncio

import pytest

from tendermint_tpu.db.memdb import MemDB
from tendermint_tpu.light import (
    LightClient,
    TrustOptions,
    verify_adjacent,
    verify_backwards,
    verify_non_adjacent,
)
from tendermint_tpu.light.client import ErrConflictingHeaders
from tendermint_tpu.light.provider import MockProvider
from tendermint_tpu.light.store import TrustedStore
from tendermint_tpu.light.verifier import (
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
)
from tests.light_helpers import CHAIN_ID, T0, gen_chain, keys, valset

PERIOD = 3 * 3600 * 10**9  # 3h
NOW = T0 + 600 * 10**9  # 10min after genesis


def run(coro):
    return asyncio.run(coro)


# -- verifier --------------------------------------------------------------


def test_verify_adjacent_ok_and_hash_chain():
    headers, vals = gen_chain(3)
    verify_adjacent(
        CHAIN_ID, headers[1], headers[2], vals[2], PERIOD, now_ns=NOW
    )
    # tampered: wrong untrusted valset
    other = valset(keys(4, tag="other"))
    with pytest.raises(ErrInvalidHeader):
        verify_adjacent(CHAIN_ID, headers[1], headers[2], other, PERIOD, now_ns=NOW)


def test_verify_adjacent_rejects_expired_trusted():
    headers, vals = gen_chain(2)
    with pytest.raises(ErrOldHeaderExpired):
        verify_adjacent(
            CHAIN_ID, headers[1], headers[2], vals[2], PERIOD,
            now_ns=T0 + PERIOD + 2 * 10**9,
        )


def test_verify_adjacent_rejects_valset_break():
    """Validator change NOT announced in next_validators_hash fails."""
    headers, vals = gen_chain(3, key_changes={3: keys(4, tag="new")})
    # headers[2].next_validators_hash points at the new set; lie about it
    bad_vals = valset(keys(4, tag="liar"))
    with pytest.raises(ErrInvalidHeader):
        verify_adjacent(CHAIN_ID, headers[2], headers[3], bad_vals, PERIOD, now_ns=NOW)
    # the honest new set passes
    verify_adjacent(
        CHAIN_ID, headers[2], headers[3], vals[3], PERIOD, now_ns=NOW
    )


def test_verify_non_adjacent_with_overlap():
    headers, vals = gen_chain(10)
    verify_non_adjacent(
        CHAIN_ID, headers[1], vals[1], headers[9], vals[9], PERIOD, now_ns=NOW
    )


def test_verify_non_adjacent_full_valset_swap_refused():
    """Total validator replacement between trusted and new → can't trust."""
    headers, vals = gen_chain(10, key_changes={5: keys(4, tag="swapped")})
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(
            CHAIN_ID, headers[1], vals[1], headers[9], vals[9], PERIOD, now_ns=NOW
        )


def test_verify_backwards():
    headers, _ = gen_chain(3)
    verify_backwards(CHAIN_ID, headers[2], headers[3])
    with pytest.raises(ErrInvalidHeader):
        bad = gen_chain(3, base_keys=keys(4, tag="fork"))[0]
        verify_backwards(CHAIN_ID, bad[2], headers[3])


# -- client ----------------------------------------------------------------


def make_client(headers, vals, witnesses=None, trust_height=1, period=PERIOD):
    primary = MockProvider(CHAIN_ID, headers, vals)
    opts = TrustOptions(
        period_ns=period, height=trust_height, hash=headers[trust_height].hash()
    )
    return LightClient(
        CHAIN_ID, opts, primary, witnesses or [], TrustedStore(MemDB())
    )


def test_client_sequential_and_bisection():
    async def go():
        headers, vals = gen_chain(20)
        c = make_client(headers, vals)
        sh = await c.verify_header_at_height(20, now_ns=NOW)
        assert sh.height == 20 and sh.hash() == headers[20].hash()
        assert c.trusted_height() == 20

    run(go())


def test_client_bisection_through_valset_changes():
    """Gradual validator changes force bisection pivots."""

    async def go():
        k = keys(8)
        changes = {
            5: k[2:6] + keys(2, tag="x"),   # partial overlap
            10: k[4:8] + keys(2, tag="y"),
            15: keys(4, tag="z") + k[6:8],
        }
        headers, vals = gen_chain(20, key_changes=changes, base_keys=k[:4])
        c = make_client(headers, vals)
        sh = await c.verify_header_at_height(20, now_ns=NOW)
        assert sh.hash() == headers[20].hash()

    run(go())


def test_client_witness_agreement_and_conflict():
    async def go():
        headers, vals = gen_chain(8)
        good_witness = MockProvider(CHAIN_ID, headers, vals)
        c = make_client(headers, vals, witnesses=[good_witness])
        await c.verify_header_at_height(8, now_ns=NOW)

        # forked witness with different headers at same heights
        fork_headers, fork_vals = gen_chain(8, base_keys=keys(4, tag="forked"))
        bad_witness = MockProvider(CHAIN_ID, fork_headers, fork_vals)
        c2 = make_client(headers, vals, witnesses=[bad_witness])
        with pytest.raises(ErrConflictingHeaders):
            await c2.verify_header_at_height(8, now_ns=NOW)

    run(go())


def test_client_backwards_verification():
    async def go():
        headers, vals = gen_chain(10)
        c = make_client(headers, vals, trust_height=8)
        await c.initialize(NOW)
        sh = await c.verify_header_at_height(3, now_ns=NOW)
        assert sh.hash() == headers[3].hash()

    run(go())


def test_client_rejects_wrong_trusted_hash():
    async def go():
        headers, vals = gen_chain(3)
        primary = MockProvider(CHAIN_ID, headers, vals)
        opts = TrustOptions(period_ns=PERIOD, height=1, hash=b"\x13" * 32)
        c = LightClient(CHAIN_ID, opts, primary, [], TrustedStore(MemDB()))
        with pytest.raises(Exception):
            await c.initialize(NOW)

    run(go())


class _DyingProvider(MockProvider):
    """Serves normally for `live_calls` fetches, then fails every call
    (a primary dying mid-bisection)."""

    def __init__(self, chain_id, headers, vals, live_calls: int):
        super().__init__(chain_id, headers, vals)
        self._live = live_calls

    def _tick(self):
        if self._live <= 0:
            raise ConnectionError("primary is dead")
        self._live -= 1

    async def signed_header(self, height: int):
        self._tick()
        return await super().signed_header(height)

    async def validator_set(self, height: int):
        self._tick()
        return await super().validator_set(height)


def test_client_primary_failover_mid_bisection():
    """Reference replacePrimaryProvider (lite2/client.go:1034, call
    sites :662,:744,:911): when the primary dies mid-verification a
    witness is promoted and the client completes."""

    async def go():
        k = keys(8)
        changes = {5: k[2:6] + keys(2, tag="x"), 10: k[4:8] + keys(2, tag="y")}
        headers, vals = gen_chain(15, key_changes=changes, base_keys=k[:4])
        # primary serves init + the first couple of fetches, then dies
        primary = _DyingProvider(CHAIN_ID, headers, vals, live_calls=5)
        witness = MockProvider(CHAIN_ID, headers, vals)
        opts = TrustOptions(period_ns=PERIOD, height=1, hash=headers[1].hash())
        c = LightClient(
            CHAIN_ID, opts, primary, [witness], TrustedStore(MemDB()),
            max_retry_attempts=2,
        )
        sh = await c.verify_header_at_height(15, now_ns=NOW)
        assert sh.hash() == headers[15].hash()
        assert c.primary is witness  # promoted
        assert c.witnesses == []  # and removed from the witness list

    run(go())


def test_client_primary_dead_no_witnesses_hard_fails():
    async def go():
        headers, vals = gen_chain(5)
        primary = _DyingProvider(CHAIN_ID, headers, vals, live_calls=0)
        opts = TrustOptions(period_ns=PERIOD, height=1, hash=headers[1].hash())
        from tendermint_tpu.light.client import LightClientError

        c = LightClient(
            CHAIN_ID, opts, primary, [], TrustedStore(MemDB()),
            max_retry_attempts=2,
        )
        with pytest.raises(LightClientError, match="no witnesses"):
            await c.verify_header_at_height(5, now_ns=NOW)

    run(go())


def test_client_prune():
    async def go():
        headers, vals = gen_chain(12)
        c = make_client(headers, vals)
        await c.verify_header_at_height(12, now_ns=NOW)
        c.prune(keep=2)
        assert len(c.store.heights()) <= 2
        assert c.store.latest_height() == 12

    run(go())


# -- the chain's commits as columns, against a per-row oracle --------------
#
# verify_chain packs every link's commit from Commit.columns() and replays
# each on arrays (types/validator_set.py). The oracle below is the serial
# reference written out: one host verification per signature in slot
# order, returning at the quorum row.


def _mixed_chain(n_heights, faults):
    """A chain of 7 validators (power 10: more than 46 of 70 needed)
    whose every commit has one absent slot and one nil vote, dealt by
    height — so exactly 5 for-block rows, the quorum falling on the
    last of them. ``faults[h]`` edits height h's slots before signing:
    ("forge", i) flips a bit of slot i's signature, ("nil", i) makes
    slot i a nil vote too."""
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.light.types import SignedHeader
    from tendermint_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BLOCK_ID_FLAG_NIL,
        BlockID,
        Commit,
        CommitSig,
    )
    from tendermint_tpu.types.vote import Vote

    privs = keys(7, tag="mixed")
    by_addr = {p.pub_key().address(): p for p in privs}
    headers, vals = gen_chain(n_heights, base_keys=privs)
    for h, sh in headers.items():
        vs = vals[h]
        kinds = {(h * 3) % 7: "absent", (h * 3 + 2) % 7: "nil"}
        for kind, i in faults.get(h, []):
            if kind == "nil":
                kinds[i] = "nil"
        slots = []
        for i, val in enumerate(vs.validators):
            if kinds.get(i) == "absent":
                slots.append(CommitSig.absent())
                continue
            nil = kinds.get(i) == "nil"
            vote = Vote(
                vote_type=PRECOMMIT_TYPE, height=h, round=0,
                block_id=BlockID() if nil else sh.commit.block_id,
                timestamp_ns=sh.time_ns + i, validator_address=val.address, validator_index=i,
            )
            sig = by_addr[val.address].sign(vote.sign_bytes(CHAIN_ID))
            if ("forge", i) in faults.get(h, []):
                sig = bytes([sig[0] ^ 0x10]) + sig[1:]
            slots.append(CommitSig(
                BLOCK_ID_FLAG_NIL if nil else BLOCK_ID_FLAG_COMMIT, val.address, sh.time_ns + i, sig,
            ))
        headers[h] = SignedHeader(sh.header, Commit(h, 0, sh.commit.block_id, slots))
    return headers, vals


def _oracle_verify_commit(vs, commit):
    """(exception type name, text) or None, as the reference's loop over
    the slots gives it (types/validator_set.go:641-668)."""
    needed = vs.total_voting_power() * 2 // 3
    talled = 0
    for i, cs in enumerate(commit.signatures):
        if cs.absent_():
            continue
        if talled > needed:
            return None
        val = vs.validators[i]
        if not val.pub_key.verify(commit.vote_sign_bytes(CHAIN_ID, i), cs.signature):
            return "ErrInvalidCommitSignature", f"wrong signature #{i} ({cs.validator_address.hex()})"
        if cs.for_block():
            talled += val.voting_power
    if talled > needed:
        return None
    return "ErrNotEnoughVotingPower", f"have {talled}, need > {needed}"


_CHAIN_CASES = {
    "sound": {},
    # height 4: absent slot 5, nil slot 0 — for-block rows 1 2 3 4 6
    "forged-before-quorum": {4: [("forge", 2)]},
    "forged-on-the-quorum-row": {4: [("forge", 6)]},
    "forged-nil-row": {4: [("forge", 0)]},
    "one-signer-short": {5: [("nil", 4)]},
    "two-links-fail": {3: [("forge", 1)], 6: [("nil", 3)]},
}


@pytest.mark.parametrize("case", sorted(_CHAIN_CASES))
def test_verify_chain_commits_match_per_row_oracle(case):
    from tendermint_tpu.light.verifier import verify_chain
    from tendermint_tpu.lightserve import core
    from tendermint_tpu.types.validator_set import verify_commits_batched

    headers, vals = _mixed_chain(7, _CHAIN_CASES[case])
    heights = range(2, 8)
    want = [_oracle_verify_commit(vals[h], headers[h].commit) for h in heights]
    # quorum sits on the last for-block row, so every planted fault is visited
    assert [w is not None for w in want] == [h in _CHAIN_CASES[case] for h in heights]

    specs = [core.full_spec(vals[h], CHAIN_ID, headers[h]) for h in heights]
    got = verify_commits_batched(specs)
    assert [None if e is None else (type(e).__name__, str(e)) for e in got] == want

    # fresh Commit objects for the chain call: the first failing link raises
    chain = [(headers[h], vals[h]) for h in heights]
    first = next((w for w in want if w is not None), None)
    try:
        verify_chain(CHAIN_ID, headers[1], vals[1], chain, PERIOD, now_ns=NOW)
        outcome = None
    except Exception as e:
        outcome = (type(e).__name__, str(e))
    assert outcome == first


def test_verify_chain_forged_row_after_quorum_is_accepted():
    """With every validator signing for the block the quorum row is the
    5th of 7: a forged 6th or 7th slot is never visited (the reference's
    early return), a forged 5th is."""
    from tendermint_tpu.light.types import SignedHeader
    from tendermint_tpu.light.verifier import verify_chain
    from tendermint_tpu.types.block import Commit
    from tendermint_tpu.types.validator_set import ErrInvalidCommitSignature

    headers, vals = gen_chain(4, base_keys=keys(7, tag="mixed"))

    def forged(h, slot):
        c = headers[h].commit
        slots = list(c.signatures)
        cs = slots[slot]
        slots[slot] = type(cs)(
            cs.block_id_flag, cs.validator_address, cs.timestamp_ns,
            bytes([cs.signature[0] ^ 0x10]) + cs.signature[1:],
        )
        return SignedHeader(headers[h].header, Commit(c.height, c.round, c.block_id, slots))

    for slot in (5, 6):
        chain = [(forged(h, slot) if h == 3 else headers[h], vals[h]) for h in (2, 3, 4)]
        assert _oracle_verify_commit(vals[3], chain[1][0].commit) is None
        verify_chain(CHAIN_ID, headers[1], vals[1], chain, PERIOD, now_ns=NOW)
    chain = [(forged(h, 4) if h == 3 else headers[h], vals[h]) for h in (2, 3, 4)]
    with pytest.raises(ErrInvalidCommitSignature, match="wrong signature #4"):
        verify_chain(CHAIN_ID, headers[1], vals[1], chain, PERIOD, now_ns=NOW)


# -- the chain's commits taken a group at a time ----------------------------


@pytest.mark.parametrize("case", sorted(_CHAIN_CASES))
def test_verify_chain_overlapped_raises_what_the_eager_call_raises(case):
    """verify_chain through a provider that takes row groups
    (crypto/batch.RowGroups; tests/seam_helpers.GroupStub takes 2
    commits at a time): the same first failing link, and every commit
    after the first group packed after a launch was on its way."""
    from tendermint_tpu.light.types import SignedHeader
    from tendermint_tpu.light.verifier import verify_chain
    from tendermint_tpu.types.block import Commit
    from tests.seam_helpers import GroupStub, seam_counts, seam_grew

    headers, vals = _mixed_chain(7, _CHAIN_CASES[case])
    heights = range(2, 8)

    def outcome(provider):
        chain = [
            (SignedHeader(headers[h].header, Commit(
                h, 0, headers[h].commit.block_id, list(headers[h].commit.signatures)
            )), vals[h])
            for h in heights
        ]
        try:
            verify_chain(CHAIN_ID, headers[1], vals[1], chain, PERIOD, now_ns=NOW, provider=provider)
        except Exception as e:
            return type(e).__name__, str(e)
        return None

    want = outcome(None)
    assert (want is not None) == bool(_CHAIN_CASES[case])
    stub = GroupStub(2)
    before = seam_counts()
    assert outcome(stub) == want
    grew = seam_grew(before)
    # six commits of 7 slots, one absent each: three groups of 12 rows
    assert [e[0] for e in stub.events] == ["take", "launch"] * 3
    assert [(t[2], t[3]) for t in stub.of("take")] == [(12, 14), (12, 28), (12, 42)]
    assert grew["packed_rows"] == 36 and grew["overlapped_rows"] == 24


@pytest.mark.parametrize("decline_at", [0, 2])
def test_verify_chain_overlapped_provider_declines(decline_at):
    """The provider answers None at the first or the last group: the
    chain is verified all the same, every row once, on the generic
    path."""
    from tendermint_tpu.light.verifier import verify_chain
    from tendermint_tpu.types.validator_set import ErrInvalidCommitSignature
    from tests.seam_helpers import GroupStub

    headers, vals = _mixed_chain(7, {6: [("forge", 3)]})
    chain = [(headers[h], vals[h]) for h in range(2, 8)]
    stub = GroupStub(2, decline_at=decline_at)
    with pytest.raises(ErrInvalidCommitSignature, match="wrong signature #3"):
        verify_chain(CHAIN_ID, headers[1], vals[1], chain, PERIOD, now_ns=NOW, provider=stub)
    assert len(stub.of("launch")) == decline_at
    assert stub.of("batch") == [("batch", 36)] and stub.row_counts.snapshot() == (0, 36)
