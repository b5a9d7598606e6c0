"""Cross-height batched commit verification (SURVEY §5.7 chain-length axis).

The reference verifies one header's commit at a time (lite2/client.go:687,
blockchain/v2/processor_context.go:42); these tests pin the TPU-first
redesign: many heights' commits in ONE BatchVerifier call, with per-height
accept/reject identical to the per-call path.
"""

import asyncio

import pytest

from tendermint_tpu.blockchain.reactor import BlockchainReactor
from tendermint_tpu.crypto.batch import CPUBatchVerifier
from tendermint_tpu.light import verifier
from tendermint_tpu.light.client import LightClient
from tendermint_tpu.light.provider import MockProvider
from tendermint_tpu.db import MemDB
from tendermint_tpu.light.store import TrustedStore
from tendermint_tpu.light.types import TrustOptions
from tendermint_tpu.types.validator_set import (
    CommitVerifySpec,
    ErrInvalidCommit,
    ErrInvalidCommitSignature,
    verify_commits_batched,
)

from tests import light_helpers as lh

TRUST_PERIOD_NS = 3 * 3600 * 10**9


class CountingProvider(CPUBatchVerifier):
    """Counts device-batch calls and total rows."""

    def __init__(self):
        super().__init__()
        self.calls = 0
        self.rows = 0
        self.max_rows = 0

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None):
        self.calls += 1
        self.rows += len(pubkeys)
        self.max_rows = max(self.max_rows, len(pubkeys))
        return super().verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)


def _now(headers, h):
    return headers[h].time_ns + 1


# -- verify_commits_batched --------------------------------------------------


def test_many_heights_one_device_call():
    headers, valsets = lh.gen_chain(30)
    specs = [
        CommitVerifySpec(
            valsets[h], lh.CHAIN_ID, headers[h].block_id(), h, headers[h].commit
        )
        for h in range(1, 31)
    ]
    p = CountingProvider()
    res = verify_commits_batched(specs, provider=p)
    assert res == [None] * 30
    assert p.calls == 1  # ★ 30 heights, ONE device call
    assert p.rows == 30 * 4


def test_batched_matches_per_call_on_bad_signature():
    headers, valsets = lh.gen_chain(5)
    # corrupt height 3's first signature
    sig = bytearray(headers[3].commit.signatures[0].signature)
    sig[0] ^= 0xFF
    headers[3].commit.signatures[0].signature = bytes(sig)

    specs = [
        CommitVerifySpec(
            valsets[h], lh.CHAIN_ID, headers[h].block_id(), h, headers[h].commit
        )
        for h in range(1, 6)
    ]
    res = verify_commits_batched(specs)
    for i, h in enumerate(range(1, 6)):
        if h == 3:
            assert isinstance(res[i], ErrInvalidCommitSignature)
        else:
            assert res[i] is None
        # agreement with the direct method call
        try:
            valsets[h].verify_commit(
                lh.CHAIN_ID, headers[h].block_id(), h, headers[h].commit
            )
            direct = None
        except Exception as e:
            direct = e
        assert type(res[i]) is type(direct)


def test_precheck_failure_isolated():
    headers, valsets = lh.gen_chain(3)
    specs = [
        # wrong height: host pre-check fails, contributes no device rows
        CommitVerifySpec(
            valsets[1], lh.CHAIN_ID, headers[1].block_id(), 99, headers[1].commit
        ),
        CommitVerifySpec(
            valsets[2], lh.CHAIN_ID, headers[2].block_id(), 2, headers[2].commit
        ),
    ]
    p = CountingProvider()
    res = verify_commits_batched(specs, provider=p)
    assert isinstance(res[0], ErrInvalidCommit)
    assert res[1] is None
    assert p.rows == 4  # only the valid spec reached the device


def test_trusting_mode_in_batch():
    from fractions import Fraction

    headers, valsets = lh.gen_chain(10)
    # trusting check: valset at height 1 trusts the commit at height 8
    # (same keys throughout, so 100% overlap)
    specs = [
        CommitVerifySpec(
            valsets[1], lh.CHAIN_ID, headers[8].block_id(), 8, headers[8].commit,
            mode="trusting", trust_level=Fraction(1, 3),
        ),
        CommitVerifySpec(
            valsets[8], lh.CHAIN_ID, headers[8].block_id(), 8, headers[8].commit
        ),
    ]
    res = verify_commits_batched(specs)
    assert res == [None, None]


# -- verifier.verify_chain ---------------------------------------------------


def test_verify_chain_adjacent_one_call():
    headers, valsets = lh.gen_chain(50)
    chain = [(headers[h], valsets[h]) for h in range(2, 51)]
    p = CountingProvider()
    verifier.verify_chain(
        lh.CHAIN_ID, headers[1], valsets[1], chain, TRUST_PERIOD_NS,
        now_ns=_now(headers, 50), provider=p,
    )
    assert p.calls == 1
    assert p.rows == 49 * 4


def test_verify_chain_detects_broken_link():
    headers, valsets = lh.gen_chain(10)
    sig = bytearray(headers[6].commit.signatures[1].signature)
    sig[5] ^= 0x01
    headers[6].commit.signatures[1].signature = bytes(sig)
    chain = [(headers[h], valsets[h]) for h in range(2, 11)]
    with pytest.raises(ErrInvalidCommitSignature):
        verifier.verify_chain(
            lh.CHAIN_ID, headers[1], valsets[1], chain, TRUST_PERIOD_NS,
            now_ns=_now(headers, 10),
        )


def test_verify_chain_non_adjacent_links():
    headers, valsets = lh.gen_chain(40)
    # skip-chain: 1 -> 10 -> 25 -> 40 (same keys, trusting passes)
    chain = [(headers[h], valsets[h]) for h in (10, 25, 40)]
    p = CountingProvider()
    verifier.verify_chain(
        lh.CHAIN_ID, headers[1], valsets[1], chain, TRUST_PERIOD_NS,
        now_ns=_now(headers, 40), provider=p,
    )
    assert p.calls == 1
    assert p.rows == 3 * 2 * 4  # trusting + full per link


def test_verify_chain_trusting_failure_raises_cant_be_trusted():
    headers, valsets = lh.gen_chain(
        20, key_changes={10: lh.keys(4, tag="other")}
    )
    # 1 -> 15 non-adjacent: valset flipped entirely at 10, so the trusting
    # check against valset(1) must fail with ErrNewValSetCantBeTrusted
    chain = [(headers[15], valsets[15])]
    with pytest.raises(verifier.ErrNewValSetCantBeTrusted):
        verifier.verify_chain(
            lh.CHAIN_ID, headers[1], valsets[1], chain, TRUST_PERIOD_NS,
            now_ns=_now(headers, 15),
        )


# -- light client sequence mode ---------------------------------------------


def test_light_client_sequence_mode_batches_windows():
    headers, valsets = lh.gen_chain(120)
    provider = MockProvider(lh.CHAIN_ID, headers, valsets)
    store = TrustedStore(MemDB())
    opts = TrustOptions(
        period_ns=TRUST_PERIOD_NS, height=1, hash=headers[1].hash()
    )
    counting = CountingProvider()

    from tendermint_tpu.crypto import batch as batch_mod

    prev = batch_mod.get_default_provider()
    batch_mod.set_default_provider(counting)
    try:
        lc = LightClient(
            lh.CHAIN_ID, opts, provider, [], store,
            mode="sequence", sequence_window=64,
        )

        async def go():
            sh = await lc.verify_header_at_height(120, now_ns=_now(headers, 120))
            assert sh.height == 120

        asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())
    finally:
        batch_mod.set_default_provider(prev)

    # init (1 call) + two windows (64 + 55 headers) = 3 calls total
    assert counting.calls == 3
    assert store.latest_height() == 120
    # every height landed in the store
    assert store.signed_header(77) is not None


# -- fast-sync windowed processor -------------------------------------------


def _make_block_chain(n):
    """Chain of n blocks + the commit for each, via the executor helpers."""
    from tests.test_state import make_commit_for, make_executor, make_genesis

    from tendermint_tpu.types.tx import Txs

    state, privs = make_genesis()
    genesis_state = state.copy()
    ex, store, cli = make_executor(genesis_state=state)

    blocks = {}

    async def build():
        nonlocal state
        await cli.start()
        last_commit = None
        for h in range(1, n + 1):
            proposer = state.validators.get_proposer()
            block = state.make_block(
                h, Txs([b"tx-%d" % h]), last_commit, [], proposer.address
            )
            commit, bid, ps = make_commit_for(state, block, privs, h)
            blocks[h] = block
            state, _ = await ex.apply_block(state, bid, block)
            last_commit = commit

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(build())
    return genesis_state, blocks


def test_fast_sync_processor_window_one_call():
    n = 9  # blocks 1..9 fetched; 1..8 processable (9's commit unknown)
    genesis_state, blocks = _make_block_chain(n)

    from tests.test_state import make_executor

    ex, store, cli = make_executor(genesis_state=genesis_state)
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.db import MemDB

    bs = BlockStore(MemDB())
    r = BlockchainReactor(genesis_state, ex, bs, fast_sync=True)
    r._blocks = dict(blocks)

    counting = CountingProvider()
    from tendermint_tpu.crypto import batch as batch_mod

    prev = batch_mod.get_default_provider()
    batch_mod.set_default_provider(counting)
    try:
        async def go():
            await cli.start()
            progressed = await r._try_process_one()
            assert progressed

        asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())
    finally:
        batch_mod.set_default_provider(prev)

    # blocks 1..8's fast-sync commit checks ran as ONE 32-row device call
    # (the other calls are apply_block's own per-block LastCommit
    # validation, present in the reference too — state/validation.go:92)
    assert counting.max_rows == 8 * 4
    assert counting.calls == 1 + 7  # window + per-apply validations (h2..h8)
    assert r.state.last_block_height == 8
    assert bs.height == 8


def test_fast_sync_processor_window_rejects_bad_block():
    genesis_state, blocks = _make_block_chain(6)
    # corrupt the commit for block 4 (carried in block 5's last_commit)
    # (building the chain verified that commit, and a verified commit is
    # immutable — its memos vouch for its bytes — so the forged one is a
    # deep copy, which starts without them)
    import copy

    forged = copy.deepcopy(blocks[5].last_commit)
    sig = bytearray(forged.signatures[0].signature)
    sig[3] ^= 0x80
    forged.signatures[0].signature = bytes(sig)
    blocks[5].last_commit = forged

    from tests.test_state import make_executor

    ex, store, cli = make_executor(genesis_state=genesis_state)
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.db import MemDB

    bs = BlockStore(MemDB())
    r = BlockchainReactor(genesis_state, ex, bs, fast_sync=True)
    r._blocks = dict(blocks)

    async def go():
        await cli.start()
        await r._try_process_one()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(go())
    # 1..3 applied; 4 rejected (its commit is bad), nothing past it
    assert r.state.last_block_height == 3
