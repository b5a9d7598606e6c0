"""ISSUE-4 chaos acceptance: a live node with faults armed at EVERY
registered site (low probability, fixed seed) still commits >= 5
consecutive heights, with the watchdog supervising the pipeline and a
file-backed WAL absorbing the write/fsync chaos.

Site/action assignment mirrors what each site can survive (the
taxonomy table in docs/robustness.md): sites whose failure the node is
BUILT to absorb (pipeline thread death -> watchdog restart + deadline
fallback; device errors -> host fallback) get `raise`; sites where a
raise IS a crash by design (WAL, apply — that's utils/fail.py's crash
matrix, tests/test_replay.py) get `delay`, which exercises the code
path without asking consensus to survive its own halt policy.
"""

import asyncio
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tests.cs_harness import make_genesis
from tendermint_tpu.consensus.wal import BaseWAL
from tendermint_tpu.crypto.batch import (
    CPUBatchVerifier,
    get_default_provider,
    set_default_provider,
)
from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache
from tendermint_tpu.utils import faultinject as faults
from tendermint_tpu.utils.faultinject import KNOWN_SITES
from tendermint_tpu.utils.watchdog import Watchdog

CHAOS_SEED = 1337

# site -> (action, kwargs). Every KNOWN_SITES entry must appear: the
# acceptance criterion is faults ENABLED at every registered site.
CHAOS_PLAN = {
    "wal.write": ("delay", dict(p=0.2, delay_ms=2)),
    "wal.fsync": ("delay", dict(p=0.2, delay_ms=2)),
    "pipeline.dispatch": ("raise", dict(after=4, times=1)),
    "pipeline.exec": ("raise", dict(after=2, times=1)),
    "device.verify": ("raise", dict(p=0.2)),
    "device.tables": ("raise", dict(p=0.2)),
    "device.hash": ("raise", dict(p=0.2)),
    "merkle.compile": ("raise", dict(p=0.2)),
    "exec.apply": ("delay", dict(p=0.2, delay_ms=2)),
    "exec.commit": ("delay", dict(p=0.2, delay_ms=2)),
    "p2p.read": ("delay", dict(p=0.1, delay_ms=1)),
    "p2p.write": ("delay", dict(p=0.1, delay_ms=1)),
    "p2p.accept": ("raise", dict(p=0.1)),
    "p2p.dial": ("raise", dict(p=0.1)),
    # lightserve absorbs raises by design: fetch retries/backoff eat
    # transient source errors, and a bundle raise fails that bundle's
    # client futures, never the dispatch thread (the chaos node here
    # runs with lightserve off, so these stay armed-but-idle; their
    # firing paths are pinned in tests/test_lightserve.py)
    "lightserve.fetch": ("raise", dict(p=0.2)),
    "lightserve.bundle": ("raise", dict(p=0.2)),
    # ingest absorbs raises by design: a batch fault fails that bundle's
    # callers (gossip drops / RPC errors, both retryable) and an
    # admission fault is one failed CheckTx — neither touches consensus.
    # test_chaos_admission_faults_node_still_commits drives them hot.
    "ingest.batch": ("raise", dict(p=0.2)),
    "mempool.admit": ("raise", dict(p=0.2)),
    # BLS absorbs raises by design: a dispatch/compile fault trips the
    # bls.compile breaker and the call falls back to the host oracle
    # with an identical verdict (models/bls.py). The ed25519 chaos node
    # here never reaches them (armed-but-idle, the lightserve pattern);
    # test_chaos_bls_faults_node_still_commits drives them hot against
    # a live node.
    "bls.pairing": ("raise", dict(p=0.3)),
    "bls.compile": ("raise", dict(p=0.3)),
    # the mesh absorbs raises by design: a shard fault trips only that
    # device's breaker (survivors re-shard the next bundle) and the
    # routed engine falls back to its single-device path for the bundle
    # (parallel/topology.py). The single-device chaos node never plans
    # a collective, so this stays armed-but-idle here;
    # test_mesh_router.py drives the shed/readmit paths hot.
    "mesh.shard": ("raise", dict(p=0.3)),
    # the executor absorbs raises by design: a batch fault fires BEFORE
    # any DeliverBatch chunk is dispatched, so the block degrades to the
    # serial per-tx path with identical responses — never a wrong app
    # hash. test_chaos_exec_batch_faults_node_still_commits drives it
    # hot against a live node landing real transfers.
    "exec.batch": ("raise", dict(p=0.3)),
}


@pytest.fixture(autouse=True)
def _clean():
    prev = get_default_provider()
    faults.disarm()
    yield
    faults.disarm()
    set_default_provider(prev)


def test_chaos_plan_covers_every_registered_site():
    assert set(CHAOS_PLAN) == set(KNOWN_SITES)


def test_chaos_node_commits_five_heights(tmp_path):
    """Faults at every site, fixed seed, supervised pipeline, real WAL:
    the node must still commit >= 5 consecutive heights, the chaos must
    actually FIRE (trigger counters), and the forced pipeline.exec
    death must be healed by the watchdog with the stranded verify
    resolving by deadline fallback — no caller hangs."""

    async def go():
        pv = PipelinedVerifier(CPUBatchVerifier(), cache=SigCache())
        wd = Watchdog(interval_s=0.05)
        pv.attach_watchdog(wd, deadline_s=1.0)
        wd.start()
        set_default_provider(pv)

        for site, (action, kw) in CHAOS_PLAN.items():
            faults.arm(site, action, seed=CHAOS_SEED, **kw)

        genesis, privs = make_genesis(1)
        from tests.cs_harness import make_node

        node = await make_node(
            genesis, privs[0], wal=BaseWAL(str(tmp_path / "cs.wal"))
        )
        await node.cs.start()
        try:
            await node.cs.wait_for_height(5, timeout_s=90)
        finally:
            st = faults.stats()["sites"]  # snapshot BEFORE disarm clears it
            await node.cs.stop()
            faults.disarm()
            wd.stop()
            pv.stop(timeout=5.0)

        assert node.cs.state.last_block_height >= 5
        # the chaos was real: the hot sites were evaluated and fired
        for site in ("wal.write", "wal.fsync", "pipeline.exec"):
            assert st[site]["evals"] > 0, f"{site} never evaluated"
        assert st["wal.write"]["triggers"] > 0, "WAL delay chaos never fired"
        assert st["pipeline.exec"]["triggers"] == 1, "exec death never injected"
        # ...and the node healed: the killed exec worker was restarted
        pstats = pv.stats()
        assert pstats["submitted_calls"] > 0, "consensus never used the pipeline"
        assert pstats["worker_restarts"] >= 1, "watchdog never restarted the worker"
        assert wd.stats()["workers"]["pipeline.exec"]["restarts"] >= 1
        # the stranded caller resolved (fallback or retry), never hung:
        # reaching height 5 past the injected exec death proves it —
        # whether via deadline fallback (fallback_serial) or a restart
        # winning the race is timing-dependent, so neither counter is
        # asserted here (test_pipeline_exec_death_pending_commit_verify_resolves
        # pins the fallback path deterministically)
        # WAL survived the chaos: replayable, ENDHEIGHT for each height
        wal = BaseWAL(str(tmp_path / "cs.wal"))
        msgs, found = wal.search_for_end_height(5)
        assert found, "WAL must hold ENDHEIGHT(5) after the chaos run"

    asyncio.run(go())


def test_chaos_admission_faults_node_still_commits(tmp_path):
    """ISSUE-7 satellite: a live node whose ADMISSION path is under
    injected faults (ingest.batch bundle failures + mempool.admit
    raises) still commits >= 5 heights — and still lands real payment
    transfers on chain, because admission failures are retryable by
    design (gossip redelivers; the driver here plays that role)."""

    async def go():
        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            sig_rows,
        )
        from tendermint_tpu.crypto.pipeline import (
            PipelinedVerifier as PV,
            SigCache as SC,
        )
        from tendermint_tpu.ingest import IngestBatcher
        from tendermint_tpu.ingest import loadgen as igen
        from tests.cs_harness import make_genesis, make_node

        faults.arm("ingest.batch", "raise", p=0.3, seed=CHAOS_SEED)
        faults.arm("mempool.admit", "raise", p=0.3, seed=CHAOS_SEED)

        privs, balances = igen.accounts(4)
        txs = igen.make_transfers(privs, 24, amount=1, fee=1)
        cache = SC()
        app = PaymentsApplication(dict(balances), sig_cache=cache)
        genesis, vals = make_genesis(1)
        node = await make_node(genesis, vals[0], app=app)
        pv = PV(CPUBatchVerifier(), cache=cache)
        batcher = IngestBatcher(
            node.mempool, verifier=pv, sig_extractor=sig_rows,
            bundle_txs=8, hash_threshold=1 << 30,
        )
        await node.cs.start()
        try:
            async def submit_with_retry(tx):
                from tendermint_tpu.mempool.mempool import ErrTxInCache

                for _ in range(20):
                    try:
                        await batcher.check_tx(tx)
                        return True
                    except ErrTxInCache:
                        return True  # an earlier attempt landed it
                    except Exception:
                        await asyncio.sleep(0.02)  # gossip-redelivery shape
                return False

            ok = await asyncio.gather(*(submit_with_retry(t) for t in txs))
            assert all(ok), "admission chaos starved a tx past 20 retries"
            await node.cs.wait_for_height(5, timeout_s=90)
        finally:
            st = faults.stats()["sites"]
            await node.cs.stop()
            await batcher.stop()
            faults.disarm()
            pv.stop(timeout=5.0)

        assert node.cs.state.last_block_height >= 5
        # the chaos was real AND transfers still committed through it
        assert st["ingest.batch"]["triggers"] + st["mempool.admit"]["triggers"] > 0
        assert app.tx_applied > 0, "no transfer survived the admission chaos"

    asyncio.run(go())


def test_chaos_exec_batch_faults_node_still_commits(tmp_path):
    """ISSUE-17 chaos acceptance: a live node whose block EXECUTION runs
    under injected exec.batch faults (p=0.3) still commits >= 5 heights
    and still lands real payment transfers — every faulted block
    degrades to the serial per-tx DeliverTx path with an identical app
    hash, so batching chaos can cost throughput but never correctness."""

    async def go():
        from tendermint_tpu.abci.examples.payments import (
            PaymentsApplication,
            sig_rows,
        )
        from tendermint_tpu.crypto.pipeline import (
            PipelinedVerifier as PV,
            SigCache as SC,
        )
        from tendermint_tpu.ingest import IngestBatcher
        from tendermint_tpu.ingest import loadgen as igen
        from tests.cs_harness import make_genesis, make_node

        faults.arm("exec.batch", "raise", p=0.3, seed=CHAOS_SEED)

        privs, balances = igen.accounts(4)
        # the site is evaluated once per block that carries txs, and the
        # seeded stream fires on the 4th evaluation: transfers go in one
        # block's worth at a time until the site's own counter moves,
        # because how many blocks a fixed lot lands in is up to timing
        txs = igen.make_transfers(privs, 64, amount=1, fee=1)
        cache = SC()
        app = PaymentsApplication(dict(balances), sig_cache=cache)
        genesis, vals = make_genesis(1)
        node = await make_node(genesis, vals[0], app=app)
        pv = PV(CPUBatchVerifier(), cache=cache)
        app.batch_verifier = pv
        batcher = IngestBatcher(
            node.mempool, verifier=pv, sig_extractor=sig_rows,
            bundle_txs=8, hash_threshold=1 << 30,
        )
        await node.cs.start()
        try:
            async def submit_with_retry(tx):
                from tendermint_tpu.mempool.mempool import ErrTxInCache

                for _ in range(20):
                    try:
                        await batcher.check_tx(tx)
                        return True
                    except ErrTxInCache:
                        return True
                    except Exception:
                        await asyncio.sleep(0.02)
                return False

            loop = asyncio.get_running_loop()
            deadline = loop.time() + 90  # the node's height deadline bounds it all

            async def next_height():
                await node.cs.wait_for_height(
                    node.cs.state.last_block_height + 1,
                    timeout_s=max(0.0, deadline - loop.time()),
                )

            sent = 0
            while not faults.stats()["sites"]["exec.batch"]["triggers"]:
                assert sent < len(txs), "exec.batch chaos never fired in 8 blocks with txs"
                wave, sent = txs[sent : sent + 8], sent + 8
                ok = await asyncio.gather(*(submit_with_retry(t) for t in wave))
                assert all(ok), "admission starved a tx past 20 retries"
                while node.mempool.size():
                    await next_height()
            while node.cs.state.last_block_height < 5:
                await next_height()
        finally:
            st = faults.stats()["sites"]
            exec_stats = node.cs._block_exec.exec_stats()
            await node.cs.stop()
            await batcher.stop()
            faults.disarm()
            pv.stop(timeout=5.0)

        assert node.cs.state.last_block_height >= 5
        # the chaos was real: the batch site fired and the serial
        # fallback absorbed it — and transfers still committed
        assert st["exec.batch"]["evals"] > 0, "exec.batch never evaluated"
        assert st["exec.batch"]["triggers"] > 0, "exec.batch chaos never fired"
        assert exec_stats["fallbacks"] > 0, "no faulted block degraded to per-tx"
        assert app.tx_applied > 0, "no transfer survived the execution chaos"

    asyncio.run(go())


def test_chaos_bls_faults_node_still_commits(tmp_path):
    """ISSUE-10 chaos acceptance: a live node keeps committing while
    BLS verification runs under injected bls.pairing + bls.compile
    faults — the engine's breaker-gated host fallback absorbs every
    device failure with identical verdicts, so aggregated-commit
    checking can never stall consensus."""

    async def go():
        import numpy as np

        from tendermint_tpu.crypto.bls import BLSBatchVerifier, BLSPrivKey
        from tendermint_tpu.models.bls import BLSEngine
        from tests.cs_harness import make_node

        faults.arm("bls.pairing", "raise", p=0.5, seed=CHAOS_SEED)
        faults.arm("bls.compile", "raise", p=0.5, seed=CHAOS_SEED)

        genesis, privs = make_genesis(1)
        node = await make_node(
            genesis, privs[0], wal=BaseWAL(str(tmp_path / "cs.wal"))
        )
        await node.cs.start()
        try:
            # device engine under chaos: cold buckets whose compile the
            # fault kills, dispatch faults on any that survive — every
            # verdict must still come back correct via the oracle
            v = BLSBatchVerifier(
                engine=BLSEngine(block_on_compile=False), use_device=True
            )
            bls_privs = [BLSPrivKey.from_secret(bytes([i, 99])) for i in range(2)]
            msgs = [b"chaos-%d" % i for i in range(2)]
            sigs = [p.sign(m) for p, m in zip(bls_privs, msgs)]
            pk = np.stack(
                [np.frombuffer(p.pub_key().bytes(), dtype=np.uint8) for p in bls_privs]
            )
            mg = np.zeros((2, 8), dtype=np.uint8)
            lens = np.zeros(2, dtype=np.int32)
            for i, m in enumerate(msgs):
                mg[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
                lens[i] = len(m)
            sg = np.stack([np.frombuffer(s, dtype=np.uint8) for s in sigs])
            for _ in range(3):
                ok = v.verify_batch(pk, mg, sg, msg_lens=lens)
                assert list(ok) == [True, True], "chaos changed a BLS verdict"
            await node.cs.wait_for_height(5, timeout_s=90)
        finally:
            st = faults.stats()["sites"]
            await node.cs.stop()
            faults.disarm()

        assert node.cs.state.last_block_height >= 5
        assert (
            st["bls.pairing"]["evals"] + st["bls.compile"]["evals"] > 0
        ), "BLS chaos never evaluated"
        assert v.counters["host_rows"] >= 2, "oracle fallback never engaged"

    asyncio.run(go())


def test_pipeline_exec_death_pending_commit_verify_resolves(tmp_path):
    """The acceptance clause in isolation: a pending COMMIT-verify
    future whose exec thread was killed resolves within its deadline
    (fallback serial verify succeeds), and the watchdog restart makes
    the next submit_commit ride the pipeline again."""

    async def go():
        from tests.test_pipeline import CHAIN, _commit_fixture
        from tendermint_tpu.types.validator_set import CommitVerifySpec

        pv = PipelinedVerifier(CPUBatchVerifier(), cache=SigCache())
        wd = Watchdog(interval_s=0.02)
        pv.attach_watchdog(wd, deadline_s=0.3)
        wd.start()
        try:
            vs, commit, bid = _commit_fixture()
            spec = CommitVerifySpec(vs, CHAIN, bid, 5, commit)

            faults.arm("pipeline.exec", "raise", times=1)
            fut = pv.submit_commit(spec)
            # no caller hangs: the future resolves (exception) within
            # its deadline despite the dead exec thread
            err = None
            try:
                res = await asyncio.wait_for(asyncio.wrap_future(fut), 3.0)
            except asyncio.TimeoutError:
                pytest.fail("commit-verify future hung past its deadline")
            except Exception as e:
                err = e
                res = None
            faults.disarm()
            if err is not None:
                # liveness failure -> the caller's serial fallback path
                vs.verify_commit(CHAIN, bid, 5, commit, provider=CPUBatchVerifier())
            else:
                assert res is None, "commit must verify clean"

            # watchdog heals the pipeline; retry rides the device path
            deadline = asyncio.get_event_loop().time() + 3.0
            while asyncio.get_event_loop().time() < deadline:
                if pv.workers_alive():
                    break
                await asyncio.sleep(0.02)
            assert pv.workers_alive(), "watchdog must restart the exec worker"
            fut2 = pv.submit_commit(spec)
            assert await asyncio.wait_for(asyncio.wrap_future(fut2), 10.0) is None
        finally:
            faults.disarm()
            wd.stop()
            pv.stop(timeout=5.0)

    asyncio.run(go())
