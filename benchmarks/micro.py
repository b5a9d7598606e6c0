#!/usr/bin/env python
"""Microbenchmarks mirroring the reference's in-tree benches (SURVEY §4.5).

Each bench prints one JSON line {"metric", "value", "unit"}. Run all:
    python benchmarks/micro.py            # everything except device benches
    python benchmarks/micro.py light mempool secretconn txindex e2e valset

Reference bench inventory: crypto/ed25519/bench_test.go (→ bench.py at
the repo root, the driver-run headline), lite2/client_benchmark_test.go,
mempool/bench_test.go, p2p/conn/secret_connection_test.go:389,
types/validator_set_test.go:1416, state/txindex/kv/kv_test.go:360,
plus an e2e single-node commit-latency probe (test/p2p analog).
"""

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.utils.jaxenv import scope_tables_cache  # noqa: E402

scope_tables_cache("bench")  # mirrors bench.py


def emit(metric, value, unit):
    print(json.dumps({"metric": metric, "value": round(value, 4), "unit": unit}))


def bench_light():
    """lite2/client_benchmark_test.go: bisection over a mock chain."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
    from light_helpers import CHAIN_ID, T0, gen_chain

    from tendermint_tpu.db.memdb import MemDB
    from tendermint_tpu.light import LightClient, TrustOptions
    from tendermint_tpu.light.provider import MockProvider
    from tendermint_tpu.light.store import TrustedStore

    n = 200  # headers (chain generation is the expensive part host-side)
    headers, vals = gen_chain(n)
    now = T0 + 600 * 10**9

    async def verify_all(mode_seq: bool):
        lc = LightClient(
            CHAIN_ID,
            TrustOptions(period_ns=10**18, height=1, hash=headers[1].hash()),
            MockProvider(CHAIN_ID, headers, vals),
            [],
            TrustedStore(MemDB()),
        )
        t0 = time.perf_counter()
        if mode_seq:
            for h in range(2, n + 1):
                await lc.verify_header_at_height(h, now_ns=now)
        else:
            await lc.verify_header_at_height(n, now_ns=now)
        return time.perf_counter() - t0

    seq = asyncio.run(verify_all(True))
    bis = asyncio.run(verify_all(False))
    emit("light_sequential_200_headers", seq * 1e3, "ms")
    emit("light_bisection_to_200", bis * 1e3, "ms")


def bench_headers_heights():
    """BASELINE eval 3: many validators × many heights — per-header device
    calls vs ONE cross-height batched call (verifier.verify_chain).

    Scaled-down by default (chain generation is host-bound); pass env
    EVAL3_FULL=1 for the full 1k-validator × 500-height config."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
    import light_helpers as lh

    from tendermint_tpu.light import verifier

    full = os.environ.get("EVAL3_FULL") == "1"
    n_vals = 1000 if full else 64
    n_heights = 500 if full else 100
    ks = lh.keys(n_vals)
    headers, vals = lh.gen_chain(n_heights, base_keys=ks)
    now = headers[n_heights].time_ns + 1
    period = 10**18
    chain = [(headers[h], vals[h]) for h in range(2, n_heights + 1)]

    # the batching win is a DEVICE property (per-call dispatch + bucket
    # padding); measure with the jax provider, not the serial-host one
    from tendermint_tpu.crypto.batch import make_provider

    prov = make_provider("tpu")
    # Warm EVERY bucket both timed paths touch out of the timed region
    # (compiles measured in-region turned the round-3 first run into a
    # 146s "batched" figure that was ~90% XLA compile):
    #  - generic buckets (host-fallback seams)
    #  - the tabled per-height bucket (n_vals rows) + the valset tables
    #  - the tabled 16384-row streaming window
    #  - the tabled 10240 tail bucket (499k % 16384 = 7480 -> 10240)
    prov.warmup(sizes=(n_vals,), msg_len=160)
    verifier.verify_adjacent(
        lh.CHAIN_ID, headers[1], chain[0][0], chain[0][1], period,
        now_ns=now, provider=prov,
    )
    if full:
        for warm_heights in (10, 17):  # 10240 bucket; 16384 window + tail
            verifier.verify_chain(
                lh.CHAIN_ID, headers[1], vals[1], chain[:warm_heights],
                period, now_ns=now, provider=prov,
            )
    else:
        verifier.verify_chain(
            lh.CHAIN_ID, headers[1], vals[1], chain[:4], period,
            now_ns=now, provider=prov,
        )

    t0 = time.perf_counter()
    cur_sh, cur_vals = headers[1], vals[1]
    for sh, vs in chain:
        verifier.verify_adjacent(lh.CHAIN_ID, cur_sh, sh, vs, period, now_ns=now, provider=prov)
        cur_sh, cur_vals = sh, vs
    per_header = time.perf_counter() - t0

    t0 = time.perf_counter()
    verifier.verify_chain(lh.CHAIN_ID, headers[1], vals[1], chain, period, now_ns=now, provider=prov)
    batched = time.perf_counter() - t0

    tag = f"{n_vals}v_x_{n_heights}h"
    emit(f"headers_per_height_calls_{tag}", per_header * 1e3, "ms")
    emit(f"headers_one_batched_call_{tag}", batched * 1e3, "ms")
    emit(f"headers_batch_speedup_{tag}", per_header / batched, "x")


def bench_sig_scaling():
    """BASELINE eval 2: raw batched signature verification at 1k / 10k /
    (optionally) 100k signatures. 100k streams through the 10240 bucket
    (SIGS_100K=1 to enable; the smaller sizes run by default)."""
    import numpy as np

    from tendermint_tpu.crypto.batch import make_provider

    sizes = [1024, 10240] + ([102400] if os.environ.get("SIGS_100K") == "1" else [])

    # deterministic valid triples via the repo bench helper (repo root is
    # already on sys.path)
    import bench as bench_root

    prov = make_provider("tpu")
    prov.warmup(sizes=(1024,), msg_len=160)
    for n in sizes:
        if n > 1024:
            prov.warmup(sizes=(min(n, 10240),), msg_len=160)
        pks, msgs, sigs = bench_root.make_batch(min(n, 10240))
        reps = max(1, n // 10240)
        if reps > 1:
            # streaming config: keep `reps` windows in flight and sync
            # once — the fast-sync/light-client streaming pattern. One
            # synchronous call per window would add a host round trip
            # per window to what is meant as a device rate.
            import jax
            import jax.numpy as jnp

            fn = prov.model._get_fn(10240, 160)
            assert fn is not None  # block_on_compile=True provider
            dev = [
                jax.device_put(jnp.asarray(x))
                for x in (
                    pks.astype(np.uint8), msgs.astype(np.uint8),
                    sigs.astype(np.uint8),
                )
            ]
            dt = bench_root.stream_windows(fn, dev, reps)
            ok = np.asarray(fn(*dev))
        else:
            t0 = time.perf_counter()
            ok = prov.verify_batch(pks, msgs, sigs)
            dt = time.perf_counter() - t0
        assert ok.all()
        emit(f"sig_verify_{n}", n / dt, "sigs/s")
        if dt > 60:
            # slow backend (forced-CPU fallback): larger sizes would run
            # for many minutes without adding information
            print(f"skipping larger sizes (last took {dt:.0f}s)", file=sys.stderr)
            break


def bench_vote_ingest():
    """BASELINE eval 5: large-validator-set vote ingest through the
    batched VoteSet path (types/vote_set.go:142 AddVote serial loop in
    the reference). Scaled down by default; EVAL5_FULL=1 for 50k."""
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.crypto.batch import make_provider
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    full = os.environ.get("EVAL5_FULL") == "1"
    n = 50_000 if full else 5_000
    micro_batch = 2_048  # gossip-arrival drain size

    privs = [Ed25519PrivKey.from_secret(b"ing%d" % i) for i in range(n)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    votes = []
    for i, val in enumerate(vals.validators):
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp_ns=1000 + i, validator_address=val.address,
            validator_index=i,
        )
        v.signature = by_addr[val.address].sign(v.sign_bytes("ingest-chain"))
        votes.append(v)

    prov = make_provider("tpu")
    tail = n % micro_batch or micro_batch
    prov.warmup(sizes=(micro_batch, tail), msg_len=160)
    # Warm the tabled path out of the timed region, like a live node
    # does at start (register_valset): the 50k table build is the
    # dominant one-time cost and must not masquerade as ingest time.
    # Bucket warmup rows are garbage (all-invalid) — shapes are what
    # compiles, validity is irrelevant.
    import numpy as np

    key, pk, _ed = vals.batch_cache()
    prov.register_valset(key, pk)
    ml = len(votes[0].sign_bytes("ingest-chain"))
    for rows in sorted({micro_batch, tail}):
        prov.verify_rows_cached(
            key, pk, np.zeros(rows, np.int32),
            np.zeros((rows, ml), np.uint8), np.zeros((rows, 64), np.uint8),
        )
    vs = VoteSet("ingest-chain", 1, 0, PRECOMMIT_TYPE, vals, provider=prov)
    t0 = time.perf_counter()
    total_added = 0
    for off in range(0, n, micro_batch):
        added, errs = vs.add_votes_batched(votes[off : off + micro_batch])
        total_added += sum(added)
        assert not errs, errs[:1]
    dt = time.perf_counter() - t0
    assert total_added == n
    emit(f"vote_ingest_{n}_validators", n / dt, "votes/s")
    emit(f"vote_ingest_{n}_total", dt * 1e3, "ms")


def bench_fastsync():
    """BASELINE eval 4: fast-sync replay verify — 4k-validator commits
    across many heights through verify_commits_batched (the v2
    processor's verify site, blockchain/v2/processor_context.go:42,
    which the reference drives ONE serial VerifyCommit per block).

    Host chain synthesis at full scale (10k blocks × 4k sigs = 40M
    signatures) is host-bound, not a device property, so ONE 4k-sig
    commit is signed and replayed across K heights; the verify work per
    block is identical. Reports blocks/s and the projected 10k-block
    replay time at that rate (labeled projected_*). EVAL4_HEIGHTS
    overrides K (default 64; 256 with EVAL4_FULL=1)."""
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.crypto.batch import make_provider
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import (
        CommitVerifySpec,
        ValidatorSet,
        verify_commits_batched,
    )
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    chain_id = "fastsync-bench"
    n_vals = 4000
    k = int(
        os.environ.get(
            "EVAL4_HEIGHTS", "256" if os.environ.get("EVAL4_FULL") == "1" else "64"
        )
    )
    privs = [Ed25519PrivKey.from_secret(b"fs%d" % i) for i in range(n_vals)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x31" * 32, PartSetHeader(1, b"\x32" * 32))
    vs = VoteSet(chain_id, 1, 0, PRECOMMIT_TYPE, vals)
    for i, val in enumerate(vals.validators):
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp_ns=1000 + i, validator_address=val.address,
            validator_index=i,
        )
        v.signature = by_addr[val.address].sign(v.sign_bytes(chain_id))
        vs.add_vote(v)
    commit = vs.make_commit()

    prov = make_provider("tpu")
    specs = [
        CommitVerifySpec(vals, chain_id, bid, 1, commit) for _ in range(k)
    ]
    # ONE untimed full-size pass: compiles the streaming window buckets,
    # builds the valset tables AND settles the device allocator at the
    # full in-flight window count (measured: a 20480-row warmup left the
    # first 262144-row call paying ~27s of one-time work that a
    # same-size second call did not)
    errs = verify_commits_batched(specs, provider=prov)
    assert all(e is None for e in errs), errs[:1]

    t0 = time.perf_counter()
    errs = verify_commits_batched(specs, provider=prov)
    dt = time.perf_counter() - t0
    assert all(e is None for e in errs), errs[:1]

    emit(f"fastsync_replay_verify_{n_vals}v_{k}blocks", dt * 1e3, "ms")
    emit(f"fastsync_replay_blocks_per_s_{n_vals}v", k / dt, "blocks/s")
    emit(f"fastsync_projected_10k_blocks_{n_vals}v", 10_000 / (k / dt), "s")


def bench_mempool():
    """mempool/bench_test.go: CheckTx + Reap."""
    from tendermint_tpu.abci.client.local import LocalClient
    from tendermint_tpu.abci.examples.kvstore import KVStoreApplication
    from tendermint_tpu.config import MempoolConfig
    from tendermint_tpu.mempool import Mempool

    async def go():
        client = LocalClient(KVStoreApplication())
        await client.start()
        pool = Mempool(MempoolConfig(size=200_000), client)
        n = 10_000
        t0 = time.perf_counter()
        for i in range(n):
            await pool.check_tx(i.to_bytes(8, "big"))
        check = time.perf_counter() - t0
        t0 = time.perf_counter()
        txs = pool.reap_max_bytes_max_gas(-1, -1)
        reap = time.perf_counter() - t0
        assert len(txs) == n
        emit("mempool_checktx", n / check, "txs/s")
        emit("mempool_reap_10k", reap * 1e3, "ms")

    asyncio.run(go())


def bench_secretconn():
    """p2p/conn/secret_connection_test.go:389: throughput."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.p2p.conn.secret_connection import SecretConnection

    async def go():
        ready = asyncio.Queue()

        async def on_conn(r, w):
            await ready.put((r, w))

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        host, port = server.sockets[0].getsockname()[:2]
        cr, cw = await asyncio.open_connection(host, port)
        sr, sw = await ready.get()
        sc1, sc2 = await asyncio.gather(
            SecretConnection.make(cr, cw, Ed25519PrivKey.generate()),
            SecretConnection.make(sr, sw, Ed25519PrivKey.generate()),
        )
        total = 64 * 1024 * 1024  # 64MB
        chunk = b"\xaa" * (1 << 20)

        async def writer():
            sent = 0
            while sent < total:
                await sc1.write(chunk)
                sent += len(chunk)

        async def reader():
            got = 0
            while got < total:
                got += len(await sc2.read(1 << 16))

        t0 = time.perf_counter()
        await asyncio.gather(writer(), reader())
        dt = time.perf_counter() - t0
        emit("secretconn_throughput", total / dt / 1e6, "MB/s")
        sc1.close()
        sc2.close()
        server.close()

    asyncio.run(go())


def bench_valset():
    """types/validator_set_test.go:1416 BenchmarkUpdates."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet

    n = 1000
    vals = [
        Validator(Ed25519PrivKey.from_secret(f"b{i}".encode()).pub_key(), 10)
        for i in range(n)
    ]
    vs = ValidatorSet(vals[: n // 2])
    t0 = time.perf_counter()
    vs.update_with_change_set(vals[n // 2 :])
    dt = time.perf_counter() - t0
    emit("valset_update_500_into_500", dt * 1e3, "ms")
    t0 = time.perf_counter()
    for _ in range(100):
        vs.increment_proposer_priority(1)
    emit("valset_increment_priority_1k_x100", (time.perf_counter() - t0) * 1e3, "ms")


def bench_txindex():
    """state/txindex/kv/kv_test.go:360: insert throughput."""
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.db.memdb import MemDB
    from tendermint_tpu.state.txindex import KVTxIndexer, TxResult

    idx = KVTxIndexer(MemDB())
    n = 10_000
    results = [
        TxResult(
            height=i // 100 + 1, index=i % 100, tx=i.to_bytes(8, "big"),
            result=abci.ResponseDeliverTx(
                events=[abci.Event("e", [abci.KVPair(b"k", str(i % 50).encode())])]
            ),
        )
        for i in range(n)
    ]
    t0 = time.perf_counter()
    for r in results:
        idx.index(r)
    dt = time.perf_counter() - t0
    emit("txindex_insert", n / dt, "txs/s")


def bench_e2e():
    """Single-node commit cadence (localnet rig analog)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))
    from cs_harness import start_network, stop_network

    from tendermint_tpu.config import test_config

    async def go():
        cfg = test_config().consensus
        cfg.timeout_commit_ms = 0
        cfg.skip_timeout_commit = True
        nodes = await start_network(4, config=cfg)
        try:
            await nodes[0].cs.wait_for_height(2, timeout_s=30)
            t0 = time.perf_counter()
            target = nodes[0].cs.state.last_block_height + 20
            await asyncio.gather(*(n.cs.wait_for_height(target, 60) for n in nodes))
            dt = time.perf_counter() - t0
            emit("e2e_4node_commit_latency", dt / 20 * 1e3, "ms/block")
        finally:
            await stop_network(nodes)

    asyncio.run(go())


BENCHES = {
    "light": bench_light,
    "headers": bench_headers_heights,
    "ingest": bench_vote_ingest,
    "sigs": bench_sig_scaling,
    "fastsync": bench_fastsync,
    "mempool": bench_mempool,
    "secretconn": bench_secretconn,
    "valset": bench_valset,
    "txindex": bench_txindex,
    "e2e": bench_e2e,
}


_DEVICE_BENCHES = {"headers", "ingest", "sigs", "fastsync"}

if __name__ == "__main__":
    names = sys.argv[1:] or list(BENCHES)
    if _DEVICE_BENCHES & set(names):
        # same discipline as bench.py: JAX's default backend, named on
        # stderr; not a TPU and JAX_PLATFORMS=cpu not set => exit != 0
        from tendermint_tpu.utils.jaxenv import require_accelerator

        require_accelerator("micro")
    for name in names:
        BENCHES[name]()
