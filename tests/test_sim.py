"""The deterministic network simulator (tendermint_tpu/sim/).

Pins the ISSUE-13 acceptance surface: schedule grammar validation,
byte-identical same-seed replays (commit hashes + event trace + ledger
phase names), the shared-engine multi-node bundle telemetry, the
scenario corpus holding at tier-1 scale, and — under ``slow`` — the
256-node/50-height partition run inside its wall-clock budget plus the
1000-node variant.
"""

import pytest

from tendermint_tpu.crypto.pipeline import SigCache
from tendermint_tpu.sim.core import Simulation
from tendermint_tpu.sim.scenario import (
    list_scenarios,
    load_scenario,
    run_scenario,
)
from tendermint_tpu.sim.schedule import ScheduleError, parse_schedule
from tendermint_tpu.utils.clock import SimClock


# -- schedule grammar -------------------------------------------------------


def test_schedule_grammar_round_trip():
    s = parse_schedule(
        "link(*,*):delay:ms=80,jitter_ms=20;link(0-3,7):loss:p=0.25;"
        "partition:at_h=12,heal_h=15,frac=0.33;"
        "crash:node=7,at_h=20,restart_h=24;"
        "byz:node=0,kind=double_sign,at_h=2;"
        "load:txs=64,at_h=3,size=40;quantum:ms=2"
    )
    s.bind(16, 8)
    # last-match-wins per field over the defaults
    assert s.link_params(5, 6) == (80.0, 20.0, 0.0)
    assert s.link_params(2, 7) == (80.0, 20.0, 0.25)
    assert s.quantum_ms == 2.0
    assert s.crashes[0].node == 7 and s.crashes[0].restart_h == 24
    assert s.byz[0].kind == "double_sign"
    assert s.loads[0].txs == 64


def test_schedule_frac_cut_is_proportional_and_deterministic():
    s = parse_schedule("partition:at_h=5,heal_h=9,frac=0.33")
    cut = s.partitions[0].cut_set(256, 16)
    # floor(0.33*16)=5 validators + round(0.33*240)=79 observers
    assert len([i for i in cut if i < 16]) == 5
    assert len(cut) == 5 + 79
    # strictly fewer than 1/3 of validators whenever frac < 1/3
    assert len([i for i in cut if i < 16]) < 16 / 3
    assert cut == s.partitions[0].cut_set(256, 16)  # no RNG involved


def test_schedule_rejects_bad_specs():
    for bad in (
        "teleport:at_h=1",                      # unknown verb
        "link(0):delay:ms=10",                  # malformed selector
        "link(*,*):warp:ms=10",                 # unknown link sub-verb
        "link(*,*):loss:p=1.5",                 # loss out of range
        "partition:at_h=5,heal_h=5,frac=0.3",   # heal must be > at
        "partition:at_h=5,heal_h=9",            # needs frac or cut
        "crash:node=1,at_h=3",                  # missing restart_h
        "byz:node=0,kind=gaslight",             # unknown byz kind
        "quantum:ms=0",                         # quantum must be positive
        "load:txs=4,at_h=2,color=red",          # unknown key
        "partition:at_h=x,heal_h=9,frac=0.3",   # non-integer
    ):
        with pytest.raises(ScheduleError):
            sched = parse_schedule(bad)
            sched.bind(8, 8)


def test_schedule_bind_validates_node_references():
    s = parse_schedule("crash:node=12,at_h=2,restart_h=4")
    with pytest.raises(ScheduleError):
        s.bind(8, 8)  # node 12 out of range
    s2 = parse_schedule("byz:node=5,kind=amnesia")
    with pytest.raises(ScheduleError):
        s2.bind(8, 4)  # byzantine node must be a validator
    s3 = parse_schedule("partition:at_h=2,heal_h=4,cut=0-7")
    with pytest.raises(ScheduleError):
        s3.bind(8, 8)  # cutting every node is not a partition


def test_schedule_rejects_overlapping_partitions():
    # SimNet models one flat cut set: concurrent partitions would merge
    # silently — bind refuses them up front; sequential windows are fine
    s = parse_schedule(
        "partition:at_h=3,heal_h=10,cut=0-1;partition:at_h=4,heal_h=8,cut=4-5"
    )
    with pytest.raises(ScheduleError, match="overlapping"):
        s.bind(8, 8)
    ok = parse_schedule(
        "partition:at_h=3,heal_h=5,cut=0-1;partition:at_h=6,heal_h=8,cut=4-5"
    )
    ok.bind(8, 8)


def test_full_receiver_queue_defers_without_reordering():
    """A full input queue opens a per-receiver backlog drained in
    arrival order — a slow receiver delays its link but NEVER reorders
    it (an overtaking part would be silently dropped by consensus and
    a one-shot simulator never re-gossips)."""
    import asyncio

    from tendermint_tpu.sim.net import SimNet
    from tendermint_tpu.utils.clock import SimClock

    class _Stub:
        def __init__(self, cap):
            self._queue = asyncio.Queue(maxsize=cap)
            self._crashed = False

    clock = SimClock(0)
    net = SimNet(clock, parse_schedule("link(*,*):delay:ms=5"), seed=1)
    nodes = [_Stub(100), _Stub(1)]  # node1 can hold ONE message
    net.attach(nodes, [None, None], 1)
    for i in range(4):
        net.unicast(0, 1, f"msg-{i}")
    while clock.has_work() and nodes[1]._queue.qsize() == 0:
        clock.advance()
    # first delivery landed, the rest deferred; drain one at a time
    seen = []
    for _ in range(16):
        while nodes[1]._queue.qsize():
            seen.append(nodes[1]._queue.get_nowait().msg)
        if not clock.has_work():
            break
        clock.advance()
    assert seen == [f"msg-{i}" for i in range(4)], seen
    assert not net._deferred  # backlog fully drained and cleaned up


def test_schedule_parse_is_atomic():
    # a malformed LATER item must fail the whole spec (nothing armed)
    with pytest.raises(ScheduleError):
        parse_schedule("link(*,*):delay:ms=10;bogus:verb=1")


# -- clock ------------------------------------------------------------------


def test_sim_clock_fires_in_deadline_then_registration_order():
    clock = SimClock(start_ns=0)
    fired = []
    clock.call_later(0.2, fired.append, "b")
    clock.call_later(0.1, fired.append, "a")
    h = clock.call_later(0.1, fired.append, "cancelled")
    clock.call_later(0.1, fired.append, "a2")
    h.cancel()
    while clock.advance():
        pass
    assert fired == ["a", "a2", "b"]
    assert clock.time_ns() == 200_000_000
    assert not clock.has_work()


def test_sim_clock_drives_consensus_timeouts():
    # TimeoutTicker resolves against the clock seam: scheduling against
    # a SimClock fires on advance(), never on the wall
    import asyncio

    from tendermint_tpu.consensus.messages import TimeoutInfo
    from tendermint_tpu.consensus.state import TimeoutTicker

    async def go():
        clock = SimClock(start_ns=0)
        q = asyncio.Queue()
        ticker = TimeoutTicker(q, clock=clock)
        ticker.schedule(TimeoutInfo(5_000, 1, 0, 1))  # 5 sim-seconds
        assert q.empty()
        assert clock.advance()
        ti = q.get_nowait()
        assert ti.height == 1 and clock.time_ns() == 5_000_000_000
        # a new schedule replaces the old (cancelled timer never fires)
        ticker.schedule(TimeoutInfo(1_000, 2, 0, 1))
        ticker.schedule(TimeoutInfo(2_000, 3, 0, 1))
        while clock.advance():
            pass
        assert q.get_nowait().height == 3
        assert q.empty()

    asyncio.run(go())


# -- determinism ------------------------------------------------------------

_DET_SCHEDULE = (
    "link(*,*):delay:ms=10,jitter_ms=6;link(1,3):loss:p=0.2;"
    "partition:at_h=3,heal_h=5,frac=0.3"
)


def _run_once(seed: int):
    sim = Simulation(
        n_nodes=6, validators=4, heights=7, seed=seed,
        schedule=_DET_SCHEDULE, record_events=True, max_sim_s=300,
    )
    res = sim.run()
    assert res.completed, res.heights
    return res


def test_same_seed_is_bit_identical():
    """The acceptance pin: same seed + schedule => identical commit
    hashes, identical fault-injection/delivery event sequence, and
    identical HeightLedger phase names across two fresh runs."""
    a = _run_once(42)
    b = _run_once(42)
    assert a.commit_hashes == b.commit_hashes
    assert a.trace_digest == b.trace_digest
    assert a.events == b.events
    assert a.ledger_phases == b.ledger_phases
    assert a.safety_ok() and b.safety_ok()
    # the trace actually contains network behavior, not just commits
    kinds = {e[0] for e in a.events}
    assert "deliver" in kinds and "drop" in kinds and "partition" in kinds


def test_changed_seed_diverges():
    a = _run_once(42)
    c = _run_once(43)
    assert a.trace_digest != c.trace_digest
    assert a.events != c.events


# -- shared-engine telemetry ------------------------------------------------


def test_verify_traffic_batches_across_nodes():
    """The shared PipelinedVerifier's engine_stats() shows device
    bundles whose rows came from MORE THAN ONE simulated node (the
    cross-node coalescing the accelerator thesis predicts), and the
    pre-verifier demonstrably warms the per-node caches (receivers'
    inline verification is cache hits, not re-verification)."""
    sim = Simulation(
        n_nodes=8, validators=6, heights=5, seed=9,
        schedule="link(*,*):delay:ms=10,jitter_ms=4", max_sim_s=300,
    )
    res = sim.run()
    assert res.completed
    eng = res.engine
    assert eng["engine"] == "pipeline"
    counters = eng["counters"]
    assert counters["multi_source_bundles"] >= 1
    assert counters["max_bundle_sources"] > 1
    # the shared engine's inner provider is the host verifier: every
    # row it verified is a host row, none a device row
    assert eng["host_rows"] > 0 and eng["device_rows"] == 0
    assert res.net["preverified_rows"] > 0
    # per-node caches were actually consulted and hit by inline ingest
    assert sum(c.hits for c in sim.node_caches) > 0


def test_pipeline_source_labels():
    """submit_batch(sources=...) attribution: one bundle spanning rows
    from several labeled nodes counts into multi_source_bundles; an
    unlabeled submit never does."""
    import numpy as np

    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier

    rows = []
    for i in range(4):
        k = Ed25519PrivKey.from_secret(f"src-{i}".encode())
        msg = f"msg-{i}".encode().ljust(32, b"\x00")
        rows.append((k.pub_key().bytes(), msg, k.sign(msg)))
    pk = np.frombuffer(b"".join(r[0] for r in rows), dtype=np.uint8).reshape(4, 32)
    mg = np.frombuffer(b"".join(r[1] for r in rows), dtype=np.uint8).reshape(4, 32)
    sg = np.frombuffer(b"".join(r[2] for r in rows), dtype=np.uint8).reshape(4, 64)
    with PipelinedVerifier(cache=SigCache()) as pv:
        ok = pv.submit_batch(
            pk, mg, sg, sources=["node0", "node1", "node2", "node2"]
        ).result(timeout=60)
        assert ok.all()
        s = pv.stats()
        assert s["multi_source_bundles"] == 1
        assert s["max_bundle_sources"] == 3
        ok2 = pv.submit_batch(pk, mg, sg).result(timeout=60)
        assert ok2.all()
        assert pv.stats()["multi_source_bundles"] == 1  # unlabeled: unchanged
        with pytest.raises(ValueError):
            pv.submit_batch(pk, mg, sg, sources=["just-one"])
    assert pv.engine_stats()["counters"]["max_bundle_sources"] == 3


def test_cached_commit_replay_is_sound():
    """The validate-path SigCache fast path can never accept what the
    slow path would reject: a tampered signature misses the cache (sig
    is part of the key) and fails, and a sub-quorum commit raises even
    with every signature cached."""
    import numpy as np

    from tendermint_tpu.types.validator_set import (
        ErrInvalidCommitSignature,
        ErrNotEnoughVotingPower,
    )
    from tests.cs_harness import make_genesis
    from tendermint_tpu.state.state import state_from_genesis_doc
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote_set import VoteSet
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.types.vote import Vote

    genesis, privs = make_genesis(4)
    state = state_from_genesis_doc(genesis)
    vals = state.validators
    bid = BlockID(hash=b"\x11" * 32, parts=PartSetHeader(total=1, hash=b"\x22" * 32))
    cache = SigCache()
    vs = VoteSet(genesis.chain_id, 1, 0, PRECOMMIT_TYPE, vals, dedupe_cache=cache)
    for i, pv in enumerate(privs):
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            validator_address=pv.address(), validator_index=i,
        )
        pv.sign_vote(genesis.chain_id, v)
        assert vs.add_vote(v)
    commit = vs.make_commit()

    # warm path: every row was verified at ingest -> replay accepts
    vals.verify_commit(genesis.chain_id, bid, 1, commit, sig_cache=cache)

    # tampered signature: different key -> cache miss -> slow path rejects
    import copy

    bad = copy.deepcopy(commit)
    sig = bytearray(bad.signatures[0].signature)
    sig[0] ^= 0xFF
    bad.signatures[0].signature = bytes(sig)
    with pytest.raises(ErrInvalidCommitSignature):
        vals.verify_commit(genesis.chain_id, bid, 1, bad, sig_cache=cache)

    # sub-quorum: strip to one signer; all-cached rows must still raise
    from tendermint_tpu.types.block import CommitSig

    sub = copy.deepcopy(commit)
    sub.signatures = [
        cs if i == 0 else CommitSig.absent()
        for i, cs in enumerate(sub.signatures)
    ]
    with pytest.raises(ErrNotEnoughVotingPower):
        vals.verify_commit(genesis.chain_id, bid, 1, sub, sig_cache=cache)


# -- scenario corpus --------------------------------------------------------


def test_scenario_corpus_is_complete_and_loads():
    names = list_scenarios()
    assert {
        "amnesia.scn", "double_sign.scn", "flash_crowd.scn",
        "partition_commit.scn", "valset_rotation.scn",
    } <= set(names)
    for name in names:
        sc = load_scenario(name)
        assert sc.expect, f"{name} pins no expectations"


def test_scenario_loader_rejects_bad_files(tmp_path):
    cases = {
        "unknown_key.scn": "nodes = 4\nheights = 3\nexpect = safety\nwarp = 9",
        "no_expect.scn": "nodes = 4\nheights = 3",
        "bad_expect.scn": "nodes = 4\nheights = 3\nexpect = vibes",
        "bad_sched.scn": "nodes = 4\nheights = 3\nexpect = safety\nschedule = nope:x=1",
        "rotate_no_app.scn": (
            "nodes = 4\nheights = 3\nexpect = safety\n"
            "rotate = at_h=2,validator=0,power=5"
        ),
    }
    for name, body in cases.items():
        p = tmp_path / name
        p.write_text(body + "\n")
        with pytest.raises(ValueError):
            load_scenario(str(p))


@pytest.mark.parametrize("name", sorted(set(list_scenarios())))
def test_scenario_holds_at_tier1_scale(name):
    """Every corpus scenario's pinned expectations hold at its file's
    (small) node count — the tier-1 leg of the corpus; 256–1000-node
    legs run under ``slow`` below."""
    sc, sim, res, fails = run_scenario(name)
    assert fails == [], f"{name}: {fails}"
    assert res.safety_ok()


def test_traced_run_exports_merged_observatory_trace():
    """traced=True gives every simulated node its own Tracer and the
    result carries ONE merged perfetto document (PR 12 observatory)
    with per-node process rows, plus per-node HeightLedger reports."""
    sim = Simulation(
        n_nodes=4, validators=4, heights=3, seed=2, traced=True,
        schedule="link(*,*):delay:ms=8", max_sim_s=300,
    )
    res = sim.run()
    assert res.completed
    doc = res.merged_trace
    assert doc is not None and doc["traceEvents"]
    pids = {e.get("pid") for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert len(pids) >= 4  # one process row per simulated node
    names = {e.get("name") for e in doc["traceEvents"]}
    assert "consensus.finalize_commit" in names
    # ledger reports came along: every node attributed its heights
    for i in range(4):
        assert res.ledgers[i]["count"] >= 3
        assert res.ledger_phases[i]


def test_crash_restart_recovers():
    """The crash verb (default mode=replay, ISSUE 14): the crashed
    node's ConsensusState is torn down, rebuilt from its durability
    domain via WAL replay, and catches back up through the net's
    catchup feed. (mode=isolation keeps the PR-13 memory-intact path —
    tests/test_sim_durability.py pins both.)"""
    sim = Simulation(
        n_nodes=5, validators=4, heights=10, seed=3,
        schedule="link(*,*):delay:ms=8;crash:node=4,at_h=3,restart_h=6",
        record_events=True, max_sim_s=300,
    )
    res = sim.run()
    assert res.completed and res.safety_ok()
    kinds = [e[0] for e in res.events]
    assert "crash" in kinds and "restart" in kinds and "catchup" in kinds
    assert "wal_replay" in kinds
    assert res.heights[4] >= 10


def test_wedge_autopsy_names_cut_validators():
    """ISSUE 18 pin: a 50/50 validator partition wedges both sides, and
    the sim auto-collects every node's stall autopsy — each side's
    diagnosis names the blocked step and EXACTLY the validator indices
    on the other side of the cut. A liveness evaluation over the same
    run carries the per-node autopsy in its failure message, so a
    wedged scenario fails with "who is missing", not just "timed out"."""
    from tendermint_tpu.sim.scenario import evaluate

    sc, sim, res, fails = run_scenario("wedge_autopsy.scn")
    assert fails == [], fails          # safety holds on a wedged net
    assert res.timed_out and not res.completed
    cut = parse_schedule(sc.schedule).partitions[0].cut_set(
        sc.nodes, sc.validators
    )
    cut_vals = sorted(i for i in cut if i < sc.validators)
    assert cut_vals == [4, 5, 6, 7]    # frac=0.5 of 8 validators
    assert set(res.autopsies) == set(range(sc.nodes))
    for i, diag in res.autopsies.items():
        other_side = (
            cut_vals if i not in cut
            else sorted(set(range(sc.validators)) - cut)
        )
        assert diag["blocked_step"] == "Prevote", (i, diag)
        assert diag["missing_validators"] == other_side, (i, diag)
        q = diag["quorum"]["prevote"]
        assert not q["has_two_thirds"]
        assert q["missing_validators"] == other_side
        assert q["power_present"] < q["power_needed"]
    # the enriched failure message names blocked step + missing set
    sc.expect = ["liveness"]
    blob = "\n".join(evaluate(sc, sim, res))
    assert "liveness violated" in blob
    assert "blocked at Prevote" in blob
    assert "missing validators [4, 5, 6, 7]" in blob   # majority's view
    assert "missing validators [0, 1, 2, 3]" in blob   # minority's view


# -- the scaled acceptance runs (slow) --------------------------------------


@pytest.mark.slow
def test_partition_256_nodes_50_heights_under_budget():
    """ISSUE 13 acceptance: a 256-node, 50-height run under the
    33%-partition-at-commit schedule completes within the wall budget
    on this box's CPU fallback, commits on the majority side, recovers
    after heal, and two same-seed runs are bit-identical (commit hashes
    + event-trace digest). Verify traffic demonstrably batches across
    nodes on the shared engine.

    Budget history: <60 s when nodes kept no durable state (PR 13,
    measured ~40 s). PR 14 gave every node a real durability domain —
    per-delivery WAL framing, store journaling, evidence pools, boot
    handshake (~65 s measured idle on this box) — so the pin is 90 s:
    still catches a structural regression (the pre-memo WAL encode bug
    measured +25 s), without failing on the cost the durable-node
    tentpole deliberately added."""
    runs = []
    for _ in range(2):
        sc, sim, res, fails = run_scenario(
            "partition_commit.scn", nodes=256, validators=8, heights=50,
        )
        assert fails == [], fails
        assert res.completed and res.safety_ok()
        assert res.wall_seconds < 90.0, f"wall {res.wall_seconds:.1f}s"
        assert res.engine["counters"]["multi_source_bundles"] > 0
        assert res.engine["counters"]["max_bundle_sources"] > 1
        runs.append(res)
    assert runs[0].trace_digest == runs[1].trace_digest
    assert runs[0].commit_hashes == runs[1].commit_hashes


@pytest.mark.slow
def test_partition_1000_nodes():
    """The 1000-node variant: same schedule semantics at the ROADMAP's
    target scale — majority commits through the partition, the ~330
    severed nodes catch up after heal, one engine serves them all."""
    sc, sim, res, fails = run_scenario(
        "partition_commit.scn", nodes=1000, validators=8, heights=30,
        max_sim_s=900.0,
    )
    assert fails == [], fails
    assert res.completed and res.safety_ok()
    assert min(res.heights.values()) >= 30
    assert res.engine["counters"]["multi_source_bundles"] > 0
