"""Per-valset cached-table verify path (round 3): the commit-shaped
path — 160-byte sign bytes, the valset-size bucket warmed at node
start, templated rows materialized on the device, and
ValidatorSet.verify_commit through the provider. The stage kernels are
in test_tabled_kernels.py, the model's materialized path in
test_tabled_verify.py, sharded tables and cross-height batches in
test_tabled_batches.py.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tendermint_tpu.crypto.batch import RowGroups
from tendermint_tpu.ops import ref_ed25519 as ref
from tests.tabled_helpers import arrs, sign_rows


def test_register_valset_prewarms_tabled_path():
    """Node-start warmup: register_valset builds tables + warms the
    valset-size bucket so the FIRST live verify uses the cached path
    (blocking mode: immediately; non-blocking: after the background
    build completes)."""
    import time as _time

    import tendermint_tpu.models.verifier as mv
    from tendermint_tpu.models.verifier import VerifierModel

    # msg_len 160 = the commit sign-bytes width register_valset warms
    pks, msgs, sigs = sign_rows(12, msg_len=160, seed=19)
    pk, mg, sg = arrs(pks, msgs, sigs)
    idx = np.arange(12, dtype=np.int32)

    m = VerifierModel(block_on_compile=True)
    m.register_valset(b"boot-valset", pk)
    assert len(m.key_pool) == 12 and m.key_pool.dispatches == 1
    ok = m.verify_rows_cached(b"boot-valset", pk, idx, mg, sg)
    assert ok is not None and ok.all()
    assert len(m.key_pool) == 12 and m.key_pool.dispatches == 1  # no rebuild

    # Non-blocking: the warmup ALONE (no live traffic) must build the
    # tables and warm the valset-size bucket — awaited on the warm-up's
    # own threads, WITHOUT calling verify_rows_cached, which would
    # otherwise kick the lazy build itself and mask a broken warmup.
    with mv._compile_threads_lock:
        before = set(mv._compile_threads)
    m2 = VerifierModel(block_on_compile=False)
    m2.register_valset(b"boot-valset-2", pk)
    # the build thread, the thread that waits for it, then the four
    # warm passes it starts: joined until none this test started is
    # left, however slowly a loaded machine compiles them
    deadline = _time.monotonic() + 540
    while True:
        with mv._compile_threads_lock:
            mine = [t for t in mv._compile_threads if t not in before and t.is_alive()]
        if not mine:
            break
        for t in mine:
            t.join(timeout=max(0.0, deadline - _time.monotonic()))
        assert _time.monotonic() < deadline, f"warm-up still running: {mine}"
    assert len(m2.key_pool) == 12, "warmup alone never built the tables"
    rows = m2.key_pool.capacity()
    # a full commit's slot-order shape (one commit of `rows` slots) and
    # the gathered pair at the set's bucket, both message flavors
    for k in (
        ("slots", rows, 160, 0, rows, 1), ("slots-tpl", rows, 160, 2, rows, 1),
        ("tabled", 16, 160, 0, rows, 1), ("tabled-tpl", 16, 160, 2, rows, 1),
    ):
        ent = m2._entries.get(k)
        assert ent is not None and ent.ready, f"warmup alone never warmed {k}"
    # and the first live call is served immediately (no None fallback)
    ok2 = m2.verify_rows_cached(b"boot-valset-2", pk, idx, mg, sg)
    assert ok2 is not None and ok2.all()


def _templated_rows(n, n_templates=3, seed=11):
    """Signed rows whose messages are template[tmpl_idx] with an 8-byte
    splice at the sign-bytes timestamp offset (93:101) — the exact
    shape materialize_sign_bytes reconstructs on device."""
    rng = np.random.default_rng(seed)
    templates = rng.integers(0, 256, size=(n_templates, 160)).astype(np.uint8)
    tmpl_idx = rng.integers(0, n_templates, size=n).astype(np.int32)
    ts8 = rng.integers(0, 256, size=(n, 8)).astype(np.uint8)
    msgs = templates[tmpl_idx].copy()
    msgs[:, 93:101] = ts8
    seeds = [rng.bytes(32) for _ in range(n)]
    pks = np.frombuffer(
        b"".join(ref.pubkey_from_seed(s) for s in seeds), dtype=np.uint8
    ).reshape(n, 32)
    sigs = np.frombuffer(
        b"".join(ref.sign(s, m.tobytes()) for s, m in zip(seeds, msgs)),
        dtype=np.uint8,
    ).reshape(n, 64)
    return pks, templates, tmpl_idx, ts8, msgs, sigs


def _tabled_counts():
    from tendermint_tpu.crypto.batch import TABLED_COUNTS

    return TABLED_COUNTS.snapshot()


def _grew(before, **want):
    after = _tabled_counts()
    got = {k: after[f"tabled_{k}"] - before[f"tabled_{k}"] for k in ("slot_rows", "slot_pad", "gathered_rows")}
    assert got == {"slot_rows": 0, "slot_pad": 0, "gathered_rows": 0, **want}, got


def test_templated_rows_cached_matches_materialized():
    """verify_rows_cached_templated must accept/reject bit-identically
    to verify_rows_cached on the materialized messages — the full-set
    shape (slot order without holes), a gathered subset with duplicate
    and descending indices (what a trusting lookup by address in an
    older set may hand over), and corrupted rows."""
    from tendermint_tpu.models.verifier import VerifierModel

    n = 16  # the 16-row bucket the other tests of this file compile
    pks, templates, tmpl_idx, ts8, msgs, sigs = _templated_rows(n)
    sigs = sigs.copy()
    sigs[5, 3] ^= 1
    ts8_bad = ts8.copy()
    ts8_bad[9] ^= 0xFF  # wrong timestamp => wrong sign bytes => reject

    m = VerifierModel(block_on_compile=True)
    key = b"tpl-parity"
    idx = np.arange(n, dtype=np.int32)
    before = _tabled_counts()
    ok_mat = m.verify_rows_cached(key, pks, idx, msgs, sigs)
    ok_tpl = m.verify_rows_cached_templated(
        key, pks, idx, templates, tmpl_idx, ts8, sigs
    )
    assert ok_mat is not None and ok_tpl is not None
    _grew(before, slot_rows=2 * n)  # every validator signed: no empty slot
    np.testing.assert_array_equal(ok_mat, ok_tpl)
    assert not ok_tpl[5] and ok_tpl.sum() == n - 1

    ok_bad_ts = m.verify_rows_cached_templated(
        key, pks, idx, templates, tmpl_idx, ts8_bad, sigs
    )
    assert not ok_bad_ts[9] and ok_bad_ts.sum() == n - 2

    # gathered shape with duplicate validator indices
    sub = np.array([3, 3, 11, 0, 7, 15], dtype=np.int32)
    before = _tabled_counts()
    ok_sub = m.verify_rows_cached_templated(
        key, pks, sub, templates, tmpl_idx[sub], ts8[sub], sigs[sub]
    )
    assert ok_sub is not None
    np.testing.assert_array_equal(ok_sub, np.ones(len(sub), dtype=bool))
    _grew(before, gathered_rows=len(sub))  # three runs: 64 slots for 6 rows


def _templated_commits(n_vals, n_commits, seed):
    """n_commits commits of ONE validator set, every validator signing
    each: (pks, templates, [(tmpl_idx, ts8, msgs, sigs) per commit])."""
    rng = np.random.default_rng(seed)
    seeds = [rng.bytes(32) for _ in range(n_vals)]
    pks = np.frombuffer(
        b"".join(ref.pubkey_from_seed(s) for s in seeds), dtype=np.uint8
    ).reshape(n_vals, 32)
    templates = rng.integers(0, 256, size=(2 * n_commits, 160)).astype(np.uint8)
    commits = []
    for k in range(n_commits):
        # a (commit, nil) template pair per height, as the seam sends
        tmpl_idx = (2 * k + (rng.random(n_vals) < 0.2)).astype(np.int32)
        ts8 = rng.integers(0, 256, size=(n_vals, 8)).astype(np.uint8)
        msgs = templates[tmpl_idx].copy()
        msgs[:, 93:101] = ts8
        sigs = np.frombuffer(
            b"".join(ref.sign(s, m.tobytes()) for s, m in zip(seeds, msgs)),
            dtype=np.uint8,
        ).reshape(n_vals, 64).copy()
        commits.append((tmpl_idx, ts8, msgs, sigs))
    return pks, templates, commits


def _present_rows(commits, absent, forged):
    """The rows a seam would hand over: each commit's present
    validators in validator order, `forged` (commit, validator) pairs
    with one signature bit flipped."""
    cols = [[] for _ in range(6)]
    for k, (tmpl_idx, ts8, msgs, sigs) in enumerate(commits):
        sigs, bad = sigs.copy(), np.zeros(len(sigs), dtype=bool)
        for val in (val for c, val in forged if c == k):
            sigs[val, 9] ^= 0x10
            bad[val] = True
        present = np.setdiff1d(np.arange(len(sigs)), absent[k]).astype(np.int32)
        cols[0].append(present)
        for col, a in zip(cols[1:], (tmpl_idx, ts8, msgs, sigs, bad)):
            col.append(a[present])
    return tuple(np.concatenate(col) for col in cols)


@pytest.mark.parametrize(
    "name,n_commits,absent,forged,slots",
    [
        # absent validators at both ends and inside; forged signatures
        # next to each hole and at the first and last present slot
        ("one commit with holes", 1, [[0, 6, 15]], [(0, 1), (0, 5), (0, 7), (0, 14)], 16),
        # three commits -> one launch of C = 4: a hole at slot 0, holes
        # at V-1 and inside, then a commit without holes whose last row
        # is forged — the last real commit of the padded launch
        (
            "three commits, C padded to 4", 3, [[0], [7, 15], []],
            [(0, 1), (1, 14), (2, 0), (2, 15)], 64,
        ),
    ],
)
def test_slot_order_equals_gathered_equals_host(monkeypatch, name, n_commits, absent, forged, slots):
    """Slot order against the gathered pair against the host reference:
    bit-equal verdicts for every row, both message sources, and the
    three counters say which table operand each call took."""
    from tendermint_tpu.models import verifier as vmod

    v = 16  # the padded set: tables.shape[0]
    pks, templates, commits = _templated_commits(v, n_commits, seed=41)
    idx, ti, t8, mg, sg, bad = _present_rows(commits, absent, forged)
    n = len(idx)
    want = np.array(
        [ref.verify(pks[i].tobytes(), m.tobytes(), s.tobytes()) for i, m, s in zip(idx, mg, sg)]
    )
    np.testing.assert_array_equal(want, ~bad)  # the host reference rejects the forged rows, only them
    assert bad.sum() == len(forged)

    m = vmod.VerifierModel(block_on_compile=True)
    key = b"slot-order-" + name.encode()
    plan = vmod.plan_slots(idx, v)
    assert plan is not None and sum(c for _, _, c in plan.launches) * v == slots

    from tendermint_tpu.crypto.batch import H2D_COUNTS

    before = _tabled_counts()
    h2d = H2D_COUNTS.snapshot()["h2d_bytes"]
    ok_tpl = m.verify_rows_cached_templated(key, pks, idx, templates, ti, t8, sg)
    # every slot's signature, template index and timestamp, and the
    # templates padded to their bucket, copied once; nothing else
    tpl_pad = m._src_tpl_pad(("tpl", np.asarray(templates)))
    assert H2D_COUNTS.snapshot()["h2d_bytes"] - h2d == slots * (64 + 4 + 8) + tpl_pad * 160
    ok_mat = m.verify_rows_cached(key, pks, idx, mg, sg)
    _grew(before, slot_rows=2 * n, slot_pad=2 * (slots - n))
    assert any(k[0] == "slots-tpl" and k[1] == slots for k in m._entries)

    monkeypatch.setattr(vmod, "_SLOT_GATHER_RATIO", 0.0)  # the same rows, gathered
    before = _tabled_counts()
    ok_gathered = m.verify_rows_cached_templated(key, pks, idx, templates, ti, t8, sg)
    _grew(before, gathered_rows=n)

    for got in (ok_tpl, ok_mat, ok_gathered):
        assert got is not None and got.shape == (n,)
        np.testing.assert_array_equal(got, want)
    assert m.row_counts.snapshot() == (3 * n, 0)  # empty slots are not device rows


@pytest.fixture(scope="module")
def slot_model():
    """One model for the tests that launch 16 validators' slots: a
    stage-2 program costs minutes on XLA:CPU, once a shape and model."""
    from tendermint_tpu.models.verifier import VerifierModel

    return VerifierModel(block_on_compile=True)


class _CommitGroups(RowGroups):
    """A crypto/batch.RowGroups over _templated_commits' commits, as
    the verify seam's: each group's rows in commit order with the
    group's own (commit, nil) template pairs. ``seen`` keeps, at each
    take, what ``probe()`` read then; ``decline_at`` answers None."""

    def __init__(self, templates, commits, absent, forged, probe, decline_at=None):
        self.templates, self.commits = templates, commits
        self.absent, self.forged = absent, forged
        self.probe, self.decline_at = probe, decline_at
        self.left, self.seen = len(commits), []

    def take(self, count):
        lo = len(self.commits) - self.left
        hi = min(lo + count, len(self.commits))
        self.left -= hi - lo
        self.seen.append(self.probe())
        if len(self.seen) - 1 == self.decline_at:
            return None
        idx, ti, t8, _mg, sg, _bad = _present_rows(
            self.commits[lo:hi], self.absent[lo:hi],
            [(c - lo, val) for c, val in self.forged if lo <= c < hi],
        )
        return idx, self.templates[2 * lo : 2 * hi], ti - 2 * lo, t8, sg


def test_row_groups_equal_the_array_form(monkeypatch, slot_model):
    """verify_rows_cached_templated fed a RowGroups against the same
    rows as arrays: the same verdicts, the same launch shapes (entry
    keys apart from the template pad: a group brings its own template
    pairs), the same counters — and group k+1 is taken only after
    launch k is dispatched. A source that declines mid-way leaves
    nothing counted."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 64)  # 4 commits of 16 slots a launch
    v, n_commits = 16, 9  # launches of 4, 4 and 1 commits
    pks, templates, commits = _templated_commits(v, n_commits, seed=43)
    absent = [[0], [], [7, 15], [], [3], [], [], [15], [1, 2]]
    forged = [(0, 1), (2, 14), (4, 4), (5, 0), (7, 14), (8, 15)]  # first, middle and last group
    idx, ti, t8, _mg, sg, bad = _present_rows(commits, absent, forged)
    n = len(idx)

    m, rows0 = slot_model, slot_model.row_counts.snapshot()[0]
    key = b"row-groups"
    launches, shapes = [], []
    launch, entry = m._launch, m._tabled_bucket_entry
    monkeypatch.setattr(m, "_launch", lambda *a: launches.append(1) or launch(*a))

    def recorded_entry(*a, **kw):
        ent = entry(*a, **kw)
        shapes.extend(k for k, known in m._entries.items() if known is ent)
        return ent

    monkeypatch.setattr(m, "_tabled_bucket_entry", recorded_entry)

    before = _tabled_counts()
    ok_arrays = m.verify_rows_cached_templated(key, pks, idx, templates, ti, t8, sg)
    _grew(before, slot_rows=n, slot_pad=9 * v - n)
    np.testing.assert_array_equal(ok_arrays, ~bad)
    shapes_arrays, launches_arrays = list(shapes), len(launches)
    del shapes[:], launches[:]

    groups = _CommitGroups(templates, commits, absent, forged, lambda: len(launches))
    before = _tabled_counts()
    ok_groups = m.verify_rows_cached_templated(key, pks, groups)
    _grew(before, slot_rows=n, slot_pad=9 * v - n)
    np.testing.assert_array_equal(ok_groups, ok_arrays)
    assert groups.left == 0 and groups.seen == [0, 1, 2]  # launch k went before take k+1
    assert len(launches) == launches_arrays == 3
    drop_pad = lambda keys: [k[:3] + k[4:] for k in keys]
    assert drop_pad(shapes) == drop_pad(shapes_arrays)
    assert [k[:2] for k in shapes] == [("slots-tpl", 64)] * 2 + [("slots-tpl", 16)]
    assert m.row_counts.snapshot() == (rows0 + 2 * n, 0)

    declining = _CommitGroups(templates, commits, absent, forged, lambda: 0, decline_at=1)
    before = _tabled_counts()
    assert m.verify_rows_cached_templated(key, pks, declining) is None
    _grew(before)
    assert len(declining.seen) == 2 and declining.left == 1  # the rest is the seam's to pack
    assert m.row_counts.snapshot() == (rows0 + 2 * n, 0)


def test_row_groups_cold_tail_declines_before_any_launch(monkeypatch, slot_model):
    """Without block_on_compile a chain whose LAST group's shape is
    cold is declined before anything is dispatched: the tail's shape is
    worked out from the commits left when the first group is taken, its
    compile started, no second group taken. A chain whose shapes are
    all warm runs."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 64)  # 4 commits of 16 slots a launch
    v = 16
    pks, templates, commits = _templated_commits(v, 9, seed=43)
    none = [[]] * 9
    m, key = slot_model, b"row-groups"
    chain = lambda k, probe: _CommitGroups(templates[: 2 * k], commits[:k], none, [], probe)
    # launches of 4, 4 and 1 commits: warm (a second or so after the test before)
    assert m.verify_rows_cached_templated(key, pks, chain(9, int)).all()

    monkeypatch.setattr(m, "block_on_compile", False)
    launches, asked = [], []
    launch = m._launch
    monkeypatch.setattr(m, "_launch", lambda *a: launches.append(1) or launch(*a))
    monkeypatch.setattr(
        m, "_compile_tabled_async",
        lambda ent, e, pad, src, slots=False: asked.append((pad, m._src_tpl_pad(src), slots)),
    )
    rows0 = m.row_counts.snapshot()
    short = chain(6, lambda: len(launches))  # 4 and 2 commits: C = 2 has never run
    before = _tabled_counts()
    assert m.verify_rows_cached_templated(key, pks, short) is None
    _grew(before)
    assert launches == [] and short.seen == [0] and short.left == 2
    assert asked == [(2 * v, 8, True)]  # 32 slots, 4 templates in the bucket of 8
    assert m.row_counts.snapshot() == rows0

    whole = chain(9, lambda: len(launches))
    ok = m.verify_rows_cached_templated(key, pks, whole)
    assert ok is not None and ok.all() and whole.seen == [0, 1, 2]
    assert asked == [(2 * v, 8, True)]


def test_templated_windowed_boundary_controls(monkeypatch):
    """The templated source through the >MAX_DEVICE_ROWS streaming path:
    invalid rows planted across every window boundary, same controls as
    the materialized windowed test."""
    from tendermint_tpu.models import verifier as vmod

    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 16)
    pks, templates, tmpl_idx, ts8, msgs, sigs = _templated_rows(16, seed=29)
    n = 42  # 2 full windows of 16 + tail of 10
    rng = np.random.default_rng(5)
    idx = rng.integers(0, 16, size=n).astype(np.int32)
    ti = tmpl_idx[idx].copy()
    t8 = ts8[idx].copy()
    sg = sigs[idx].copy()
    bad = [0, 15, 16, 31, 32, 41]
    for b in bad:
        sg[b, 7] ^= 0x08
    m = vmod.VerifierModel(block_on_compile=True)
    ok = m.verify_rows_cached_templated(b"tpl-win", pks, idx, templates, ti, t8, sg)
    assert ok is not None and ok.shape == (n,)
    want = np.ones(n, dtype=bool)
    want[bad] = False
    np.testing.assert_array_equal(ok, want)

    # non-blocking with cold buckets: nothing dispatches, caller falls back
    m2 = vmod.VerifierModel(block_on_compile=False)
    assert (
        m2.verify_rows_cached_templated(b"tpl-win-2", pks, idx, templates, ti, t8, sg)
        is None
    )


def test_validator_set_verify_commit_uses_cached_tables():
    """End-to-end: ValidatorSet.verify_commit through a TPU provider must
    accept/reject identically to the CPU provider, and hit the cached
    path (table cache populated)."""
    from tendermint_tpu.crypto.batch import CPUBatchVerifier, TPUBatchVerifier
    from tendermint_tpu.state.state import state_from_genesis_doc
    from tests.cs_harness import make_genesis

    genesis, privs = make_genesis(6)
    st = state_from_genesis_doc(genesis)
    vals = st.validators
    from tendermint_tpu.codec.signbytes import PRECOMMIT_TYPE
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    bid = BlockID(hash=b"\x21" * 32, parts=PartSetHeader(total=2, hash=b"\x22" * 32))
    by_addr = {pv.address(): pv for pv in privs}
    ordered = [by_addr[v.address] for v in vals.validators]
    vs = VoteSet(genesis.chain_id, 3, 0, PRECOMMIT_TYPE, vals)
    for i, pv in enumerate(ordered):
        v = Vote(
            vote_type=PRECOMMIT_TYPE, height=3, round=0, block_id=bid,
            timestamp_ns=9000 + i, validator_address=pv.address(),
            validator_index=i,
        )
        v.signature = pv.priv_key.sign(v.sign_bytes(genesis.chain_id))
        assert vs.add_vote(v)
    commit = vs.make_commit()

    tpu = TPUBatchVerifier(block_on_compile=True, min_device_batch=2)
    vals.verify_commit(genesis.chain_id, bid, 3, commit, provider=tpu)  # no raise
    assert len(tpu.model.key_pool) == len(vals)  # cached path exercised
    cpu = CPUBatchVerifier()
    vals.verify_commit(genesis.chain_id, bid, 3, commit, provider=cpu)

    # corrupt one signature: both providers must reject identically
    # (on a deep copy — a verified commit is immutable, its memos vouch
    # for its bytes; the copy starts without them)
    import copy

    commit = copy.deepcopy(commit)
    bad = commit.signatures[2]
    bad.signature = bad.signature[:10] + bytes([bad.signature[10] ^ 1]) + bad.signature[11:]
    from tendermint_tpu.types.validator_set import ErrInvalidCommitSignature

    for prov in (tpu, cpu):
        with pytest.raises(ErrInvalidCommitSignature):
            vals.verify_commit(genesis.chain_id, bid, 3, commit, provider=prov)
