"""The slot-order planner (models/verifier.plan_slots) alone: pure
numpy, no device. Which batches go to their validators' slots, where
each row lands, how runs are dealt to launches — and which batches stay
with the gathered pair (the K rule, _SLOT_GATHER_RATIO)."""

import numpy as np
import pytest

from tendermint_tpu.models import verifier as vmod
from tendermint_tpu.models.verifier import plan_slots


def _commit(v, absent=()):
    return np.setdiff1d(np.arange(v), np.asarray(absent, dtype=np.int64))


def _check(plan, idx, v):
    """A plan's own invariants: every row in its run's commit at its
    validator's slot, slots unique, launches cover the rows in order
    and hold whole commits."""
    idx = np.asarray(idx)
    assert plan.slots.shape == idx.shape
    np.testing.assert_array_equal(plan.slots % v, idx)
    assert len(np.unique(plan.slots)) == len(idx)
    assert plan.launches[0][0] == 0 and plan.launches[-1][1] == len(idx)
    base = 0
    for (lo, hi, c), nxt in zip(plan.launches, plan.launches[1:] + ((len(idx), 0, 0),)):
        assert hi == nxt[0] and lo < hi
        assert c >= 1 and c & (c - 1) == 0  # a power of two
        at = plan.slots[lo:hi] - base
        assert at.min() >= 0 and at.max() < c * v
        base += c * v


@pytest.mark.parametrize(
    "name,idx,v,launches",
    [
        ("full commit", np.arange(16), 16, ((0, 16, 1),)),
        ("full set below its pad", np.arange(12), 16, ((0, 12, 1),)),
        ("hole at slot 0", _commit(16, [0]), 16, ((0, 15, 1),)),
        ("hole at slot V-1", _commit(16, [15]), 16, ((0, 15, 1),)),
        ("holes at both ends and inside", _commit(256, [0, 7, 100, 255]), 256, ((0, 252, 1),)),
        ("one row of a 16-slot set", np.array([5]), 16, ((0, 1, 1),)),
        ("commit cell: 9,450 of 10,000", _commit(10000, range(0, 10000, 18)), 10240, None),
        (
            "three commits of different sizes, last launch rounded up to 4",
            np.concatenate([_commit(16, [3]), _commit(16, [0, 15]), _commit(16)]),
            16, ((0, 45, 4),),
        ),
    ],
)
def test_slot_order_taken(name, idx, v, launches):
    plan = plan_slots(idx, v)
    assert plan is not None, name
    _check(plan, idx, v)
    if launches is not None:
        assert plan.launches == launches
    else:
        assert plan.launches == ((0, len(idx), 1),)
        np.testing.assert_array_equal(plan.slots, idx)  # one commit: slot = validator


@pytest.mark.parametrize(
    "name,idx,v",
    [
        ("empty", np.zeros(0, dtype=np.int32), 16),
        ("duplicates: every row its own run", np.array([3, 3, 11, 0, 7, 15]), 16),
        ("descending, as a trusting lookup by address may be", np.arange(15, -1, -1), 16),
        ("a sparse vote batch of a large set", np.arange(0, 10000, 100), 10240),
        ("in order but few: 10 of 64", np.arange(10), 64),
        ("an index beyond the table", np.array([0, 1, 16]), 16),
        ("a negative index", np.array([-1, 2, 3]), 16),
    ],
)
def test_gathered_kept(name, idx, v):
    assert plan_slots(idx, v) is None, name


def test_runs_are_dealt_to_launches_and_the_last_is_rounded_up(monkeypatch):
    """The light cell's shape in small: 16-slot commits, 4 to a launch;
    11 commits -> two full launches and a last of 3 rounded up to 4."""
    monkeypatch.setattr(vmod, "MAX_DEVICE_ROWS", 64)
    rng = np.random.default_rng(7)
    commits = [np.sort(rng.choice(16, size=15, replace=False)) for _ in range(11)]
    idx = np.concatenate(commits)
    plan = plan_slots(idx, 16)
    _check(plan, idx, 16)
    assert plan.launches == ((0, 60, 4), (60, 120, 4), (120, 165, 4))
    # row r of commit k sits at k*16 + its validator
    k = np.repeat(np.arange(11), 15)
    np.testing.assert_array_equal(plan.slots, k * 16 + idx)
    # 9 commits: the last launch holds 1, not rounded past it
    plan = plan_slots(idx[: 9 * 15], 16)
    assert [c for _, _, c in plan.launches] == [4, 4, 1]
    # 10 commits: 2 stays 2
    assert [c for _, _, c in plan_slots(idx[: 10 * 15], 16).launches] == [4, 4, 2]


def test_light_cell_shape():
    """127 commits of a 1,000-validator set, 945 present: 8 launches of
    16 commits, 131,072 slots against 124,928 gathered rows (+4.9%)."""
    rng = np.random.default_rng(11)
    idx = np.concatenate(
        [np.sort(rng.choice(1000, size=945, replace=False)) for _ in range(127)]
    )
    plan = plan_slots(idx, 1024)
    _check(plan, idx, 1024)
    assert [c for _, _, c in plan.launches] == [16] * 8
    assert vmod._gathered_rows(len(idx)) == 7 * 16384 + 10240


def test_k_rule_both_sides(monkeypatch):
    """One run of a 64-slot set launches 64 slots; the gathered pair
    would launch the rows' bucket. 46 rows pad to 64: slot order
    (64 <= 1.5 * 64). 12 rows pad to 16: gathered (64 > 1.5 * 16).
    Moving K moves both."""
    assert vmod._SLOT_GATHER_RATIO == 1.5
    assert plan_slots(np.arange(46), 64) is not None
    assert plan_slots(np.arange(12), 64) is None
    monkeypatch.setattr(vmod, "_SLOT_GATHER_RATIO", 0.99)
    assert plan_slots(np.arange(46), 64) is None  # 64 slots > 0.99 * 64 rows
    monkeypatch.setattr(vmod, "_SLOT_GATHER_RATIO", 4.0)
    assert plan_slots(np.arange(12), 64) is not None  # 64 <= 4 * 16
