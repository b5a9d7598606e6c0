"""``correct`` has to come out false when it should.

The control — the reference in the program's place, checking signatures
only up to the quorum point — runs through ``control.py`` on both cells'
shapes. The faults break the timed path underneath a full run of the
harness (everything but its look for a chip): an answer altered where it
is produced, either half of the batch left out of the verification, one
window of eight left out. Each is an entry adapter written into the
copy, as a later PR would add one, and each is read on three seeds.
"""

import pytest

FAULTY_ENTRY = '''
import numpy as np
from perfbench.entries import {base} as base

class _Broken:
    """The device provider with one thing wrong underneath the recorder."""
    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
    def __getattr__(self, name):
        return getattr(self.inner, name)
    def _break(self, ok):
        if ok is None:
            return None
        ok = np.array(ok, dtype=bool)
        {fault}
        return ok
    def verify_batch(self, *a, **kw):
        return self._break(self.inner.verify_batch(*a, **kw))
    def verify_rows_cached(self, *a, **kw):
        return self._break(self.inner.verify_rows_cached(*a, **kw))
    def verify_rows_cached_templated(self, *a, **kw):
        return self._break(self.inner.verify_rows_cached_templated(*a, **kw))

class Entry(base.Entry):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.recorder.inner = _Broken(self.recorder.inner)
'''

FAULTS = {
    # a verdict altered where it is produced
    "flip_row": "ok[len(ok) // 3] = not ok[len(ok) // 3]",
    # half of the batch left out: its rows are reported valid unseen
    "skip_first_half": "ok[:len(ok) // 2] = True",
    "skip_second_half": "ok[len(ok) // 2:] = True",
    # one window of a batch that is verified in eight left out
    "skip_first_window": "ok[:len(ok) // 8] = True",
    "skip_fourth_window": "ok[3 * len(ok) // 8:4 * len(ok) // 8] = True",
    "skip_last_window": "ok[7 * len(ok) // 8:] = True",
}
# the chain at the shipped depth (its rows are a tenth of a window's at 16 validators, in the same
# proportions: a window is 16 commits here, 17 in the cell); the commit stream as shipped
CELLS = {
    "commit": ("verify_commit", "commit-stream-partial", {}),
    "chain": ("verify_chain", "seq-chain-128", {"heights": 128, "trusting_period_ns": 10800000000000}),
}
CASES = [("commit", f) for f in ("flip_row", "skip_first_half", "skip_second_half")] + [
    ("chain", f) for f in sorted(FAULTS)
]
SEEDS = [2**31 + 77, 3, 990001]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell, fault", CASES)
def test_a_fault_under_the_timed_path_reads_not_correct(bench_copy, cell, fault, seed):
    b = bench_copy
    base, traffic, sizes = CELLS[cell]
    b.write(f"entries/{base}_{fault}.py", FAULTY_ENTRY.format(base=base, fault=FAULTS[fault]))
    b.add_config("faulty", {**b.tiny, "chain_id": "pb-faulty", "entry": f"{base}_{fault}", **sizes})
    b.add_cell("faulty-cell", "faulty", traffic)
    rc, line, err = b.run("faulty-cell", seed=seed, seconds=0.3)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["check"]["row_mismatches"]["value"] > 0, line["check"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_same_cells_unbroken_read_correct(bench_copy, cell, seed):
    base, traffic, sizes = CELLS[cell]
    bench_copy.add_config("sound", {**bench_copy.tiny, "chain_id": "pb-faulty", "entry": base, **sizes})
    bench_copy.add_cell("sound-cell", "sound", traffic)
    rc, line, err = bench_copy.run("sound-cell", seed=seed, seconds=0.3)
    assert rc == 0, err
    assert line["correct"] is True, line["check"]


def test_a_row_served_on_the_host_reads_not_correct(bench_copy):
    """The deployment's ``window_limits``: the CPU provider serves every
    row on the host, which a deployment that states ``host_rows: 0`` fails."""
    bench_copy.add_config("host-served", {**bench_copy.tiny, "chain_id": "pb-host", "entry": "verify_commit",
                                          "window_limits": {"host_rows": 0, "compiles_in_window": 0}})
    bench_copy.add_cell("host-cell", "host-served", "commit-stream-partial")
    rc, line, err = bench_copy.run("host-cell", seed=11, seconds=0.3)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["check"]["host_rows"]["value"] > 0 and line["check"]["row_mismatches"]["value"] == 0
    assert line["check"]["compiles_in_window"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("cell", ["tiny-commit", "tiny-chain"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5, 4242])
def test_the_control_reads_not_correct(bench_copy, cell, seed):
    rc, line, err = bench_copy.run(cell, seed=seed, seconds=0.3, script="control.py")
    assert rc == 0, err  # control.py exits 0 only when the comparison caught it
    assert line["correct"] is False and line["control"] == "control_quorum_only"
    assert line["check"]["row_mismatches"]["value"] > 0
    assert line["check"]["verdict_mismatches"]["value"] == 0  # it is the rows' number that catches it
