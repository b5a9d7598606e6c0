"""A control for a chain whose validator set changes: the plain
reference put in the program's place, checking every height's commit
against the FIRST set — what a program does that keeps one table of keys
and misses that the set moved on. The headers still link (their hashes
are checked against the true sets); from the first height whose set
differs, rows land on another validator's key: ``row_mismatches`` and
the verdict have to read above their limits. Run through
``perfbench/control_sets.py``; the benchmark's own runs never load this
file.
"""

from __future__ import annotations

from perfbench.entries.verify_chain_sets import reference_answer
from perfbench.spans import PlainRecorder, RequestRecord


class Entry:
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        if mix["request_kind"] != "chain" or "sets" not in dataset:
            raise SystemExit("perfbench: control_first_set_only needs a chain with a set a height")
        self.data, self.period_ns = dataset, int(config["trusting_period_ns"])
        self.recorder = PlainRecorder()
        self.rows = sum(dataset["rows"][1:])
        self._answer = None

    def fresh_request(self, i: int):
        return 0, RequestRecord(i, 0, self.rows)

    def call(self, _k: int) -> None:
        stale = [self.data["sets"][0]] * len(self.data["sets"])
        self._answer = reference_answer(self.data, self.period_ns, stale)

    def answer(self, rec: RequestRecord) -> dict:
        return self._answer

    def reference_answers(self, quorum_only: bool = False, workers: int = 1) -> list:
        return [reference_answer(self.data, self.period_ns, self.data["sets"], quorum_only, workers)]

    def engine_stats(self) -> dict:
        return {}

    def close(self) -> None:
        pass
