"""The control: the plain reference put in the program's place, with one
of the deployment's guarantees broken — it stops checking signatures once
the tally has passed 2/3, as the Go loop does and as a later PR would be
tempted to (a third less device work). Verdicts stay right; a bad
signature past the quorum point goes unseen, so ``row_mismatches`` has to
read above its limit. Run through ``perfbench/control.py``; the
benchmark's own runs never load this file.
"""

from __future__ import annotations

from perfbench.reference import verify as ref
from perfbench.spans import PlainRecorder, RequestRecord


class Entry:
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        self.data, self.config = dataset, config
        self.chain = {"chain": True, "commit": False}[mix["request_kind"]]  # the mix says what one request is
        self.recorder = PlainRecorder()
        self.rows = dataset["rows"]
        self._answers = {}

    def fresh_request(self, i: int):
        if self.chain:
            return 0, RequestRecord(i, 0, sum(self.rows[1:]))
        k = i % len(self.rows)
        return k, RequestRecord(i, k, self.rows[k])

    def call(self, k: int) -> None:
        self._answers[k] = self._answer(k, quorum_only=True)

    def _answer(self, k: int, quorum_only: bool, workers: int = 1) -> dict:
        d = self.data
        commits = [el["commit"] for el in d["chain"]]
        if not self.chain:
            return ref.commit_answers(d["validators"], d["chain_id"], [commits[k]], quorum_only)[0]
        answers = ref.commit_answers(d["validators"], d["chain_id"], commits[1:], quorum_only, workers)
        return ref.chain_answer(
            ref.ValidatorKeys(**d["validators"]), d["chain_id"], d["chain"], answers,
            int(self.config["trusting_period_ns"]), d["now_ns"],
        )

    def answer(self, rec: RequestRecord) -> dict:
        return self._answers[rec.pool_index]

    def reference_answers(self, quorum_only: bool = False, workers: int = 1) -> list:
        pool = [0] if self.chain else range(len(self.rows))
        return [self._answer(k, quorum_only, workers) for k in pool]

    def engine_stats(self) -> dict:
        return {}

    def close(self) -> None:
        pass
