"""Entry adapter: one request is one ``ValidatorSet.verify_commit`` through
the default provider, on a commit of the pool (``i`` modulo its size)."""

from __future__ import annotations

from perfbench.entries import program_objects as po
from perfbench.entries.provider_stack import StackEntry
from perfbench.reference import verify as ref
from perfbench.spans import RequestRecord


class Entry(StackEntry):
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        super().__init__(config, annotate)
        self.chain_id = dataset["chain_id"]
        self.data = dataset
        self.vals = po.validator_set(dataset["validators"])
        self.pool = [po.decoded_commit(self.vals, el["commit"]) for el in dataset["chain"]]
        self.rows = dataset["rows"]

    def fresh_request(self, i: int):
        k = i % len(self.pool)
        commit = po.fresh_commit(self.pool[k])
        return commit, RequestRecord(i, k, self.rows[k])

    def call(self, commit) -> None:
        if hasattr(commit, "_parts_cache"):
            raise RuntimeError("a request was handed a commit that has been verified before")
        self.vals.verify_commit(self.chain_id, commit.block_id, commit.height, commit)

    def answer(self, rec: RequestRecord) -> dict:
        return {"verdict": po.verdict(rec.outcome), "rows": self.rows_of(rec)}

    def reference_answers(self, quorum_only: bool = False, workers: int = 1) -> list:
        """The reference's answer for each commit of the pool."""
        return ref.commit_answers(
            self.data["validators"], self.chain_id,
            [el["commit"] for el in self.data["chain"]], quorum_only, workers,
        )
