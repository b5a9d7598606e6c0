"""Entry adapter: one request is one ``light.verifier.verify_chain`` over
every header after the trusted first one, each handed over with THAT
height's validator set (``generators/signed_chain_sets``).

As ``verify_chain``: the device provider is handed to ``verify_chain``
directly and no pipeline is built, so the whole chain is one
``verify_commits_batched`` call. The ``ValidatorSet`` objects are made
once and kept across requests, as ``light-1k`` keeps its one (a client
that receives every set anew also hashes it: the deployment's file says
so under ``assumed``); headers and commits are fresh objects a request.
``engine_stats`` carries the program's process-wide counters (the seam's,
the cached-table path's, the key pool's) under ``counters``, where the
deployment's ``window_limits`` and the per-layer readers look; a program
that lacks one of them leaves it out.
"""

from __future__ import annotations

from tendermint_tpu.light import verifier as light
from tendermint_tpu.light.types import SignedHeader

from perfbench.entries import program_objects as po
from perfbench.entries.provider_stack import StackEntry
from perfbench.reference import chain_sets as ref
from perfbench.spans import RequestRecord

COUNTS = ("SEAM_COUNTS", "TABLED_COUNTS", "TABLE_COUNTS")


class Entry(StackEntry):
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        super().__init__(config, annotate, pipelined=False)
        self.chain_id = dataset["chain_id"]
        self.data = dataset
        self.vals = [po.validator_set(s) for s in dataset["sets"]]
        self.headers = [po.header(el["header"]) for el in dataset["chain"]]
        self.commits = [po.decoded_commit(v, el["commit"]) for v, el in zip(self.vals, dataset["chain"])]
        self.period_ns = int(config["trusting_period_ns"])
        self.now_ns = dataset["now_ns"]
        self.rows = sum(dataset["rows"][1:])  # the trusted header's commit is not verified

    def fresh_request(self, i: int):
        chain = [SignedHeader(h, po.fresh_commit(c)) for h, c in zip(self.headers, self.commits)]
        return chain, RequestRecord(i, 0, self.rows)

    def call(self, chain) -> None:
        light.verify_chain(
            self.chain_id, chain[0], self.vals[0], list(zip(chain[1:], self.vals[1:])),
            self.period_ns, now_ns=self.now_ns, provider=self.recorder,
        )

    def answer(self, rec: RequestRecord) -> dict:
        return {"verdict": po.verdict(rec.outcome), "rows": self.rows_of(rec)}

    def reference_answers(self, quorum_only: bool = False, workers: int = 1) -> list:
        """One pool entry: the whole chain's answer."""
        return [reference_answer(self.data, self.period_ns, self.data["sets"], quorum_only, workers)]

    def engine_stats(self) -> dict:
        from tendermint_tpu.crypto import batch

        stats = super().engine_stats()
        counters = dict(stats.get("counters") or {})
        for name in COUNTS:
            counts = getattr(batch, name, None)
            if counts is not None:
                counters.update(counts.snapshot())
        return {**stats, "counters": counters}

    def setup_report(self) -> dict:
        """The stack's report, and what the warm-ups cost the key pool:
        keys built, build dispatches, seconds (the price of a validator
        a live client has not seen)."""
        report = super().setup_report()
        pool = getattr(getattr(self.recorder.inner, "model", None), "key_pool", None)
        if pool is not None:
            report["key_pool"] = {
                "keys": len(pool), "build_dispatches": pool.dispatches, "build_s": round(pool.build_s, 3),
            }
        return report


def reference_answer(data: dict, period_ns: int, sets, quorum_only: bool = False, workers: int = 1) -> dict:
    """The chain's answer by the plain reference, each commit checked
    against the set of ``sets`` beside it."""
    commits = ref.commit_answers(
        sets[1:], data["chain_id"], [el["commit"] for el in data["chain"][1:]], quorum_only, workers,
    )
    return ref.chain_answer(data["sets"], data["chain_id"], data["chain"], commits, period_ns, data["now_ns"])
