"""Entry adapter: one request is one ``light.verifier.verify_chain`` over
every header after the trusted first one.

The device provider is handed to ``verify_chain`` directly (as
``chip_smoke.py`` does), NOT picked up as the node's default, pipelined
one, and no pipeline is built. Through the pipeline
``lightserve/core.verify_specs`` submits the specs one by one, and its
dispatch thread cuts them into bundles wherever it happens to wake: the
bundle sizes, and with them the padded shapes the model compiles, then
depend on thread timing, and a shape never seen before compiles inside
the window. With the provider passed the whole chain is one
``verify_commits_batched`` call. ``verify_chain_pipelined`` is the
node's path, for a deployment's file to name once the program bundles
steadily; PERF.md has the chip reading under Open questions."""

from __future__ import annotations

from tendermint_tpu.light import verifier as light
from tendermint_tpu.light.types import SignedHeader

from perfbench.entries import program_objects as po
from perfbench.entries.provider_stack import StackEntry
from perfbench.reference import verify as ref
from perfbench.spans import RequestRecord


class Entry(StackEntry):
    PIPELINED = False

    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        super().__init__(config, annotate, pipelined=self.PIPELINED)
        self.chain_id = dataset["chain_id"]
        self.data = dataset
        self.vals = po.validator_set(dataset["validators"])
        self.headers = [po.header(el["header"]) for el in dataset["chain"]]
        self.commits = [po.decoded_commit(self.vals, el["commit"]) for el in dataset["chain"]]
        self.period_ns = int(config["trusting_period_ns"])
        self.now_ns = dataset["now_ns"]
        self.rows = sum(dataset["rows"][1:])  # the trusted header's commit is not verified

    def fresh_request(self, i: int):
        chain = [SignedHeader(h, po.fresh_commit(c)) for h, c in zip(self.headers, self.commits)]
        return chain, RequestRecord(i, 0, self.rows)

    def call(self, chain) -> None:
        light.verify_chain(
            self.chain_id, chain[0], self.vals, [(sh, self.vals) for sh in chain[1:]],
            self.period_ns, now_ns=self.now_ns, provider=None if self.PIPELINED else self.recorder,
        )

    def answer(self, rec: RequestRecord) -> dict:
        return {"verdict": po.verdict(rec.outcome), "rows": self.rows_of(rec)}

    def reference_answers(self, quorum_only: bool = False, workers: int = 1) -> list:
        """One pool entry: the whole chain's answer."""
        commits = ref.commit_answers(
            self.data["validators"], self.chain_id,
            [el["commit"] for el in self.data["chain"][1:]], quorum_only, workers,
        )
        return [
            ref.chain_answer(
                ref.ValidatorKeys(**self.data["validators"]), self.chain_id,
                self.data["chain"], commits, self.period_ns, self.now_ns,
            )
        ]
