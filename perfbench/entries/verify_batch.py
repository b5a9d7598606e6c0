"""Entry adapter: one request is one ``verify_batch`` of a whole batch of
independent ``(pubkey, msg, sig)`` rows on the process's default
provider — the node's stack (``ProviderStack``: the pipeline over the
device provider), as the payments app's ``deliver_batch`` sends its
SigCache misses through it. The rows of pool entry ``i`` modulo its size
are handed over as fresh array copies, made before the request's clock
starts; the answer is the verdict rows the call returned.
"""

from __future__ import annotations

import numpy as np

from tendermint_tpu.crypto.batch import get_default_provider

from perfbench.entries import program_objects as po
from perfbench.entries.provider_stack import StackEntry
from perfbench.reference import batch as ref
from perfbench.spans import RequestRecord


class Entry(StackEntry):
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        super().__init__(config, annotate)
        self.pool = dataset["batches"]
        self._rows = {}  # request index -> the verdicts verify_batch returned

    def fresh_request(self, i: int):
        k = i % len(self.pool)
        b = self.pool[k]
        rows = (b["pubkeys"].copy(), b["msgs"].copy(), b["sigs"].copy())
        return (i, rows), RequestRecord(i, k, len(b["pubkeys"]))

    def call(self, request) -> None:
        i, (pubkeys, msgs, sigs) = request
        self._rows[i] = np.asarray(get_default_provider().verify_batch(pubkeys, msgs, sigs), dtype=bool)

    def answer(self, rec: RequestRecord) -> dict:
        return {"verdict": po.verdict(rec.outcome), "rows": self._rows.pop(rec.index, np.zeros(0, dtype=bool))}

    def reference_answers(self, workers: int = 1) -> list:
        """The reference's answer for each batch of the pool."""
        return [ref.batch_answer(b["pubkeys"], b["msgs"], b["sigs"], workers) for b in self.pool]
