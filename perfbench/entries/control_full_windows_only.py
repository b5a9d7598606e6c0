"""A control for a bare batch that streams as windows: the plain
reference put in the program's place, checking the full windows of the
batch and taking every row of the tail after them as valid — what a
program does that streams the windows and drops, or never reads back,
the tail's launch. The generator puts four rejected rows into the tail
as into every window, so ``row_mismatches`` has to read four a batch
and ``correct`` false. Run through ``perfbench/control_batch.py``; the
benchmark's own runs never load this file.
"""

from __future__ import annotations

import os

from perfbench.generators.independent_batch import windows
from perfbench.reference import batch as ref
from perfbench.spans import PlainRecorder, RequestRecord


class Entry:
    def __init__(self, dataset: dict, config: dict, mix: dict, annotate: bool = False):
        if mix["request_kind"] != "batch":
            raise SystemExit("perfbench: control_full_windows_only needs a bare batch")
        self.pool, self.window = dataset["batches"], int(dataset["window_rows"])
        self.recorder = PlainRecorder()
        self.workers = min(8, os.cpu_count() or 1)
        self._answers = {}  # pool index -> the control's last answer for it

    def fresh_request(self, i: int):
        k = i % len(self.pool)
        return k, RequestRecord(i, k, len(self.pool[k]["pubkeys"]))

    def call(self, k: int) -> None:
        b = self.pool[k]
        got = ref.batch_answer(b["pubkeys"], b["msgs"], b["sigs"], self.workers)
        tail = windows(len(b["pubkeys"]), self.window)[-1]
        if tail[1] - tail[0] < self.window:
            got["rows"][tail[0]:] = True  # the tail taken as valid, unread
        self._answers[k] = got

    def answer(self, rec: RequestRecord) -> dict:
        return self._answers[rec.pool_index]

    def reference_answers(self, workers: int = 1) -> list:
        return [ref.batch_answer(b["pubkeys"], b["msgs"], b["sigs"], workers) for b in self.pool]

    def engine_stats(self) -> dict:
        return {}

    def close(self) -> None:
        pass
