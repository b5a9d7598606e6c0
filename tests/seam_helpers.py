"""A recording stub provider for the overlapped verify seam
(crypto/batch.RowGroups): it takes a spec list's rows ``per`` commits at
a time as a device provider would (take a group, "dispatch" it, take the
next), verifies them on the host, and keeps the order of events and the
seam's column counter at each of them — counts and orders, no clocks.
"""

from __future__ import annotations

import numpy as np

from tendermint_tpu.codec.signbytes import splice_timestamps
from tendermint_tpu.crypto.batch import (
    SEAM_COUNTS,
    BatchVerifier,
    CPUBatchVerifier,
    RowCounts,
    RowGroups,
)


def seam_counts() -> dict:
    return SEAM_COUNTS.snapshot()


def seam_grew(before: dict) -> dict:
    after = seam_counts()
    return {k[len("seam_"):]: after[k] - before[k] for k in after}


class GroupStub(BatchVerifier):
    """``group_keys``: the GroupKeys each group named before it was
    taken. ``events``: ("take", k, rows or None, column_rows so far)
    when group k has been taken, ("launch", k) when its rows are on their
    way, ("arrays", n) / ("rows", n) / ("batch", n) for the eager
    calls. ``decline_at=k`` answers None once group k is taken, as a
    provider whose launch k failed. Like the model, it counts device
    rows only for a call it answered; host rows are the generic
    path's."""

    name = "group-stub"
    takes_row_groups = True

    def __init__(self, per: int, decline_at=None):
        self.per, self.decline_at = per, decline_at
        self.events: list = []
        self.group_keys: list = []
        self.row_counts = RowCounts()
        self._host = CPUBatchVerifier()
        self._columns0 = seam_counts()["seam_column_rows"]

    def of(self, kind: str) -> list:
        return [e for e in self.events if e[0] == kind]

    def verify_rows_cached_templated(
        self, valset_key, all_pubkeys, row_idx, templates=None, tmpl_idx=None,
        ts8=None, sigs=None,
    ):
        if not isinstance(row_idx, RowGroups):
            self.events.append(("arrays", len(row_idx)))
            return None
        outs, k = [], 0
        while row_idx.left:
            keys = row_idx.keys(self.per)  # the group's own keys, asked before it is taken
            if keys is not None:
                all_pubkeys = keys.pubkeys
                self.group_keys.append(keys)
            got = row_idx.take(self.per)
            columns = seam_counts()["seam_column_rows"] - self._columns0
            self.events.append(("take", k, None if got is None else len(got[0]), columns))
            if got is None or k == self.decline_at:
                return None
            idx, tpl, ti, t8, sg = got
            outs.append(
                self._host.verify_batch(
                    np.asarray(all_pubkeys)[idx], splice_timestamps(tpl[ti], t8), sg
                )
            )
            self.events.append(("launch", k))
            k += 1
        ok = np.concatenate(outs) if outs else np.zeros(0, dtype=bool)
        self.row_counts.add(device=len(ok))
        return ok

    def verify_rows_cached(self, valset_key, all_pubkeys, row_idx, msgs, sigs):
        self.events.append(("rows", len(row_idx)))
        return None

    def verify_batch(self, pubkeys, msgs, sigs, msg_lens=None):
        self.events.append(("batch", len(pubkeys)))
        self.row_counts.add(host=len(pubkeys))
        return self._host.verify_batch(pubkeys, msgs, sigs, msg_lens=msg_lens)
