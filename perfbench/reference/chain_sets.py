"""Serial verification of a sequential light chain whose validator set
changes from height to height, on plain data: ``verify.chain_answer``
with a set a chain element.

Semantics (reference ``lite2/verifier.go`` VerifyAdjacent): every header
after the trusted one is checked against the one before it — chain id,
adjacent height, later time, not from the future, inside the trusting
period — and links the sets: its ``validators_hash`` is the hash of the
set it is handed (the reference's own merkle hash of that set,
``encoding.validator_set_hash``) and equals the previous header's
``next_validators_hash`` — ``verify``'s link checks, a set a link. Each
commit is then walked serially against THAT height's set, one
``cryptography`` check a present row (``verify.commit_answer``).
Imports nothing of the program.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import List, Sequence

import numpy as np

from . import verify as ref

ACCEPT = ref.ACCEPT


def commit_answers(
    sets: Sequence[dict], chain_id: str, commits: Sequence[dict], quorum_only: bool = False,
    workers: int = 1,
) -> List[dict]:
    """``verify.commit_answer`` of each commit against the set beside it
    (``sets[k]`` for ``commits[k]``), over ``workers`` processes."""
    tasks = [(s, chain_id, c, quorum_only) for s, c in zip(sets, commits)]
    if workers <= 1 or len(tasks) <= 1:
        return [ref._commit_task(t) for t in tasks]
    import multiprocessing

    with multiprocessing.get_context("spawn").Pool(min(workers, len(tasks))) as pool:
        out = pool.map(ref._commit_task, tasks)
        pool.close()
        pool.join()
    return out


def chain_answer(
    sets: Sequence[dict], chain_id: str, chain: Sequence[dict], commits: Sequence[dict],
    trusting_period_ns: int, now_ns: int,
) -> dict:
    """Sequential (adjacent) verification of ``chain[1:]`` from the
    trusted ``chain[0]``; ``sets[k]`` is the set handed over with
    ``chain[k]`` and ``commits`` holds ``commit_answer`` of each link's
    commit against its own set. The verdict is the first failing link's;
    ``rows`` covers every present row of every link."""
    trusted = chain[0]["header"]
    if trusted["time_ns"] + trusting_period_ns <= now_ns:
        return ref._answer(("expired", trusted["height"]))
    prev = trusted
    for li, (link, s) in enumerate(zip(chain[1:], sets[1:])):
        # host-side checks run over the whole chain before any signature is looked at
        handed = SimpleNamespace(hash=lambda s=s: ref.enc.validator_set_hash(s["pubkeys"], s["powers"]))
        err = ref._header_error(handed, chain_id, prev, link["header"], link["commit"], now_ns)
        if err is not None:
            return ref._answer(("invalid_header", li, err))
        prev = link["header"]
    verdict = next((a["verdict"] for a in commits if a["verdict"] != ACCEPT), ACCEPT)
    return ref._answer(verdict, np.concatenate([a["rows"] for a in commits]) if commits else ())
