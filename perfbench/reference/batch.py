"""Serial verification of a bare batch of ``(pubkey, msg, sig)`` rows, one
``cryptography`` ed25519 check a row, on plain data.

Semantics (reference ``crypto/ed25519/ed25519.go`` Verify, lines 151-157):
a row is valid iff its signature is 64 bytes and the cofactorless check
with ``s < L`` accepts it under its key; a key that ``cryptography``
cannot load is a rejected row. No row's verdict depends on another's:
the batch's verdict is always accept, and its answer is the row verdicts
in order.
"""

from __future__ import annotations

from typing import List

import numpy as np
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

ACCEPT = ("accept",)


def check(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64:
        return False
    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def _rows_task(task) -> np.ndarray:
    pubkeys, msgs, sigs = task
    return np.fromiter(
        (check(pk.tobytes(), mg.tobytes(), sg.tobytes()) for pk, mg, sg in zip(pubkeys, msgs, sigs)),
        dtype=bool, count=len(pubkeys),
    )


def batch_answer(pubkeys: np.ndarray, msgs: np.ndarray, sigs: np.ndarray, workers: int = 1) -> dict:
    """The verdict and every row's validity, over ``workers`` processes
    (the rows are independent, and a run waits for this once its window
    has closed). The workers import this package and ``cryptography`` only."""
    n = len(pubkeys)
    if workers <= 1 or n < 1024:
        return {"verdict": ACCEPT, "rows": _rows_task((pubkeys, msgs, sigs))}
    import multiprocessing

    cuts = np.linspace(0, n, 4 * workers + 1).astype(int)
    tasks = [(pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]) for lo, hi in zip(cuts[:-1], cuts[1:])]
    with multiprocessing.get_context("spawn").Pool(workers) as pool:
        parts: List[np.ndarray] = pool.map(_rows_task, tasks)
        pool.close()
        pool.join()
    return {"verdict": ACCEPT, "rows": np.concatenate(parts)}
