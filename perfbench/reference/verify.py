"""Serial verification of commits and of a sequential light chain, on
plain data, one ``cryptography`` ed25519 check per present signature.

Semantics (reference ``types/validator_set.go`` VerifyCommit and
``lite2/verifier.go`` VerifyAdjacent): signatures are walked in order;
the first invalid one BEFORE the tally passes 2/3 rejects the commit,
one after it does not; nil votes are verified and not tallied. The
deployment's stronger guarantee is stated on top: EVERY present row has
a verdict, also past the quorum point (``rows``), so a client can name
every bad signer. ``quorum_only=True`` drops exactly that guarantee (it
stops checking once the tally has passed 2/3, as the Go loop does) and
is the control the comparison has to fail.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from . import encoding as enc

CLOCK_DRIFT_NS = 10 * 10**9

ACCEPT = ("accept",)


class ValidatorKeys:
    """The validator set as the reference holds it: public keys in
    address order, powers, and a parsed key per validator."""

    def __init__(self, pubkeys: Sequence[bytes], powers: Sequence[int]):
        self.pubkeys = list(pubkeys)
        self.powers = list(powers)
        self.addresses = [enc.address(pk) for pk in self.pubkeys]
        self.total_power = sum(self.powers)
        self._parsed = [Ed25519PublicKey.from_public_bytes(pk) for pk in self.pubkeys]
        self._hash: Optional[bytes] = None

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = enc.validator_set_hash(self.pubkeys, self.powers)
        return self._hash

    def check(self, i: int, msg: bytes, sig: bytes) -> bool:
        if len(sig) != 64:
            return False
        try:
            self._parsed[i].verify(sig, msg)
        except InvalidSignature:
            return False
        return True


def commit_answer(
    vals: ValidatorKeys, chain_id: str, commit: dict, quorum_only: bool = False
) -> dict:
    """Verdict and per-present-row validity of one commit (``commit`` as
    the generator makes it)."""
    flags, stamps, sigs = commit["flags"], commit["timestamps"], commit["signatures"]
    if len(flags) != len(vals.pubkeys):
        return _answer(("invalid_commit", f"wrong set size: {len(vals.pubkeys)} vs {len(flags)}"))
    for_block = enc.vote_sign_bytes(
        chain_id, commit["height"], commit["round"], commit["block_hash"],
        commit["parts_total"], commit["parts_hash"], 0,
    )
    nil = enc.vote_sign_bytes(chain_id, commit["height"], commit["round"], b"", 0, b"", 0)
    need = vals.total_power * 2 // 3
    rows: List[bool] = []
    verdict = None
    walked = 0  # power tallied by the in-order walk, up to the quorum point
    for i, flag in enumerate(flags):
        if flag == enc.FLAG_ABSENT:
            continue
        past_quorum = walked > need
        if past_quorum and quorum_only:
            rows.append(True)  # the control: not looked at, taken as valid
            continue
        base = for_block if flag == enc.FLAG_COMMIT else nil
        msg = base[:93] + enc.i64(stamps[i]) + base[101:]
        ok = vals.check(i, msg, sigs[i])
        rows.append(ok)
        if past_quorum or verdict is not None:
            continue
        if not ok:
            verdict = ("invalid_signature", i, vals.addresses[i].hex())
        elif flag == enc.FLAG_COMMIT:
            walked += vals.powers[i]
    if verdict is None:
        verdict = ACCEPT if walked > need else ("not_enough_power", walked, need)
    return _answer(verdict, rows)


def _answer(verdict: Tuple, rows: Sequence[bool] = ()) -> dict:
    return {"verdict": verdict, "rows": np.asarray(rows, dtype=bool)}


def _commit_task(task) -> dict:
    validators, chain_id, commit, quorum_only = task
    return commit_answer(ValidatorKeys(**validators), chain_id, commit, quorum_only)


def commit_answers(
    validators: dict, chain_id: str, commits: Sequence[dict], quorum_only: bool = False,
    workers: int = 1,
) -> List[dict]:
    """``commit_answer`` for each commit, over ``workers`` processes: the
    rows are independent, and the run waits for this once its window has
    closed. The workers import this package and ``cryptography`` only."""
    tasks = [(validators, chain_id, c, quorum_only) for c in commits]
    if workers <= 1 or len(tasks) <= 1:
        return [_commit_task(t) for t in tasks]
    import multiprocessing

    with multiprocessing.get_context("spawn").Pool(min(workers, len(tasks))) as pool:
        out = pool.map(_commit_task, tasks)
        pool.close()
        pool.join()
    return out


def chain_answer(
    vals: ValidatorKeys, chain_id: str, chain: Sequence[dict], commits: Sequence[dict],
    trusting_period_ns: int, now_ns: int,
) -> dict:
    """Sequential (adjacent) verification of ``chain[1:]`` from the
    trusted ``chain[0]``, one validator set throughout. Each element is
    ``{"header": fields, "commit": commit}``; ``commits`` holds
    ``commit_answer`` of each link's commit. The verdict is the first
    failing link's; ``rows`` covers every present row of every link."""
    trusted = chain[0]["header"]
    if trusted["time_ns"] + trusting_period_ns <= now_ns:
        return _answer(("expired", trusted["height"]))
    prev = trusted
    for li, link in enumerate(chain[1:]):
        # host-side checks run over the whole chain before any signature is looked at
        err = _header_error(vals, chain_id, prev, link["header"], link["commit"], now_ns)
        if err is not None:
            return _answer(("invalid_header", li, err))
        prev = link["header"]
    verdict = next((a["verdict"] for a in commits if a["verdict"] != ACCEPT), ACCEPT)
    return _answer(verdict, np.concatenate([a["rows"] for a in commits]) if commits else ())


def _header_error(vals, chain_id, prev, h, c, now_ns) -> Optional[str]:
    if h["chain_id"] != chain_id:
        return "header belongs to another chain"
    if c["height"] != h["height"]:
        return "header and commit height mismatch"
    if c["block_hash"] != enc.header_hash(h):
        return "commit signs another block"
    if h["height"] != prev["height"] + 1:
        return "headers must be adjacent in height"
    if h["time_ns"] <= prev["time_ns"]:
        return "expected new header time after old header time"
    if h["time_ns"] >= now_ns + CLOCK_DRIFT_NS:
        return "new header time is from the future"
    if h["validators_hash"] != vals.hash():
        return "validators do not match those supplied"
    if h["validators_hash"] != prev["next_validators_hash"]:
        return "old header next validators do not match"
    return None
