"""Plain data -> the program's own objects, and the program's verdicts ->
the reference's vocabulary. The only benchmark code that knows the
program's types; both entry adapters share it."""

from __future__ import annotations

import re
from typing import Optional, Tuple

from tendermint_tpu.crypto.keys import Ed25519PubKey
from tendermint_tpu.types.block import BlockID, Commit, CommitSig, Header, PartSetHeader
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import (
    ErrInvalidCommit,
    ErrInvalidCommitSignature,
    ErrNotEnoughVotingPower,
    ValidatorSet,
)

from perfbench.reference import encoding as enc

_WRONG_SIG = re.compile(r"^wrong signature #(\d+) \(([0-9a-f]{40})\)$")
_POWER = re.compile(r"^have (\d+), need > (\d+)$")


def validator_set(validators: dict) -> ValidatorSet:
    vals = ValidatorSet(
        [Validator(Ed25519PubKey(pk), pw) for pk, pw in zip(validators["pubkeys"], validators["powers"])]
    )
    if [v.pub_key.bytes() for v in vals.validators] != list(validators["pubkeys"]):
        raise RuntimeError("the program orders the validator set otherwise than the generator")
    return vals


def block_id(commit: dict) -> BlockID:
    return BlockID(commit["block_hash"], PartSetHeader(commit["parts_total"], commit["parts_hash"]))


def decoded_commit(vals: ValidatorSet, commit: dict) -> Commit:
    """The commit as a node holds it fresh off the wire: built, encoded,
    decoded. Its CommitSig list is what each request's fresh ``Commit``
    is made over (``fresh_commit``)."""
    sigs = [
        CommitSig.absent() if f == enc.FLAG_ABSENT
        else CommitSig(f, vals.validators[i].address, t, s)
        for i, (f, t, s) in enumerate(zip(commit["flags"], commit["timestamps"], commit["signatures"]))
    ]
    return Commit.decode(Commit(commit["height"], commit["round"], block_id(commit), sigs).encode())


def fresh_commit(c: Commit) -> Commit:
    """A ``Commit`` object that no code has seen: none of the memos a
    verified commit carries (``_parts_cache``, ``_vb_cache``, row keys,
    hash, encoding). The CommitSig objects are shared — they hold no
    memo, and decoding 10,000 of them takes as long as verifying them."""
    return Commit(c.height, c.round, c.block_id, c.signatures)


def header(h: dict) -> Header:
    last = h["last_block_id"]
    return Header(
        chain_id=h["chain_id"], height=h["height"], time_ns=h["time_ns"],
        last_block_id=BlockID(last[0], PartSetHeader(last[1], last[2])) if last else BlockID(),
        validators_hash=h["validators_hash"], next_validators_hash=h["next_validators_hash"],
        consensus_hash=h["consensus_hash"], app_hash=h["app_hash"],
        proposer_address=h["proposer_address"],
        version_block=h["version_block"], version_app=h["version_app"],
    )


def verdict(outcome: Optional[BaseException]) -> Tuple:
    """The program's outcome in the reference's words; what cannot be
    read is kept as it is and so matches nothing."""
    if outcome is None:
        return ("accept",)
    text = str(outcome)
    if isinstance(outcome, ErrInvalidCommitSignature):
        m = _WRONG_SIG.match(text)
        if m:
            return ("invalid_signature", int(m.group(1)), m.group(2))
    elif isinstance(outcome, ErrNotEnoughVotingPower):
        m = _POWER.match(text)
        if m:
            return ("not_enough_power", int(m.group(1)), int(m.group(2)))
    elif isinstance(outcome, ErrInvalidCommit):
        return ("invalid_commit", text)
    return ("unread", type(outcome).__name__, text)
