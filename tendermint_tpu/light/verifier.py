"""Light-client header verification.

Reference: lite2/verifier.go — VerifyAdjacent :96 (hash-chain +
untrusted VerifyCommit), VerifyNonAdjacent :32 (trusted
VerifyCommitTrusting at 1/3 :60 + untrusted VerifyCommit :76), Verify
dispatch :140, VerifyBackwards :228; common checks
(verifyNewHeaderAndVals :167): basic validation, height/time
monotonicity, clock drift, trusting period.

Every commit check drains through the shared device-backed core
(lightserve/core.py — ★ the BASELINE config-3 hot path: headers ×
heights). The host-side checks + spec construction for one trust link
live in :func:`link_specs` so the lightserve aggregator can verify the
SAME link semantics while batching the device work across many
concurrent clients (docs/light-service.md).
"""

from __future__ import annotations

import time
from fractions import Fraction
from typing import List, Optional, Tuple

from tendermint_tpu.light.types import DEFAULT_TRUST_LEVEL, SignedHeader
from tendermint_tpu.lightserve import core
from tendermint_tpu.types.validator_set import CommitVerifySpec, ValidatorSet
from tendermint_tpu.utils.trace import span

DEFAULT_CLOCK_DRIFT_NS = 10 * 10**9  # 10s (reference defaultClockDrift)


class VerificationError(Exception):
    pass


class ErrOldHeaderExpired(VerificationError):
    pass


class ErrNewValSetCantBeTrusted(VerificationError):
    """Non-adjacent trust check failed — bisection should pivot."""


class ErrInvalidHeader(VerificationError):
    pass


def _now_ns(now_ns: Optional[int]) -> int:
    return time.time_ns() if now_ns is None else now_ns


def header_expired(h: SignedHeader, trusting_period_ns: int, now_ns: int) -> bool:
    """Reference HeaderExpired lite2/verifier.go:186."""
    return h.time_ns + trusting_period_ns <= now_ns


def _verify_new_header_and_vals(
    chain_id: str,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted: SignedHeader,
    now_ns: int,
    clock_drift_ns: int,
) -> None:
    """Reference verifyNewHeaderAndVals :167."""
    try:
        core.ensure_basic(chain_id, untrusted)
    except core.ErrBadHeader as e:
        raise ErrInvalidHeader(str(e)) from None
    if untrusted.height <= trusted.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted.height} > trusted {trusted.height}"
        )
    if untrusted.time_ns <= trusted.time_ns:
        raise ErrInvalidHeader(
            "expected new header time after old header time"
        )
    if untrusted.time_ns >= now_ns + clock_drift_ns:
        raise ErrInvalidHeader("new header time is from the future")
    try:
        core.ensure_valset_matches(untrusted, untrusted_vals)
    except core.ErrValsetMismatch:
        raise ErrInvalidHeader(
            "expected new header validators to match those supplied"
        ) from None


def link_specs(
    chain_id: str,
    trusted: SignedHeader,
    trusted_vals: Optional[ValidatorSet],
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    now_ns: Optional[int] = None,
    clock_drift_ns: int = DEFAULT_CLOCK_DRIFT_NS,
) -> List[Tuple[str, CommitVerifySpec]]:
    """Host-side checks for ONE trust link trusted→untrusted, returning
    the commit specs the device must confirm: ``[("full", spec)]`` for
    an adjacent link (after the hash-chain check), ``[("trusting",
    spec), ("full", spec)]`` for a skip link. Host failures raise here;
    a "trusting" spec failing on the device means the link needs a
    bisection pivot (:class:`ErrNewValSetCantBeTrusted`), which
    :func:`_raise_link` maps. This is the seam the lightserve
    aggregator shares with :func:`verify`, so a batched fleet request
    accepts/rejects bit-identically to a direct serial call."""
    now = _now_ns(now_ns)
    if header_expired(trusted, trusting_period_ns, now):
        raise ErrOldHeaderExpired(
            f"old header expired at {trusted.time_ns + trusting_period_ns}"
        )
    _verify_new_header_and_vals(
        chain_id, untrusted, untrusted_vals, trusted, now, clock_drift_ns
    )
    if untrusted.height == trusted.height + 1:
        # the hash-chain link: H+1 validators were committed to by H
        if untrusted.header.validators_hash != trusted.header.next_validators_hash:
            raise ErrInvalidHeader(
                f"expected old header next validators "
                f"({trusted.header.next_validators_hash.hex()[:12]}) to match "
                f"those from new header "
                f"({untrusted.header.validators_hash.hex()[:12]})"
            )
        return [("full", core.full_spec(untrusted_vals, chain_id, untrusted))]
    # Both checks (1/3+ of the trusted set still signs; the new set has
    # a proper +2/3 commit) share ONE device batch. The reference runs
    # them serially (VerifyCommitTrusting :60 then VerifyCommit :76);
    # the trusting error still surfaces first, so observable behavior
    # matches.
    if trusted_vals is None:
        raise ValueError("non-adjacent link requires the trusted valset")
    return [
        ("trusting", core.trusting_spec(trusted_vals, chain_id, untrusted, trust_level)),
        ("full", core.full_spec(untrusted_vals, chain_id, untrusted)),
    ]


def _raise_link(kind: str, err: Exception, prefix: str = "") -> None:
    if kind == "trusting":
        raise ErrNewValSetCantBeTrusted(f"{prefix}{err}")
    raise err


def verify_adjacent(
    chain_id: str,
    trusted: SignedHeader,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: Optional[int] = None,
    clock_drift_ns: int = DEFAULT_CLOCK_DRIFT_NS,
    provider=None,
) -> None:
    """Reference VerifyAdjacent :96 — untrusted.height == trusted.height+1."""
    if untrusted.height != trusted.height + 1:
        raise ValueError("headers must be adjacent in height")
    specs = link_specs(
        chain_id, trusted, None, untrusted, untrusted_vals,
        trusting_period_ns, now_ns=now_ns, clock_drift_ns=clock_drift_ns,
    )
    # ★ one batched device call
    core.verify_one(specs[0][1], provider=provider)


def verify_non_adjacent(
    chain_id: str,
    trusted: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    now_ns: Optional[int] = None,
    clock_drift_ns: int = DEFAULT_CLOCK_DRIFT_NS,
    provider=None,
) -> None:
    """Reference VerifyNonAdjacent :32."""
    if untrusted.height == trusted.height + 1:
        raise ValueError("headers must be non-adjacent in height")
    specs = link_specs(
        chain_id, trusted, trusted_vals, untrusted, untrusted_vals,
        trusting_period_ns, trust_level, now_ns, clock_drift_ns,
    )
    res = core.verify_specs([s for _, s in specs], provider=provider)
    for (kind, _), err in zip(specs, res):
        if err is not None:
            _raise_link(kind, err)


def verify(
    chain_id: str,
    trusted: SignedHeader,
    trusted_vals: ValidatorSet,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    now_ns: Optional[int] = None,
    clock_drift_ns: int = DEFAULT_CLOCK_DRIFT_NS,
    provider=None,
) -> None:
    """Reference Verify :140: dispatch on adjacency."""
    if untrusted.height != trusted.height + 1:
        verify_non_adjacent(
            chain_id, trusted, trusted_vals, untrusted, untrusted_vals,
            trusting_period_ns, trust_level, now_ns, clock_drift_ns, provider,
        )
    else:
        verify_adjacent(
            chain_id, trusted, untrusted, untrusted_vals, trusting_period_ns,
            now_ns, clock_drift_ns, provider,
        )


def verify_chain(
    chain_id: str,
    trusted: SignedHeader,
    trusted_vals: ValidatorSet,
    chain,  # List[Tuple[SignedHeader, ValidatorSet]], ascending heights
    trusting_period_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
    now_ns: Optional[int] = None,
    clock_drift_ns: int = DEFAULT_CLOCK_DRIFT_NS,
    provider=None,
) -> None:
    """Verify a whole chain of headers with ONE batched device call.

    The reference verifies one header per step (sequence lite2/client.go:620,
    bisection :687 — one VerifyCommit[Trusting] call each). Here every
    link's signature checks (adjacent → 1 commit; non-adjacent → trusting +
    full, 2 commits) pack into a single rectangular batch — the SURVEY §5.7
    "headers × heights" axis (BASELINE config 3). Host-side hash-chain and
    header checks run sequentially first; the per-link accept/reject replay
    preserves the step-by-step semantics, so the first failing link raises
    exactly what the per-step path would have raised.

    One device call is one provider call, its launches fed as they are
    packed: a chain of adjacent headers under one validator set reaches a
    provider that takes row groups (crypto/batch.RowGroups) a launch's
    worth of commits at a time, so every commit after the first group is
    read into columns and packed while the device runs the launch before
    it (types/validator_set.verify_commits_batched). Should the provider
    decline at any point, the packing is finished and the whole chain
    goes down the generic path, each row verified once; the verdicts and
    the exception raised are the same either way.
    """
    now = _now_ns(now_ns)
    specs: List[CommitVerifySpec] = []
    spec_links: List[Tuple[int, str]] = []  # (link_idx, kind) parallel to specs
    cur_sh, cur_vals = trusted, trusted_vals
    with span("verify.links"):
        for li, (sh, vals) in enumerate(chain):
            try:
                link = link_specs(
                    chain_id, cur_sh, cur_vals, sh, vals,
                    trusting_period_ns, trust_level, now, clock_drift_ns,
                )
            except ErrInvalidHeader as e:
                raise ErrInvalidHeader(f"link {li}: {e}") from None
            for kind, s in link:
                specs.append(s)
                spec_links.append((li, kind))
            cur_sh, cur_vals = sh, vals

    results = core.verify_specs(specs, provider=provider)  # ★ one device call
    for (li, kind), err in zip(spec_links, results):
        if err is not None:
            _raise_link(kind, err, prefix=f"link {li}: " if kind == "trusting" else "")


def verify_backwards(chain_id: str, untrusted: SignedHeader, trusted: SignedHeader) -> None:
    """Reference VerifyBackwards :228: hash-chain only, no signatures —
    untrusted is EARLIER than trusted and must be its ancestor."""
    try:
        core.ensure_basic(chain_id, untrusted)
    except core.ErrBadHeader as e:
        raise ErrInvalidHeader(str(e)) from None
    if untrusted.height != trusted.height - 1:
        raise ValueError("headers must be adjacent (backwards)")
    if untrusted.time_ns >= trusted.time_ns:
        raise ErrInvalidHeader("expected older header time to be before newer")
    if trusted.header.last_block_id.hash != untrusted.hash():
        raise ErrInvalidHeader(
            f"trusted header's LastBlockID {trusted.header.last_block_id.hash.hex()[:12]} "
            f"does not match older header's hash {untrusted.hash().hex()[:12]}"
        )
