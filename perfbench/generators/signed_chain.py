"""The one traffic generator: a validator set and a hash-linked chain of
signed headers, as plain data, from the seed.

A mix's file gives the parameters (``generator.params``):

    heights        commits made (consecutive heights, one header each);
                   left out, the configuration's ``heights`` plus
                   ``trusted_headers`` (a chain starts from a trusted one)
    absent_share   [lo, hi]: the commits' absent shares are EVENLY spaced
                   over it and dealt to the heights in seeded order, so
                   every seed carries the same number of signature rows
                   (a trusted header's commit, which no request verifies,
                   always takes the lowest share)
    nil_share      share of each commit's signers that precommit nil
    tampered       [{"where": "before_quorum" | "after_quorum"}, ...]:
                   one signature with a flipped bit each, each in another
                   commit: the one of a fixed rank by absent share (so
                   every seed carries the same commits), the row and the
                   bit drawn from the seed;
                   with ``"every": k`` one such commit in EACH run of k
                   consecutive verified heights, so that no stretch of
                   2k - 1 commits (and so no window, and neither half,
                   of a batch over the chain) is without a forged row
    quorum_edge    [{"for_block": "just_over" | "just_under"}, ...]: one
                   commit each (of a fixed rank too, none of them
                   tampered) in which signers
                   are moved to nil until the for-block power is the
                   least that passes 2/3 of the total, or the most that
                   does not; the number of signature rows stays

and the configuration's file the deployment (``validators``,
``voting_power``, ``chain_id``, ``block_time_ns``). Signing uses
``cryptography`` over the reference's own sign bytes; nothing of the
program is imported, so what the program is handed was made without it.
"""

from __future__ import annotations

import hashlib
from typing import List

import numpy as np
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from perfbench.reference import encoding as enc

GENESIS_NS = 1_700_000_000_000_000_000
_RAW = (serialization.Encoding.Raw, serialization.PublicFormat.Raw)


def generate(config: dict, params: dict, seed: int) -> dict:
    # the deployment's shapes are the reference encoders' own: anything else is another benchmark
    if config["key_type"] != "ed25519" or int(config["sign_bytes_len"]) != enc.SIGN_BYTES_LEN:
        raise SystemExit(
            f"perfbench: signed_chain makes ed25519 keys and {enc.SIGN_BYTES_LEN}-byte sign bytes, "
            f"the configuration states {config['key_type']!r} and {config['sign_bytes_len']}"
        )
    rng = np.random.default_rng([int(seed), 0x70657266])
    n, power = int(config["validators"]), int(config["voting_power"])
    chain_id = f"{config['chain_id']}-{seed}"
    keys = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(f"{chain_id}/validator/{i}".encode()).digest()
        )
        pk = sk.public_key().public_bytes(*_RAW)
        keys.append((enc.address(pk), pk, sk))
    keys.sort(key=lambda k: k[0])  # a validator set is held in address order
    pubkeys = [k[1] for k in keys]
    powers = [power] * n
    vhash = enc.validator_set_hash(pubkeys, powers)

    trusted = int(params.get("trusted_headers", 0))
    heights = int(params.get("heights") or config["heights"] + trusted)
    lo, hi = params["absent_share"]
    spaced = np.rint(np.linspace(lo, hi, heights - trusted) * n).astype(int)
    order = rng.permutation(heights - trusted)
    absent_counts = np.concatenate([np.full(trusted, spaced[0]), spaced[order]])
    marks = _mark_commits(rng, order, trusted, params)
    block_ns = int(config["block_time_ns"])
    need = n * power * 2 // 3  # a commit passes with MORE for-block power than this

    chain: List[dict] = []
    last = None
    for j in range(heights):
        header = {
            "version_block": 10, "version_app": 0, "chain_id": chain_id,
            "height": j + 1, "time_ns": GENESIS_NS + (j + 1) * block_ns,
            "last_block_id": last, "validators_hash": vhash,
            "next_validators_hash": vhash, "consensus_hash": b"\x01" * 32,
            "app_hash": b"", "proposer_address": keys[j % n][0],
        }
        block_hash = enc.header_hash(header)
        parts_hash = enc.sha256(block_hash)
        last = (block_hash, 1, parts_hash)
        flags = np.full(n, enc.FLAG_COMMIT, dtype=np.uint8)
        absent = rng.choice(n, size=int(absent_counts[j]), replace=False)
        flags[absent] = enc.FLAG_ABSENT
        present = np.flatnonzero(flags != enc.FLAG_ABSENT)
        nil = rng.choice(present, size=int(round(params["nil_share"] * present.size)), replace=False)
        flags[nil] = enc.FLAG_NIL
        edge = marks.get(j, {}).get("for_block")
        if edge:
            keep = need // power + (1 if edge == "just_over" else 0)
            signers = np.flatnonzero(flags == enc.FLAG_COMMIT)
            flags[rng.choice(signers, size=signers.size - keep, replace=False)] = enc.FLAG_NIL
        # every validator stamps its own precommit, within the block's second
        stamps = header["time_ns"] + rng.integers(0, block_ns, size=n)
        for_block = enc.vote_sign_bytes(chain_id, j + 1, 0, block_hash, 1, parts_hash, 0)
        for_nil = enc.vote_sign_bytes(chain_id, j + 1, 0, b"", 0, b"", 0)
        sigs = [b""] * n
        for i in present:
            base = for_block if flags[i] == enc.FLAG_COMMIT else for_nil
            sigs[i] = keys[i][2].sign(base[:93] + enc.i64(int(stamps[i])) + base[101:])
        where = marks.get(j, {}).get("where")
        if where:
            i = _tamper_row(rng, flags, n, where)
            bad = bytearray(sigs[i])
            bad[int(rng.integers(0, 64))] ^= 1 << int(rng.integers(0, 8))
            sigs[i] = bytes(bad)
        chain.append({
            "header": header,
            "commit": {
                "height": j + 1, "round": 0, "block_hash": block_hash,
                "parts_total": 1, "parts_hash": parts_hash,
                "flags": flags.tolist(), "timestamps": stamps.tolist(), "signatures": sigs,
            },
        })
    return {
        "chain_id": chain_id,
        "validators": {"pubkeys": pubkeys, "powers": powers},
        "chain": chain,
        "now_ns": GENESIS_NS + (heights + 1) * block_ns,
        "rows": [int(n - c) for c in absent_counts],
    }


def _mark_commits(rng, order, trusted: int, params: dict) -> dict:
    """Which commits are tampered with or moved to the quorum's edge:
    {commit index: the mix's entry}, no commit marked twice, never a
    trusted header's. ``order[p]`` is the rank, by absent share, of the
    commit at verified position ``p``. An entry without ``every`` goes to
    a FIXED rank, evenly spread over the ranks, so that every seed makes
    the same commits (size and kind) in another order; one with ``every``
    goes to a seeded position in each run of that many."""
    marks: dict = {}
    n = len(order)
    fixed = [t for t in params.get("tampered", ()) if not t.get("every")] + list(params.get("quorum_edge", ()))
    position_of = np.argsort(order)
    for i, entry in enumerate(fixed):
        marks[trusted + int(position_of[int((i + 0.5) * n / len(fixed))])] = entry
    for t in params.get("tampered", ()):
        k = int(t.get("every", 0))
        for lo in range(0, n, k) if k else ():
            run = [trusted + p for p in range(lo, min(lo + k, n)) if trusted + p not in marks]
            if run:
                marks[int(rng.choice(run))] = t
    return marks


def _tamper_row(rng, flags, n: int, where: str) -> int:
    """A for-block signer well before, or well after, the point at which
    the in-order walk passes 2/3 (equal powers, at most a tenth absent:
    the walk passes it between 0.667n and 0.75n)."""
    lo, hi = {"before_quorum": (0, n // 2), "after_quorum": (n - n // 8, n)}[where]
    rows = np.flatnonzero(flags[lo:hi] == enc.FLAG_COMMIT) + lo
    return int(rng.choice(rows))
