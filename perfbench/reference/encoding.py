"""Wire encodings the reference needs: canonical sign bytes, the
length-prefixed binary primitives, the RFC-6962-style merkle root, and
the header and validator-set hashes built from them.

Written from the documented layouts (160-byte canonical vote, 14-field
header), not copied from the program: the generator signs THESE bytes
and links THESE hashes, and the program recomputes both with its own
code, so a disagreement in either direction rejects every row.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Sequence

PRECOMMIT_TYPE = 2
SIGN_BYTES_LEN = 160
FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3
PUBKEY_TYPE = "ed25519"
_ZERO32 = b"\x00" * 32


def sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def address(pubkey: bytes) -> bytes:
    """A validator's address: the first 20 bytes of sha256(pubkey)."""
    return sha256(pubkey)[:20]


def chain_id_commitment(chain_id: str) -> bytes:
    raw = chain_id.encode("utf-8")
    return raw.ljust(32, b"\x00") if len(raw) <= 32 else sha256(raw)


def vote_sign_bytes(
    chain_id: str, height: int, round_: int, block_hash: bytes,
    parts_total: int, parts_hash: bytes, timestamp_ns: int,
) -> bytes:
    """The fixed 160-byte canonical precommit. A nil vote passes empty
    hashes and a zero part count."""
    body = struct.pack(
        ">BQqq32sI32sq32s",
        PRECOMMIT_TYPE, height, round_, -1,
        block_hash or _ZERO32, parts_total, parts_hash or _ZERO32,
        timestamp_ns, chain_id_commitment(chain_id),
    )
    return body.ljust(SIGN_BYTES_LEN, b"\x00")


def uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def lp(data: bytes) -> bytes:
    """Length-prefixed bytes."""
    return uvarint(len(data)) + data


def u64(n: int) -> bytes:
    return struct.pack(">Q", n)


def i64(n: int) -> bytes:
    return struct.pack(">q", n)


def merkle_root(items: Sequence[bytes]) -> bytes:
    """Leaf = sha256(0x00 || item), inner = sha256(0x01 || l || r), split
    at the largest power of two strictly below n."""
    n = len(items)
    if n == 0:
        return sha256(b"")
    if n == 1:
        return sha256(b"\x00" + items[0])
    k = 1
    while k * 2 < n:
        k *= 2
    return sha256(b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:]))


def parts_header_bytes(total: int, parts_hash: bytes) -> bytes:
    return struct.pack(">I", total) + lp(parts_hash)


def block_id_bytes(block_hash: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    return lp(block_hash) + lp(parts_header_bytes(parts_total, parts_hash))


def header_hash(h: dict) -> bytes:
    """Merkle root of the header's 14 field encodings. ``h`` holds the
    fields by name; ``last_block_id`` is (hash, parts_total, parts_hash)
    or None for the first block."""
    last = h.get("last_block_id")
    last_bytes = block_id_bytes(*last) if last else block_id_bytes(b"", 0, b"")
    return merkle_root([
        u64(h.get("version_block", 10)) + u64(h.get("version_app", 0)),
        h["chain_id"].encode("utf-8"),
        u64(h["height"]),
        i64(h["time_ns"]),
        last_bytes,
        h.get("last_commit_hash", b""),
        h.get("data_hash", b""),
        h["validators_hash"],
        h["next_validators_hash"],
        h.get("consensus_hash", b""),
        h.get("app_hash", b""),
        h.get("last_results_hash", b""),
        h.get("evidence_hash", b""),
        h.get("proposer_address", b""),
    ])


def validator_set_hash(pubkeys: Sequence[bytes], powers: Sequence[int]) -> bytes:
    """Merkle root over (typed pubkey, power), in address order."""
    return merkle_root([
        lp(lp(PUBKEY_TYPE.encode()) + lp(pk)) + i64(pw)
        for pk, pw in zip(pubkeys, powers)
    ])
