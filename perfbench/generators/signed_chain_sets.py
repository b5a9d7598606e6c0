"""``signed_chain`` with a validator set that changes at every height:
a hash-linked chain of signed headers, each commit signed by ITS
height's set, as plain data, from the seed.

The deployment's file gives the churn (``valset_change_per_height`` c):
validators are made in a fixed order from the seed; the set of chain
element j holds the ``validators`` consecutive ones from c * j on — at
every height the c oldest keys leave and c fresh ones join (reference
lite2 ``GenMockNode(.., valVariation)``: ``ChangeKeys`` drops the first
keys and extends by as many), the size stays — held in address order, so
a key that joins shifts the index of every validator after it. Header j
commits to its own set's hash and to the next one's; commit j is signed
positionally by set j. The mix's parameters are ``signed_chain``'s own
(absent and nil shares, tampered rows), dealt the same way, so the two
light cells differ in the sets alone. Out: ``signed_chain``'s dataset
with ``sets`` beside it — one ``{"pubkeys", "powers"}`` a chain element
(``validators`` is the first, for code that knows one set).
"""

from __future__ import annotations

import hashlib
from typing import List

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from perfbench.generators.signed_chain import GENESIS_NS, _RAW, _mark_commits, _tamper_row
from perfbench.reference import encoding as enc


def generate(config: dict, params: dict, seed: int) -> dict:
    if config["key_type"] != "ed25519" or int(config["sign_bytes_len"]) != enc.SIGN_BYTES_LEN:
        raise SystemExit(
            f"perfbench: signed_chain_sets makes ed25519 keys and {enc.SIGN_BYTES_LEN}-byte sign bytes, "
            f"the configuration states {config['key_type']!r} and {config['sign_bytes_len']}"
        )
    if params.get("quorum_edge"):
        raise SystemExit("perfbench: signed_chain_sets deals no quorum_edge commits")
    rng = np.random.default_rng([int(seed), 0x70657266])
    n, power = int(config["validators"]), int(config["voting_power"])
    churn = int(config["valset_change_per_height"])
    chain_id = f"{config['chain_id']}-{seed}"
    trusted = int(params.get("trusted_headers", 0))
    heights = int(params.get("heights") or config["heights"] + trusted)

    made = []  # (address, pubkey, private key), in the order validators join
    for i in range(n + churn * heights):  # one set past the chain: the last header names its successor's hash
        sk = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(f"{chain_id}/validator/{i}".encode()).digest()
        )
        pk = sk.public_key().public_bytes(*_RAW)
        made.append((enc.address(pk), pk, sk))
    sets = [sorted(made[churn * j : churn * j + n], key=lambda k: k[0]) for j in range(heights + 1)]
    powers = [power] * n
    hashes = [enc.validator_set_hash([k[1] for k in s], powers) for s in sets]

    lo, hi = params["absent_share"]
    spaced = np.rint(np.linspace(lo, hi, heights - trusted) * n).astype(int)
    order = rng.permutation(heights - trusted)
    absent_counts = np.concatenate([np.full(trusted, spaced[0]), spaced[order]])
    marks = _mark_commits(rng, order, trusted, params)
    block_ns = int(config["block_time_ns"])

    chain: List[dict] = []
    last = None
    for j in range(heights):
        keys = sets[j]
        header = {
            "version_block": 10, "version_app": 0, "chain_id": chain_id,
            "height": j + 1, "time_ns": GENESIS_NS + (j + 1) * block_ns,
            "last_block_id": last, "validators_hash": hashes[j],
            "next_validators_hash": hashes[j + 1], "consensus_hash": b"\x01" * 32,
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
    plain = [{"pubkeys": [k[1] for k in s], "powers": list(powers)} for s in sets[:heights]]
    return {
        "chain_id": chain_id,
        "validators": plain[0],
        "sets": plain,
        "chain": chain,
        "now_ns": GENESIS_NS + (heights + 1) * block_ns,
        "rows": [int(n - c) for c in absent_counts],
    }
