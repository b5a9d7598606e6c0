"""A pool of signed batches of independent rows, every row under its own
key: a payments block's transfers as a node that never saw them in its
mempool verifies them (a DeliverBatch's SigCache misses), as plain data
from the seed.

A row is ``(pubkey, msg, sig)``: the sender's key, the transfer message
``PAY1 | sender (32) | nonce u64 | fee u64 | recipient (32) | amount u64``
(big-endian integers, 92 bytes; the payments app's layout, written out
here) and the sender's signature over it. Batch ``b`` of the pool holds
the same senders, each at nonce ``b + 1``.

A mix's file gives the parameters (``generator.params``):

    batches        batches in the pool (the same senders, consecutive nonces)
    window_rows    the rows of one device window: the witnesses go into
                   every run of this many rows and into the tail after them
    witnesses      the kinds of rejected row put into each window, one row
                   each at a seeded place:
                   "r_bit"          one bit of the signature's R flipped
                   "s_plus_l"       s + L in place of s (the same point,
                                    a scalar the check must refuse)
                   "key_no_root"    the key replaced by an encoding y < p
                                    whose x has no square root
                   "other_message"  another row's message under this
                                    row's key and signature

and the configuration's file the deployment (``rows_per_batch``,
``msg_len``, ``key_type``). Keys are derived from the seed and are
canonical: no row holds y >= p or a small-order key, where the
reference's semantics and ``cryptography``'s may differ. Signing uses
``cryptography``, in a process pool when serial signing would take more
than a few seconds; nothing of the program is imported.
"""

from __future__ import annotations

import hashlib
import os
import struct
from typing import List, Tuple

import numpy as np
from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

MAGIC = b"PAY1"
MSG_LEN = 4 + 32 + 8 + 8 + 32 + 8
P = 2**255 - 19
L = 2**252 + 27742317777372542331952422992315860723
D = (-121665 * pow(121666, P - 2, P)) % P
KINDS = ("r_bit", "s_plus_l", "key_no_root", "other_message")
SERIAL_ROWS = 4096  # above this many senders, sign in a process pool
_RAW = (serialization.Encoding.Raw, serialization.PublicFormat.Raw)


def generate(config: dict, params: dict, seed: int) -> dict:
    if config["key_type"] != "ed25519" or int(config["msg_len"]) != MSG_LEN:
        raise SystemExit(
            f"perfbench: independent_batch makes ed25519 keys and {MSG_LEN}-byte transfer messages, "
            f"the configuration states {config['key_type']!r} and {config['msg_len']}"
        )
    n, n_batches = int(config["rows_per_batch"]), int(params["batches"])
    window = int(params["window_rows"])
    kinds = list(params["witnesses"])
    if not set(kinds) <= set(KINDS) or any(hi - lo < len(kinds) for lo, hi in windows(n, window)):
        raise SystemExit(f"perfbench: witnesses {kinds} do not fit windows of {window} rows of {n}")
    rng = np.random.default_rng([int(seed), 0x62617463])
    recipients = rng.permutation(n)
    amounts = rng.integers(1, 10**6, size=n)
    fees = rng.integers(0, 1000, size=n)
    pubkeys, sigs = _sign_all(seed, n, n_batches, recipients, amounts, fees)
    batches = []
    for b in range(n_batches):
        msgs = _messages(pubkeys, recipients, amounts, fees, nonce=b + 1)
        pk, sg = pubkeys.copy(), sigs[b].copy()
        witnesses = _plant(rng, pk, msgs, sg, window, kinds)
        batches.append({"pubkeys": pk, "msgs": msgs, "sigs": sg, "witnesses": witnesses})
    return {"batches": batches, "rows": [n] * n_batches, "window_rows": window}


def windows(n: int, window: int) -> List[Tuple[int, int]]:
    """[start, end) of each full window and of the tail after them."""
    full = (n // window) * window
    return [(s, s + window) for s in range(0, full, window)] + ([(full, n)] if full < n else [])


def message(sender: bytes, nonce: int, fee: int, recipient: bytes, amount: int) -> bytes:
    return MAGIC + sender + struct.pack(">QQ", nonce, fee) + recipient + struct.pack(">Q", amount)


def _messages(pubkeys: np.ndarray, recipients, amounts, fees, nonce: int) -> np.ndarray:
    n = pubkeys.shape[0]
    msgs = np.zeros((n, MSG_LEN), dtype=np.uint8)
    msgs[:, :4] = np.frombuffer(MAGIC, dtype=np.uint8)
    msgs[:, 4:36] = pubkeys
    msgs[:, 36:44] = _be64(np.full(n, nonce, dtype=np.uint64))
    msgs[:, 44:52] = _be64(np.asarray(fees, dtype=np.uint64))
    msgs[:, 52:84] = pubkeys[recipients]
    msgs[:, 84:92] = _be64(np.asarray(amounts, dtype=np.uint64))
    return msgs


def _be64(x: np.ndarray) -> np.ndarray:
    return x.astype(">u8").view(np.uint8).reshape(-1, 8)


def _secret(seed: int, i: int) -> bytes:
    return hashlib.sha256(f"batchverify/{seed}/sender/{i}".encode()).digest()


def _sign_range(task) -> List[bytes]:
    """Each sender of [lo, hi) signs its message of every batch: the
    signatures concatenated, one bytes object a batch."""
    seed, lo, hi, n_batches, recipient_keys, amounts, fees = task
    keys = [Ed25519PrivateKey.from_private_bytes(_secret(seed, i)) for i in range(lo, hi)]
    pubs = [k.public_key().public_bytes(*_RAW) for k in keys]
    out = []
    for b in range(n_batches):
        out.append(b"".join(
            k.sign(message(pk, b + 1, int(f), rk, int(a)))
            for k, pk, rk, a, f in zip(keys, pubs, recipient_keys, amounts, fees)
        ))
    return out


def _pubkeys_range(task) -> bytes:
    seed, lo, hi = task
    return b"".join(
        Ed25519PrivateKey.from_private_bytes(_secret(seed, i)).public_key().public_bytes(*_RAW)
        for i in range(lo, hi)
    )


def _sign_all(seed: int, n: int, n_batches: int, recipients, amounts, fees):
    """(pubkeys (n, 32) u8, [sigs (n, 64) u8 a batch]): the keys first,
    since a message names its recipient's key, then the signatures."""
    import multiprocessing

    workers = 1 if n <= SERIAL_ROWS else max(1, min(8, os.cpu_count() or 1))
    cuts = np.linspace(0, n, 4 * workers + 1).astype(int)
    ranges = list(zip(cuts[:-1].tolist(), cuts[1:].tolist()))
    pool = multiprocessing.get_context("spawn").Pool(workers) if workers > 1 else None
    mapped = pool.map if pool is not None else (lambda f, tasks: [f(t) for t in tasks])
    try:
        pubs = b"".join(mapped(_pubkeys_range, [(seed, lo, hi) for lo, hi in ranges]))
        pk = np.frombuffer(pubs, dtype=np.uint8).reshape(n, 32).copy()
        parts = mapped(_sign_range, [
            (seed, lo, hi, n_batches, [pk[r].tobytes() for r in recipients[lo:hi]],
             amounts[lo:hi].tolist(), fees[lo:hi].tolist())
            for lo, hi in ranges
        ])
    finally:
        if pool is not None:
            pool.close()
            pool.join()
    sigs = [
        np.frombuffer(b"".join(p[b] for p in parts), dtype=np.uint8).reshape(n, 64).copy()
        for b in range(n_batches)
    ]
    return pk, sigs


def no_root_keys(count: int) -> List[bytes]:
    """Encodings of the ``count`` smallest y < p (x's sign bit clear)
    for which (y^2 - 1) / (d y^2 + 1) has no square root mod p: no point
    has them, so a check must reject any signature under them."""
    out, y = [], 2
    while len(out) < count:
        u, v = (y * y - 1) % P, (D * y * y + 1) % P
        x2 = u * pow(v, P - 2, P) % P
        if x2 and pow(x2, (P - 1) // 2, P) != 1:
            out.append(y.to_bytes(32, "little"))
        y += 1
    return out


def _plant(rng, pk: np.ndarray, msgs: np.ndarray, sigs: np.ndarray, window: int, kinds) -> List[list]:
    """Put one row of each kind into every window and the tail, in place;
    returns [[row, kind], ...]."""
    n = pk.shape[0]
    off_curve = no_root_keys(16)
    signed = msgs.copy()  # another row's message as it was signed, whatever was planted there
    planted = []
    for lo, hi in windows(n, window):
        rows = lo + rng.choice(hi - lo, size=len(kinds), replace=False)
        for row, kind in zip(rows.tolist(), kinds):
            if kind == "r_bit":
                sigs[row, int(rng.integers(0, 32))] ^= np.uint8(1 << int(rng.integers(0, 8)))
            elif kind == "s_plus_l":
                s = int.from_bytes(sigs[row, 32:].tobytes(), "little")
                sigs[row, 32:] = np.frombuffer((s + L).to_bytes(32, "little"), dtype=np.uint8)
            elif kind == "key_no_root":
                bad = bytearray(off_curve[int(rng.integers(0, len(off_curve)))])
                bad[31] |= 0x80 * int(rng.integers(0, 2))  # either sign of x
                pk[row] = np.frombuffer(bytes(bad), dtype=np.uint8)
            else:  # other_message
                other = (row + 1 + int(rng.integers(0, n - 1))) % n
                msgs[row] = signed[other]
            planted.append([row, kind])
    return planted
