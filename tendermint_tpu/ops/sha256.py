"""Batched SHA-256 kernels for the device merkle and tx-key engines.

One compression (``_compress``) serves every kernel here and every
backend: the 64 rounds are a ROLLED ``lax.fori_loop`` over the state as
one (8, N) uint32 array and a sliding 16-word schedule window as one
(16, N) array. Rolled because XLA:CPU (jax 0.9.0) does not finish
executing the statically unrolled 64-round graph — tier-1 runs on that
backend, and a kernel no test can execute is not guarded at all.

Hash state travels between dispatches as that stacked (8, N) array —
big-endian words, exactly the digest — and bytes are only materialized
host-side (state_to_digests). Inner-node messages are built in WORD
space (merkle_inner): an inner node hashes 0x01 || left || right (65
bytes, 2 blocks) and both children arrive as (8, half) word columns, so
block one's words are shifts/ors of child words — no byte round-trip.

Used by models/hasher.py for block data hashes, tx roots, part-set
roots, validator-set hashes and evidence hashes above the
merkle_device_threshold (crypto/merkle.py), and by ingest/hashing.py
for mempool tx keys.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32

_K = [
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
    0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
    0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
    0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
    0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
    0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
    0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
    0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
    0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
    0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
    0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
]

_H0 = [
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
]

def _ror(x, n: int):
    return (x >> n) | (x << (32 - n))


def _compress(state, w):
    """One block: state (8, N) u32, w (16, N) u32 message words ->
    (8, N). Round t reads w[0] and slides the window on by the schedule
    word of round t + 16 (the last 16 are computed and never read)."""
    k = jnp.asarray(_K, dtype=U32)

    def round_(t, carry):
        (a, b, c, d, e, f, g, h), w = carry
        s1 = _ror(e, 6) ^ _ror(e, 11) ^ _ror(e, 25)
        ch = g ^ (e & (f ^ g))
        t1 = h + s1 + ch + k[t] + w[0]
        s0 = _ror(a, 2) ^ _ror(a, 13) ^ _ror(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        x1, x14 = w[1], w[14]
        nxt = (
            w[0]
            + (_ror(x1, 7) ^ _ror(x1, 18) ^ (x1 >> 3))
            + w[9]
            + (_ror(x14, 17) ^ _ror(x14, 19) ^ (x14 >> 10))
        )
        return (
            jnp.stack([t1 + s0 + maj, a, b, c, d + t1, e, f, g]),
            jnp.concatenate([w[1:], nxt[None]], axis=0),
        )

    out, _ = jax.lax.fori_loop(0, 64, round_, (state, w))
    return state + out


def _initial_state(n: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(_H0, dtype=U32)[:, None], (8, n))


def _words_from_bytes(blk):
    """(N, 64) u8 byte values -> (16, N) u32 big-endian words."""
    b = blk.astype(U32).reshape(blk.shape[0], 16, 4)
    w = (b[:, :, 0] << 24) | (b[:, :, 1] << 16) | (b[:, :, 2] << 8) | b[:, :, 3]
    return w.T


# -- leaf hashing -----------------------------------------------------------


def leaf_block_state(blk: jnp.ndarray) -> jnp.ndarray:
    """First (or only) message block of every leaf: (N, 64) u8 pre-padded
    block bytes -> (8, N) u32 state. Rows are independent leaves; the
    block must already carry the 0x00 leaf prefix and, for single-block
    leaves, the 0x80 terminator + bit length (models/hasher.py packs)."""
    return _compress(_initial_state(blk.shape[0]), _words_from_bytes(blk))


def leaf_block_update(state: jnp.ndarray, blk: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Fold one more block into multi-block leaves: state (8, N) u32,
    blk (N, 64) u8, active (N,) bool (False rows — leaves already fully
    consumed — keep their state)."""
    return jnp.where(active, _compress(state, _words_from_bytes(blk)), state)


# -- inner levels -----------------------------------------------------------


def merkle_inner(level: jnp.ndarray, m) -> jnp.ndarray:
    """Hash all sibling pairs of a level and build the next one.

    level (8, C) u32 word columns; m () int32 — the level's LOGICAL node
    count (<= C; columns past it are padding junk). Output
    (8, ceil(C/2)): column i is the pair hash when 2i+1 < m, the
    PROMOTED left child when 2i == m-1 (odd count, reference
    getSplitPoint recursion — the lone node rides up unchanged), junk
    otherwise.

    Inner node = sha256(0x01 || left(32) || right(32)): 65 bytes, two
    blocks. Block one is bytes 0..63 (prefix, left, right[0:31]); block
    two is right[31] || 0x80 || zeros || len(520 bits)."""
    half = level.shape[1] // 2
    left = level[:, 0 : 2 * half : 2]
    right = level[:, 1 : 2 * half : 2]
    lr = jnp.concatenate([left, right], axis=0)  # (16, half): left || right
    prev = jnp.concatenate([jnp.full((1, half), 0x01, dtype=U32), lr[:-1]], axis=0)
    first = (prev << 24) | (lr >> 8)  # the same bytes behind the 0x01 prefix
    tail = jnp.zeros((16, half), dtype=U32)
    tail = tail.at[0].set((right[7] << 24) | jnp.uint32(0x00800000))
    tail = tail.at[15].set(65 * 8)  # bit length of the 65-byte message
    pair = _compress(_compress(_initial_state(half), first), tail)
    has_right = (2 * jnp.arange(half, dtype=jnp.int32) + 1) < m
    out = jnp.where(has_right, pair, left)
    if level.shape[1] % 2:
        # odd STATIC width: the last column can only pair with padding,
        # so it is carried; when the logical count is smaller and odd,
        # the promoted node lives inside the pairs region and the
        # has_right select above already carried it.
        out = jnp.concatenate([out, level[:, -1:]], axis=1)
    return out


# -- host-side helpers ------------------------------------------------------


def state_to_digests(state: np.ndarray) -> np.ndarray:
    """(8, N) u32 state words -> (N, 32) u8 big-endian digests (pure
    numpy; digests only materialize host-side by design)."""
    st = np.asarray(state, dtype=np.uint32)
    return (
        st.byteswap()
        .view(np.uint8)
        .reshape(8, st.shape[1], 4)
        .transpose(1, 0, 2)
        .reshape(st.shape[1], 32)
    )


def digests_to_state(digests: np.ndarray) -> np.ndarray:
    """(N, 32) u8 -> (8, N) u32 big-endian words (inverse of
    state_to_digests; used to feed host-computed levels back)."""
    d = np.ascontiguousarray(np.asarray(digests, dtype=np.uint8))
    return (
        d.reshape(d.shape[0], 8, 4)
        .transpose(1, 0, 2)
        .reshape(8, d.shape[0] * 4)
        .view(np.uint32)
        .byteswap()
        .reshape(8, d.shape[0])
    )


def pack_leaf_blocks(
    items: Sequence[bytes], n_pad: int, n_blocks: int, prefix_len: int = 1
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack leaves into fully padded SHA-256 message blocks, host-side
    and vectorized: (n_pad, n_blocks, 64) u8 blocks + (n_pad,) int32
    per-row block counts. Each row is ``prefix_len`` ZERO prefix bytes
    || leaf || 0x80 || zeros || 64-bit big-endian bit length — the
    kernel never touches padding logic. The default prefix_len=1 is the
    merkle 0x00 leaf prefix (zero content, so it never needs writing);
    prefix_len=0 packs plain sha256 messages (the ingest tx-key engine,
    ingest/hashing.py). Pad rows (>= len(items)) get count 0 and
    all-zero blocks; their junk digests are never selected
    (merkle_inner masks on the logical count)."""
    n = len(items)
    p = int(prefix_len)
    lens = np.fromiter((len(x) for x in items), dtype=np.int64, count=n)
    row = n_blocks * 64
    flat = np.zeros(n_pad * row, dtype=np.uint8)
    counts = np.zeros(n_pad, dtype=np.int32)
    if not n:
        return flat.reshape(n_pad, n_blocks, 64), counts
    if int(lens.min()) == int(lens.max()):
        # uniform leaves (tx-hash / part-split shape): one reshape-copy
        # and constant padding — ~4x cheaper than the ragged scatter
        length = int(lens[0])
        buf = flat.reshape(n_pad, row)
        if length:
            buf[:n, p : p + length] = np.frombuffer(
                b"".join(items), dtype=np.uint8
            ).reshape(n, length)
        buf[:n, p + length] = 0x80
        nbi = (length + p + 72) // 64
        bits = (length + p) * 8
        buf[:n, nbi * 64 - 8 : nbi * 64] = np.frombuffer(
            bits.to_bytes(8, "big"), dtype=np.uint8
        )
        counts[:n] = nbi
        return flat.reshape(n_pad, n_blocks, 64), counts
    total = int(lens.sum())
    src = np.frombuffer(b"".join(items), dtype=np.uint8)
    row_base = np.arange(n, dtype=np.int64) * row + p
    if total:
        offs = np.zeros(n, dtype=np.int64)
        np.cumsum(lens[:-1], out=offs[1:])
        dst = np.repeat(row_base - offs, lens) + np.arange(total, dtype=np.int64)
        flat[dst] = src
    flat[row_base + lens] = 0x80
    nb = (lens + p + 72) // 64  # prefix + 1 terminator + 8 length bytes
    bits = (lens + p) * 8
    tail = np.arange(n, dtype=np.int64) * row + nb * 64
    for k in range(8):
        flat[tail - 1 - k] = (bits >> (8 * k)) & 0xFF
    counts[:n] = nb
    return flat.reshape(n_pad, n_blocks, 64), counts


def leaf_blocks_needed(max_len: int) -> int:
    """Blocks for the longest leaf (prefix + terminator + length)."""
    return int((max_len + 73) // 64)


# -- generic fixed-length batch (sha512-style API) --------------------------


def sha256(msgs: jnp.ndarray) -> jnp.ndarray:
    """Batched SHA-256 of uniform-length messages: (N, L) u8/int32 byte
    values -> (N, 32) int32 digest bytes. L is static; padding is
    computed at trace time (mirror of ops/sha512.sha512's contract)."""
    n, length = msgs.shape
    blocks = (length + 1 + 8 + 63) // 64
    pad = np.zeros(blocks * 64 - length, dtype=np.uint32)
    pad[0] = 0x80
    bitlen = length * 8
    for i in range(8):
        pad[-1 - i] = (bitlen >> (8 * i)) & 0xFF
    m = jnp.concatenate(
        [msgs.astype(U32), jnp.broadcast_to(jnp.asarray(pad), (n, pad.shape[0]))],
        axis=1,
    )
    st = _initial_state(n)
    for b in range(blocks):
        st = _compress(st, _words_from_bytes(m[:, b * 64 : (b + 1) * 64]))
    shifts = jnp.asarray([24, 16, 8, 0], dtype=U32)
    out = (st.T[:, :, None] >> shifts) & 0xFF  # (N, 8, 4) big-endian bytes
    return out.reshape(n, 32).astype(jnp.int32)
