"""Operations and bytes one ed25519 signature row needs, whatever
implements it — the numerator of ``verify_roofline``.

The predicate: cofactorless verification, accept iff
``enc([s]B - [h]A) == R`` with ``h = SHA-512(R || A || M) mod L``, for a
validator whose key ``A`` is known in advance (its decompression and its
window tables are set-up, amortised over every commit of the set's life)
and a 160-byte canonical message, so ``R || A || M`` is 224 bytes.

Field multiplications (a squaring counts as one), per row:

    [s]B   fixed-base comb, 4-bit windows over 256 bits:
           64 mixed additions, no doubling                64 x 8 =  512
    [h]A   the key's comb in 16 splits of 16 bits, 4-bit windows:
           64 mixed additions + 16 doublings        (64 + 16) x 8 =  640
    mod L  reduce the 512-bit hash: two wide products, as            4
    enc    blocked (Montgomery) inversion, 3 per row amortised, +1   4
    cmp    x's sign and y from the affine point                      2
                                                         total   1,162

(an extended-coordinates mixed addition is 7M + a constant product, a
doubling 4M + 4S: 8 each; table selects, carries and padding are what an
implementation spends, not what the predicate needs, and are left out.)
This is the tabled Straus count of BENCHMARKS.md "Round 5" without its
select overhead. One 255-bit multiplication is priced as the 32 x 32
byte products of a schoolbook product, each a multiply and an add:
2 x 1,024 = 2,048 int8 operations, the unit of the chip's published
integer peak.

SHA-512 over 224 bytes pads to 256: 2 compressions. One compression is
80 rounds of about 38 operations on 64-bit words (T1: 4 adds, Sigma1 5,
Ch 3; T2: 1 add, Sigma0 5, Maj 5; 2 adds into the state; the schedule's
sigma0 5, sigma1 5, 3 adds) plus the 8-word feed-forward; a 64-bit word
operation is priced as 8 byte operations: 80 x 38 x 8 + 64 = 24,384.

Bytes a row must move: 32 (key, by index or by value) + 160 (message) +
64 (signature) in, 1 (verdict) out.

The least time is the larger of operations over the int8 peak and bytes
over the memory bandwidth. The scan is bound by the vector unit, which
has no published peak, so the share reads well under 1%: it orders PRs
and bounds a claim; it does not say how far the kernels are from the
hardware.
"""

from __future__ import annotations

FIELD_MULS_PER_ROW = 64 * 8 + (64 + 16) * 8 + 4 + 4 + 2
OPS_PER_FIELD_MUL = 2 * 32 * 32
SHA512_COMPRESSIONS_PER_ROW = 2
OPS_PER_SHA512_COMPRESSION = 80 * 38 * 8 + 64
BYTES_PER_ROW = 32 + 160 + 64 + 1


def work(rows: int) -> dict:
    """int8-equivalent operations and bytes for ``rows`` signature rows."""
    ops = rows * (
        FIELD_MULS_PER_ROW * OPS_PER_FIELD_MUL
        + SHA512_COMPRESSIONS_PER_ROW * OPS_PER_SHA512_COMPRESSION
    )
    return {"ops": ops, "bytes": rows * BYTES_PER_ROW}


def least_seconds(rows: int, peaks: dict) -> dict:
    """The least time the chip could take, and which peak bounds it."""
    w = work(rows)
    by_ops = w["ops"] / peaks["int8_ops_per_s"]
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes), "bound": "int8_ops" if by_ops >= by_bytes else "hbm_bytes", **w}
