"""Batched GF(2^255-19) arithmetic in 13-bit limbs, pure int32.

TPU-first representation choices:

- 20 limbs x 13 bits (260-bit capacity), int32 everywhere -- native TPU
  VPU ops, no 64-bit emulation.
- REDUNDANT (weak) limbs: stored elements keep limbs in [0, WEAK_MAX]
  with WEAK_MAX = 8800 slightly above 2^13. Then partial products are
  bounded by 20 * WEAK_MAX^2 = 1.55e9 < 2^31, so a full schoolbook
  column fits int32, while carry propagation can be VECTORIZED: a small
  fixed number of parallel (lo = x & mask, hi = x >> 13, x = lo +
  shift(hi)) passes instead of a 39-step sequential ripple. Sequential
  exact carries exist only inside canonical() (used at encode/compare).
- The 20x20 partial-product convolution is one broadcast outer product
  plus 20 statically-shifted adds -- ~60 HLO ops per field mul, which
  keeps the 256-iteration scalar-mult scan compilable and lets XLA tile
  the (N, 20) batch onto 8x128 vector registers.
- Signed arithmetic shifts make subtraction branch-free (add 64p).

A field element batch is an int32 array of shape (..., 20); functions
broadcast over leading axes (no vmap needed -- the batch axis is
explicit).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

LIMBS = 20
SHIFT = 13
MASK = (1 << SHIFT) - 1

P = 2**255 - 19
# 2^260 = 2^5 * 2^255 == 2^5 * 19 = 608 (mod p): the fold factor for
# carries out of limb 19.
FOLD = 608
TOP_BITS = 255 - SHIFT * (LIMBS - 1)  # = 8: bits of limb 19 below 2^255
TOP_MASK = (1 << TOP_BITS) - 1

# Weak-limb invariant: limbs of stored elements are in [0, WEAK_MAX].
WEAK_MAX = MASK + 1 + FOLD  # 8800

# 64p in 20 limbs (top limb 14 bits) -- added before subtraction so the
# result is positive for any weak operand (weak value < 2^260.2 < 64p).
_64P_LIMBS = tuple(
    ((64 * P) >> (SHIFT * i)) & (MASK if i < LIMBS - 1 else 0x3FFF)
    for i in range(LIMBS)
)


# -- host-side conversion ---------------------------------------------------


def to_limbs(x: int) -> np.ndarray:
    x %= P
    return np.array([(x >> (SHIFT * i)) & MASK for i in range(LIMBS)], dtype=np.int32)


def from_limbs(limbs) -> int:
    arr = np.asarray(limbs)
    val = 0
    for i in range(LIMBS):
        val += int(arr[..., i]) << (SHIFT * i)
    return val % P


def const(x: int) -> np.ndarray:
    """Module-level field constants stay numpy: converting to a device
    array at import time would initialize the JAX backend on import
    (and so claim the chip for any process that merely imports the
    package); jnp ops convert numpy operands at trace time for
    free."""
    return to_limbs(x)


# -- vectorized weak carries ------------------------------------------------


def _vpass(a: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry pass over (..., 20): hi bits move one limb up;
    the carry out of limb 19 folds back times 608 into limb 0."""
    lo = a & MASK
    hi = a >> SHIFT  # arithmetic shift: handles negative columns
    shifted = jnp.concatenate(
        [FOLD * hi[..., LIMBS - 1 :], hi[..., : LIMBS - 1]], axis=-1
    )
    return lo + shifted


def _vpasses(a: jnp.ndarray, n: int) -> jnp.ndarray:
    for _ in range(n):
        a = _vpass(a)
    return a


def weak_reduce(cols: List[jnp.ndarray], passes: int = 2) -> jnp.ndarray:
    """Stack 20 int32 columns and carry down to the weak invariant."""
    return _vpasses(jnp.stack(cols, axis=-1), passes)


# -- multiplication ---------------------------------------------------------


def _mul_cols(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook convolution: (..., 20) x (..., 20) -> (..., 39) columns,
    as one outer product + 20 shifted adds."""
    outer = a[..., :, None] * b[..., None, :]  # (..., 20, 20)
    ncols = 2 * LIMBS - 1
    pad_cfg = [(0, 0)] * (outer.ndim - 2) + [(0, 0)]
    cols = None
    for i in range(LIMBS):
        row = outer[..., i, :]  # contributes to columns i..i+19
        padded = jnp.pad(row, pad_cfg[:-1] + [(i, ncols - LIMBS - i)])
        cols = padded if cols is None else cols + padded
    return cols


def _reduce_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """(..., 39) product columns (< 2^31) -> weak (..., 20) element."""
    # Two parallel passes shrink every column below 2^13 + 2^6 and push
    # overflow into columns 39/40.
    ext = jnp.pad(cols, [(0, 0)] * (cols.ndim - 1) + [(0, 2)])  # (..., 41)
    for _ in range(2):
        lo = ext & MASK
        hi = ext >> SHIFT
        ext = lo + jnp.pad(hi[..., :-1], [(0, 0)] * (cols.ndim - 1) + [(1, 0)])
    # Fold limbs 20..40 (weight 2^260 * 2^13j == 608 * 2^13j) into 0..19;
    # limb 40 (weight 2^520 == 608^2 at limb 0) folds twice.
    r = ext[..., :LIMBS] + FOLD * ext[..., LIMBS : 2 * LIMBS]
    r = r.at[..., 0].add(FOLD * FOLD * ext[..., 2 * LIMBS])
    # Four passes: 1.2e7 -> 899k -> 74k -> 13.7k -> <= WEAK_MAX.
    return _vpasses(r, 4)


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched field multiply: (..., 20) x (..., 20) -> (..., 20)."""
    return _reduce_cols(_mul_cols(a, b))


def square(a: jnp.ndarray) -> jnp.ndarray:
    return _reduce_cols(_mul_cols(a, a))


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _vpasses(a + b, 2)


_2P_LIMBS = tuple(
    ((2 * P) >> (SHIFT * i)) & (MASK if i < LIMBS - 1 else 0x3FFF)
    for i in range(LIMBS)
)


def _resolve_negatives(x: jnp.ndarray) -> jnp.ndarray:
    """After signed passes limbs sit in [-608, WEAK_MAX]; adding 2p makes
    every limb non-negative, then two passes restore the weak bound."""
    return _vpasses(x + jnp.asarray(_2P_LIMBS, dtype=jnp.int32), 2)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b + 64p + 2p (branch-free, non-negative for weak operands)."""
    k = jnp.asarray(_64P_LIMBS, dtype=jnp.int32)
    return _resolve_negatives(_vpasses(a + k - b, 3))


def neg(a: jnp.ndarray) -> jnp.ndarray:
    k = jnp.asarray(_64P_LIMBS, dtype=jnp.int32)
    return _resolve_negatives(_vpasses(k - a, 3))


def mul_small(a: jnp.ndarray, c: int) -> jnp.ndarray:
    """Multiply by a small non-negative constant (c < 2^15)."""
    return _vpasses(a * c, 5)


# -- exponentiation chains --------------------------------------------------


def _nsquare(x: jnp.ndarray, n: int) -> jnp.ndarray:
    # fori_loop keeps the HLO graph small; squaring runs are sequential
    # so no cross-iteration fusion is lost.
    if n <= 2:
        for _ in range(n):
            x = square(x)
        return x
    return jax.lax.fori_loop(0, n, lambda _, v: square(v), x)


def pow22523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3) (standard ref10 addition chain)."""
    t0 = square(z)  # 2
    t1 = _nsquare(t0, 2)  # 8
    t1 = mul(z, t1)  # 9
    t0 = mul(t0, t1)  # 11
    t0 = square(t0)  # 22
    t0 = mul(t1, t0)  # 31 = 2^5-1
    t1 = _nsquare(t0, 5)
    t0 = mul(t1, t0)  # 2^10-1
    t1 = _nsquare(t0, 10)
    t1 = mul(t1, t0)  # 2^20-1
    t2 = _nsquare(t1, 20)
    t1 = mul(t2, t1)  # 2^40-1
    t1 = _nsquare(t1, 10)
    t0 = mul(t1, t0)  # 2^50-1
    t1 = _nsquare(t0, 50)
    t1 = mul(t1, t0)  # 2^100-1
    t2 = _nsquare(t1, 100)
    t1 = mul(t2, t1)  # 2^200-1
    t1 = _nsquare(t1, 50)
    t0 = mul(t1, t0)  # 2^250-1
    t0 = _nsquare(t0, 2)  # 2^252-4
    return mul(t0, z)  # 2^252-3


def invert(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2); returns 0 for 0 like ref10."""
    t0 = square(z)  # 2
    t1 = _nsquare(t0, 2)  # 8
    t1 = mul(z, t1)  # 9
    t0 = mul(t0, t1)  # 11
    t2 = square(t0)  # 22
    t1 = mul(t1, t2)  # 31 = 2^5-1
    t2 = _nsquare(t1, 5)
    t1 = mul(t2, t1)  # 2^10-1
    t2 = _nsquare(t1, 10)
    t2 = mul(t2, t1)  # 2^20-1
    t3 = _nsquare(t2, 20)
    t2 = mul(t3, t2)  # 2^40-1
    t2 = _nsquare(t2, 10)
    t1 = mul(t2, t1)  # 2^50-1
    t2 = _nsquare(t1, 50)
    t2 = mul(t2, t1)  # 2^100-1
    t3 = _nsquare(t2, 100)
    t2 = mul(t3, t2)  # 2^200-1
    t2 = _nsquare(t2, 50)
    t1 = mul(t2, t1)  # 2^250-1
    t1 = _nsquare(t1, 5)  # 2^255-2^5
    return mul(t1, t0)  # 2^255-21 = p-2


def invert_batched(z: jnp.ndarray) -> jnp.ndarray:
    """Montgomery batch inversion over the LEADING axis: (N, 20) -> (N, 20).

    Work drops from ~254 muls/row (the addition chain) to ~6 muls/row:
    two log-depth prefix/suffix product sweeps (lax.associative_scan)
    plus ONE width-1 addition-chain inversion of the total product —
    1/z_i = prefix_{i-1} * suffix_{i+1} * (prod z)^-1.

    NOT used by the jitted verify pipeline: at (10k, 20) int32 the
    associative_scan lowering blows the stage compile from ~6s to
    >530s (measured round 2) — the runtime win is ~12ms, so the hot
    path keeps the per-row chain. Available for host-side/eager uses.

    Rows with z == 0 return 0 (ref10 invert(0) == 0): zeros are replaced
    by 1 for the sweeps so one bad row (e.g. a non-point from a failed
    decompression) cannot zero the whole batch's product."""
    zero = is_zero(z)
    one = jnp.zeros_like(z).at[..., 0].set(1)
    z_safe = jnp.where(zero[..., None], one, z)
    prefix = jax.lax.associative_scan(mul, z_safe, axis=0)
    suffix = jax.lax.associative_scan(mul, z_safe, axis=0, reverse=True)
    total_inv = invert(prefix[-1:])  # width-1 chain
    # prod_{j != i} z_j = prefix[i-1] * suffix[i+1] (identity at the ends)
    pre = jnp.concatenate([one[:1], prefix[:-1]], axis=0)
    suf = jnp.concatenate([suffix[1:], one[:1]], axis=0)
    inv = mul(mul(pre, suf), total_inv)
    return jnp.where(zero[..., None], jnp.zeros_like(z), inv)


def invert_blocked(z: jnp.ndarray, block: int = 64) -> jnp.ndarray:
    """Montgomery batch inversion over the leading axis via BLOCKED
    prefix products: (N, 20) -> (N, 20).

    invert_batched's associative_scan lowers to an odd/even slicing tree
    that blows the XLA compile at (10k, 20) (>530s, measured round 2).
    This version reshapes to (B, G, 20) blocks and runs a plain
    lax.scan of `block` steps over the block axis — each step is one
    field mul on a (G, 20) slab, so the graph is tiny and compiles with
    the rest of the finish stage. Work: ~2 full-batch muls for the two
    sweeps + one width-G addition chain, vs ~254 muls/row for per-row
    chains — the finish stage's inversion cost drops ~40x.

    Rows with z == 0 return 0 (ref10 invert(0) == 0); zeros are replaced
    by 1 for the sweeps so one bad row cannot zero a whole block.
    """
    n = z.shape[0]
    b = block
    while n % b:  # static at trace time: pick the largest divisor <= block
        b //= 2
    g = n // b
    zero = is_zero(z)
    one = jnp.zeros_like(z).at[..., 0].set(1)
    z_safe = jnp.where(zero[..., None], one, z)
    zb = z_safe.reshape(b, g, LIMBS)  # block-major: step i touches row i of each group

    def fwd(acc, zi):
        nxt = mul(acc, zi)
        return nxt, acc  # prefix EXCLUSIVE of zi

    ones_g = jnp.zeros((g, LIMBS), dtype=z.dtype).at[..., 0].set(1)
    total, pre = jax.lax.scan(fwd, ones_g, zb)  # total (g,20); pre (b,g,20)
    total_inv = invert(total)  # width-g addition chain: cheap

    def bwd(acc, xs):
        zi, prei = xs
        inv_i = mul(acc, prei)  # 1/zi = (prod of later z * total_inv) * pre_i
        nxt = mul(acc, zi)
        return nxt, inv_i

    _, inv = jax.lax.scan(bwd, total_inv, (zb, pre), reverse=True)
    inv = inv.reshape(n, LIMBS)
    return jnp.where(zero[..., None], jnp.zeros_like(z), inv)


# -- canonical form / encoding ---------------------------------------------


def _strict_carry(a: jnp.ndarray) -> List[jnp.ndarray]:
    """Sequential exact carry: weak (..., 20) -> limbs < 2^13 with value
    < 2^255 + 19*small (i.e. < 2p). Used only at canonicalization."""
    out = [a[..., i] for i in range(LIMBS)]
    for _ in range(2):
        carry = None
        for i in range(LIMBS):
            v = out[i] if carry is None else out[i] + carry
            out[i] = v & MASK
            carry = v >> SHIFT
        # carry holds bits >= 260; recombine with bits 247..259 and fold
        # everything >= 255 back times 19.
        top = out[LIMBS - 1] + (carry << SHIFT)
        hi = top >> TOP_BITS
        out[LIMBS - 1] = top & TOP_MASK
        out[0] = out[0] + 19 * hi
    return out


def canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Fully reduce mod p (canonical limbs < 2^13, value < p)."""
    s = _strict_carry(a)
    p_limbs = [(P >> (SHIFT * i)) & MASK for i in range(LIMBS)]
    diff = []
    borrow = None
    for i in range(LIMBS):
        v = s[i] - p_limbs[i] if borrow is None else s[i] - p_limbs[i] + borrow
        diff.append(v & MASK)
        borrow = v >> SHIFT  # 0 or -1
    geq = borrow == 0
    out = [jnp.where(geq, diff[i], s[i]) for i in range(LIMBS)]
    return jnp.stack(out, axis=-1)


def to_bytes(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical little-endian encoding: (..., 20) -> (..., 32) int32
    byte values."""
    c = canonical(a)
    out = []
    for j in range(32):
        bitpos = 8 * j
        i, off = divmod(bitpos, SHIFT)
        v = c[..., i] >> off
        if off + 8 > SHIFT and i + 1 < LIMBS:
            v = v | (c[..., i + 1] << (SHIFT - off))
        out.append(v & 0xFF)
    return jnp.stack(out, axis=-1)


def from_bytes(b: jnp.ndarray) -> jnp.ndarray:
    """(..., 32) little-endian bytes -> weak limbs; masks bit 255 like
    Go's feFromBytes (y >= p accepted, reduced implicitly)."""
    bi = b.astype(jnp.int32)
    limbs = []
    for i in range(LIMBS):
        bitpos = SHIFT * i
        j, off = divmod(bitpos, 8)
        v = bi[..., j] >> off
        shift = 8 - off
        jj = j + 1
        while shift < SHIFT and jj < 32:
            v = v | (bi[..., jj] << shift)
            shift += 8
            jj += 1
        limbs.append(v & MASK)
    limbs[LIMBS - 1] = limbs[LIMBS - 1] & TOP_MASK
    return jnp.stack(limbs, axis=-1)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == canonical(b), axis=-1)


def is_negative(a: jnp.ndarray) -> jnp.ndarray:
    """Sign bit = lowest bit of the canonical encoding."""
    return canonical(a)[..., 0] & 1


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == 0, axis=-1)


def select(cond: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(cond[..., None], a, b)


def zeros_like_batch(shape) -> jnp.ndarray:
    return jnp.zeros(tuple(shape) + (LIMBS,), dtype=jnp.int32)


def broadcast_const(x: int, shape) -> jnp.ndarray:
    return jnp.broadcast_to(const(x), tuple(shape) + (LIMBS,))
