"""GF(2^255-19) arithmetic on limb lists, rows on lanes, and the per-row
exponentiation chains of ``ops/field.py`` as one Pallas kernel.

A field element here is a list of 20 same-shaped int32 arrays instead of
a trailing axis of 20: in a kernel body each is a (TB, 128) block of rows
— a limb a vector register at TB = 8 — so a limb shift is a renaming.
The integer operations are those of ``ops/field.py`` limb for limb (every
product column and carry pass; sums of non-negative terms below 2^31 are
exact in any order), the weak-limb invariant (limbs in [0, WEAK_MAX],
columns < 2^31) is unchanged and no carry pass is dropped, so every limb
is BIT-EQUAL to the XLA form's. ``ops/stage2_kernel.py`` builds its point
operations from these.

The chains. ``F.pow22523`` (decompression's square root) and
``F.invert`` (the encode's inversion) are ~254 squarings and 11
multiplications a row; under XLA a squaring is ~28 fusions of about a
microsecond each. ``field_chain`` runs either as ONE kernel: grid over
blocks of rows laid [r // 128, r % 128], the shared ref10 chain to
z^(2^250-1) and then the asked-for tail, each squaring run a fori_loop
inside the body. ``pow22523`` / ``invert`` pick the kernel where the
program is lowered for a TPU and the rows tile onto whole blocks
(kernel_form), else the XLA chain — every CPU path, the small buckets —
which stays the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import field as F

LANES = 128
SUBLANES = 8  # (8, 128) int32 = one vector register a limb
BLOCK_ROWS = SUBLANES * LANES

_L = F.LIMBS


def kernel_form(n: int, platform: str) -> bool:
    """Whether a program lowered for ``platform`` has the kernel body: a
    TPU, and n — the slot-order family's table keys, the generic
    family's rows (a device's, under a mesh) — tiling onto whole (8, 128)
    blocks. Nothing else is consulted."""
    return platform == "tpu" and n % BLOCK_ROWS == 0


# -- field arithmetic on limb lists ------------------------------------------


def vpass(a):
    lo = [x & F.MASK for x in a]
    hi = [x >> F.SHIFT for x in a]
    return [lo[0] + F.FOLD * hi[_L - 1]] + [lo[k] + hi[k - 1] for k in range(1, _L)]


def vpasses(a, n):
    for _ in range(n):
        a = vpass(a)
    return a


def _zero(x):
    return isinstance(x, int) and x == 0


def mul(a, b):
    """field._mul_cols + field._reduce_cols. A limb may be a Python int
    (a constant operand): a product by a zero limb is folded away at
    trace time, which adds nothing to an exact column."""
    cols = []
    for k in range(2 * _L - 1):
        terms = [
            a[i] * b[k - i]
            for i in range(max(0, k - _L + 1), min(_L, k + 1))
            if not (_zero(a[i]) or _zero(b[k - i]))
        ]
        cols.append(functools.reduce(lambda x, y: x + y, terms) if terms else 0)
    # two passes over the 39 columns and two zero columns above them,
    # the zeros folded at trace time
    lo = [x & F.MASK for x in cols]
    hi = [x >> F.SHIFT for x in cols]
    ext = [lo[0]] + [lo[k] + hi[k - 1] for k in range(1, 2 * _L - 1)] + [hi[2 * _L - 2]]
    lo = [x & F.MASK for x in ext]
    hi = [x >> F.SHIFT for x in ext]
    ext = [lo[0]] + [lo[k] + hi[k - 1] for k in range(1, 2 * _L)] + [hi[2 * _L - 1]]
    r = [ext[k] + F.FOLD * ext[_L + k] for k in range(_L)]
    r[0] = r[0] + F.FOLD * F.FOLD * ext[2 * _L]
    return vpasses(r, 4)


def square(a):
    return mul(a, a)


def add(a, b):
    return vpasses([x + y for x, y in zip(a, b)], 2)


def _resolve_negatives(x):
    return vpasses([v + k for v, k in zip(x, F._2P_LIMBS)], 2)


def sub(a, b):
    return _resolve_negatives(
        vpasses([x + k - y for x, y, k in zip(a, b, F._64P_LIMBS)], 3)
    )


def neg(a):
    return _resolve_negatives(vpasses([k - x for x, k in zip(a, F._64P_LIMBS)], 3))


# -- the exponentiation chains -----------------------------------------------


def _nsquare(x, n):
    """n squarings as one loop body: a run's values never leave the
    kernel, and its body is compiled once."""
    return jax.lax.fori_loop(0, n, lambda _, v: square(v), x)


def chain(z, tail):
    """F.pow22523 (tail "pow22523") or F.invert (tail "invert") on a
    limb list: the ref10 chain the two share, product for product and
    squaring for squaring, so every limb equals theirs."""
    z2 = square(z)
    z9 = mul(z, _nsquare(z2, 2))
    z11 = mul(z2, z9)
    e5 = mul(z9, square(z11))  # 2^5-1
    e10 = mul(_nsquare(e5, 5), e5)
    e20 = mul(_nsquare(e10, 10), e10)
    e40 = mul(_nsquare(e20, 20), e20)
    e50 = mul(_nsquare(e40, 10), e10)
    e100 = mul(_nsquare(e50, 50), e50)
    e200 = mul(_nsquare(e100, 100), e100)
    e250 = mul(_nsquare(e200, 50), e50)
    if tail == "pow22523":
        return mul(_nsquare(e250, 2), z)  # 2^252-3
    return mul(_nsquare(e250, 5), z11)  # 2^255-21 = p-2


def _chain_kernel(z_ref, out_ref, *, tail):
    """One block of rows: z_ref and out_ref (20, TB, 128)."""
    r = chain([z_ref[k] for k in range(_L)], tail)
    for k in range(_L):
        out_ref[k] = r[k]


def field_chain(z_t, tail, *, tb=SUBLANES, interpret=False):
    """z_t (20, NR, 128), row r at [:, r // 128, r % 128] -> the chain's
    result in the same layout."""
    _, nr, _ = z_t.shape
    spec = pl.BlockSpec((_L, tb, LANES), lambda v: (0, v, 0))
    return pl.pallas_call(
        functools.partial(_chain_kernel, tail=tail),
        grid=(nr // tb,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(z_t.shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="field_chain",
        interpret=interpret,
    )(z_t)


def chain_rows_kernel(z, tail, *, tb=SUBLANES, interpret=False):
    """The chain for (N, 20) rows, N a multiple of tb * 128."""
    n = z.shape[0]
    z_t = jnp.transpose(z).reshape(_L, n // LANES, LANES)
    out = field_chain(z_t, tail, tb=tb, interpret=interpret)
    return jnp.transpose(out.reshape(_L, n))


def _dispatch(z, tail, xla):
    """The kernel form where the program is lowered for a TPU and z is
    (N, 20) rows on the rule (kernel_form: the 1,024-row buckets and
    up, a device's rows under a mesh), else the XLA chain."""
    if z.ndim != 2 or not kernel_form(z.shape[0], "tpu"):
        return xla(z)
    return jax.lax.platform_dependent(
        z, tpu=functools.partial(chain_rows_kernel, tail=tail), default=xla
    )


def pow22523(z):
    """F.pow22523, a row: z^(2^252-3)."""
    return _dispatch(z, "pow22523", F.pow22523)


def invert(z):
    """F.invert, a row: z^(p-2), 0 for 0."""
    return _dispatch(z, "invert", F.invert)
