"""Stage 2 of the slot-order tabled verify and of the generic verify as
Pallas kernels, rows on lanes.

Under XLA a field multiplication of ``ops/field.py`` is ~28 fusions and
one launch of the tabled stage 2 at 10,240 slots 21,469 of them, each a
microsecond of mostly fixed cost (compile-only reading, ISSUE 36). Here
a point operation is ONE kernel body: a field element is a list of 20
int32 arrays of (TB, 128) rows — a limb a vector register at TB = 8 —
so a limb shift is a renaming, no value between the multiplications of
a point addition leaves VMEM, and the window loop keeps its accumulator
in the kernel's resident output block.

The integer operations are those of ``ops/field.py`` and ``ops/curve.py``
in the same order (every product column and carry pass; sums of
non-negative terms below 2^31 are exact in any order), the weak-limb
invariant (limbs in [0, WEAK_MAX], columns < 2^31) is unchanged and no
carry pass is dropped, so every coordinate of the result is BIT-EQUAL to
``curve.double_scalar_mul_tabled``'s (``curve.double_scalar_mul_signed``'s
for the generic kernel) — which stay as the oracles, as the CPU path,
and as the bodies of the programs off the shape rule (kernel_form).

Layout. Slot c*V + i (commit c, validator i) lies at [c, i // 128,
i % 128]. The set's (V, SPLITS, 8, 60) tables are transposed once a
launch to (SPLITS, 8, 60, V/128, 128); the grid runs (validator block,
window, split, commit) with the commit innermost, so a block's split
table is fetched once and reused over the launch's commits. In the
generic kernel row r lies at [r // 128, r % 128]; the grid runs (row
block, window), and a block's [1..8]Q tables are built at its first
window into VMEM scratch, where they stay for its 64 windows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import curve
from tendermint_tpu.ops import field as F

LANES = 128
SUBLANES = 8  # (8, 128) int32 = one vector register a limb
BLOCK_ROWS = SUBLANES * LANES

_L = F.LIMBS
_AFF = 3 * _L  # an affine-cached table entry: ypx, ymx, t2d
_COMB_DIGITS = 32


def kernel_form(n: int, platform: str) -> bool:
    """Whether a stage 2 lowered for ``platform`` has the kernel body: a
    TPU, and n — the slot-order family's table keys, the generic
    family's rows (a device's, under a mesh) — tiling onto whole (8, 128)
    blocks. Nothing else is consulted."""
    return platform == "tpu" and n % BLOCK_ROWS == 0


# -- field arithmetic on limb lists ------------------------------------------
#
# ops/field.py on lists of 20 same-shaped int32 arrays instead of a
# trailing axis of 20: the same integer operations, limb for limb.


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


# -- point operations on limb lists ------------------------------------------


def madd(p, q):
    """curve.madd: p (x, y, z, t) + q (ypx, ymx, t2d)."""
    px, py, pz, pt = p
    ypx, ymx, t2d = q
    a = mul(sub(py, px), ymx)
    b = mul(add(py, px), ypx)
    c = mul(pt, t2d)
    d = add(pz, pz)
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    return mul(e, f), mul(g, h), mul(f, g), mul(e, h)


def double(p, want_t=True):
    """curve.double; p.t is never read."""
    px, py, pz, _ = p
    a = square(px)
    b = square(py)
    c = square(pz)
    c = add(c, c)
    d = neg(a)
    e = sub(sub(square(add(px, py)), a), b)
    g = add(d, b)
    f = sub(g, c)
    h = sub(d, b)
    t = mul(e, h) if want_t else [jnp.zeros_like(x) for x in e]
    return mul(e, f), mul(g, h), mul(f, g), t


_D2 = tuple(int(x) for x in curve._D2_C)


def point_add(p, q):
    """curve.add: the complete addition of two extended points."""
    px, py, pz, pt = p
    qx, qy, qz, qt = q
    a = mul(sub(py, px), sub(qy, qx))
    b = mul(add(py, px), add(qy, qx))
    c = mul(mul(pt, _D2), qt)
    d = mul(pz, add(qz, qz))
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    return mul(e, f), mul(g, h), mul(f, g), mul(e, h)


def to_cached(p):
    """curve.to_cached: (Y+X, Y-X, 2Z, 2dT)."""
    px, py, pz, pt = p
    return add(py, px), sub(py, px), add(pz, pz), mul(pt, _D2)


def add_cached(p, q, want_t=True):
    """curve.add_cached: p (x, y, z, t) + q (ypx, ymx, z2, t2d)."""
    px, py, pz, pt = p
    ypx, ymx, z2, t2d = q
    a = mul(sub(py, px), ymx)
    b = mul(add(py, px), ypx)
    c = mul(pt, t2d)
    d = mul(pz, z2)
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    t = mul(e, h) if want_t else [jnp.zeros_like(x) for x in e]
    return mul(e, f), mul(g, h), mul(f, g), t


def _where(cond, x, y):
    """jnp.where, folded at trace time where both sides are the same
    Python int (a limb every entry of a constant table shares)."""
    if isinstance(x, int) and isinstance(y, int) and x == y:
        return x
    return jnp.where(cond, x, y)


def signed_operand(sel, digit):
    """The zero and sign handling of curve._select_affine,
    curve._select_comb256 and curve._select_signed: ``sel`` the 60
    affine (ypx, ymx, t2d) or 80 cached (ypx, ymx, z2, t2d) limbs
    selected by |digit|; digit 0 gives the identity (1, 1, [2,] 0), a
    negative digit swaps ypx and ymx and negates t2d."""
    parts = [sel[i : i + _L] for i in range(0, len(sel), _L)]
    ypx, ymx, t2d = parts[0], parts[1], parts[-1]
    zero = digit == 0
    one = [1 if k == 0 else 0 for k in range(_L)]
    ypx = [_where(zero, o, x) for o, x in zip(one, ypx)]
    ymx = [_where(zero, o, x) for o, x in zip(one, ymx)]
    z2 = [[_where(zero, 2 * o, x) for o, x in zip(one, z)] for z in parts[2:-1]]
    t2d = [_where(zero, 0, x) for x in t2d]
    neg_ = digit < 0
    ypx, ymx = (
        [_where(neg_, y, x) for x, y in zip(ypx, ymx)],
        [_where(neg_, x, y) for x, y in zip(ypx, ymx)],
    )
    t2d = [_where(neg_, n, x) for n, x in zip(neg(t2d), t2d)]
    return (ypx, ymx, *z2, t2d)


def tree_select(entry, mag, width=_AFF):
    """curve._tree_select: ``entry(e, l)`` gives limb l of table entry e;
    the bits of max(mag - 1, 0) halve the _TBL entries to one."""
    m = jnp.maximum(mag - 1, 0)
    bits = [((m >> b) & 1) != 0 for b in range(curve._TBL.bit_length() - 1)]
    out = []
    for l in range(width):
        t = [entry(e, l) for e in range(curve._TBL)]
        for b in bits:
            t = [_where(b, t[2 * i + 1], t[2 * i]) for i in range(len(t) // 2)]
        out.append(t[0])
    return out


# -- kernels -----------------------------------------------------------------


def _load_point(ref, *lead):
    return tuple([ref[lead + (i, k)] for k in range(_L)] for i in range(4))


def _store_point(ref, p, *lead):
    for i in range(4):
        for k in range(_L):
            ref[lead + (i, k)] = p[i][k]


def _store_identity(ref, *lead):
    zero = jnp.zeros(ref.shape[-2:], jnp.int32)
    one = [zero + 1] + [zero] * (_L - 1)
    _store_point(ref, ([zero] * _L, one, one, [zero] * _L), *lead)


def _window_doublings(acc_ref, *lead):
    """curve._window_doublings on the resident accumulator: doubling
    never reads T, so the first three skip it."""

    def dbl(_, carry):
        _store_point(acc_ref, double(_load_point(acc_ref, *lead), want_t=False), *lead)
        return carry

    jax.lax.fori_loop(0, 3, dbl, 0)
    _store_point(acc_ref, double(_load_point(acc_ref, *lead)), *lead)


def _window_kernel(kd_ref, tbl_ref, acc_ref):
    """One key-side mixed addition: grid (validator block, window,
    split, commit). acc_ref is the block's (C, 4, 20, TB, 128)
    accumulator, resident over the three inner axes: the identity before
    the first window, the 4-doubling run before each window's first
    split (on the identity too, as the XLA scan does), then acc +=
    select(table[split], digit)."""
    w, m, c = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    pl.when((w == 0) & (m == 0))(lambda: _store_identity(acc_ref, c))
    pl.when(m == 0)(lambda: _window_doublings(acc_ref, c))
    digit = kd_ref[...]
    sel = tree_select(lambda e, l: tbl_ref[e, l], jnp.abs(digit))
    _store_point(acc_ref, madd(_load_point(acc_ref, c), signed_operand(sel, digit)), c)


def _comb_kernel(sd_ref, sel_ref, in_ref, acc_ref):
    """One base-comb mixed addition: grid (commit, validator block,
    digit position); sel_ref the MXU-selected entry of this position,
    its zero and sign handled here. The last addition feeds encode,
    which never reads T: zeros, as the XLA body gives."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _store_point(acc_ref, _load_point(in_ref))

    digit = sd_ref[...]
    q = signed_operand([sel_ref[l] for l in range(_AFF)], digit)
    x, y, z, t = madd(_load_point(acc_ref), q)
    keep_t = (p < pl.num_programs(2) - 1).astype(jnp.int32)
    _store_point(acc_ref, (x, y, z, [v * keep_t for v in t]))


# curve._BASE_TABLE as Python ints: entry e's 80 cached limbs, constants
# of the kernel body (its 2Z limbs are one value over all entries and
# fold away in the select)
_BASE_CACHED = tuple(tuple(int(x) for x in e.reshape(-1)) for e in curve._BASE_TABLE)


def _generic_kernel(sd_ref, kd_ref, q_ref, acc_ref, tbl_ref):
    """One window of curve.double_scalar_mul_signed for a block of rows:
    grid (row block, window). At a block's first window the rows'
    cached [1..8]Q table is built into tbl_ref (VMEM, resident for the
    block's 64 windows) as the XLA table_body does, and the accumulator
    starts at the identity; then every window is the 4-doubling run, +
    the base entry (constants), + the key entry without T."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        _store_point(acc_ref, _load_point(q_ref))  # [1]Q

        def entry(e, carry):  # table_body: emit [e+1]Q, step to [e+2]Q
            p = _load_point(acc_ref)
            _store_point(tbl_ref, to_cached(p), e)
            _store_point(acc_ref, point_add(p, _load_point(q_ref)))
            return carry

        jax.lax.fori_loop(0, curve._TBL - 1, entry, 0)
        _store_point(tbl_ref, to_cached(_load_point(acc_ref)), curve._TBL - 1)
        _store_identity(acc_ref)

    _window_doublings(acc_ref)
    sd, kd = sd_ref[...], kd_ref[...]
    base = tree_select(lambda e, l: _BASE_CACHED[e][l], jnp.abs(sd), 4 * _L)
    acc = add_cached(_load_point(acc_ref), signed_operand(base, sd))
    key = tree_select(lambda e, l: tbl_ref[e, l // _L, l % _L], jnp.abs(kd), 4 * _L)
    _store_point(acc_ref, add_cached(acc, signed_operand(key, kd), want_t=False))


def _vmem_limit(*block_bytes):
    # every block twice (the pipeline's two buffers) and as much again
    # for what the body spills
    return int(2 * 2 * sum(block_bytes)) + (8 << 20)


def windows(kdw, tables_t, *, tb=SUBLANES, interpret=False):
    """The key side, [k]Q: kdw (SPLIT_W, SPLITS, C, VR, 128) signed
    nibble digits, most significant window first; tables_t (SPLITS, 8,
    60, VR, 128). -> (C, 4, 20, VR, 128) extended coordinates."""
    n_w, n_m, c, vr, _ = kdw.shape
    blk = tb * LANES * 4
    return pl.pallas_call(
        _window_kernel,
        grid=(vr // tb, n_w, n_m, c),
        in_specs=[
            pl.BlockSpec(
                (None, None, None, tb, LANES), lambda v, w, m, ci: (w, m, ci, v, 0)
            ),
            pl.BlockSpec(
                (None, curve._TBL, _AFF, tb, LANES), lambda v, w, m, ci: (m, 0, 0, v, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (c, 4, _L, tb, LANES), lambda v, w, m, ci: (0, 0, 0, v, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((c, 4, _L, vr, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(curve._TBL * _AFF * blk, c * 4 * _L * blk),
        ),
        name="stage2_window",
        interpret=interpret,
    )(kdw, tables_t)


def comb(sdt, sel_t, acc, *, tb=SUBLANES, interpret=False):
    """The base side, acc + [s]B: sdt (32, C, VR, 128) signed base-256
    digits, sel_t (32, 60, C, VR, 128) the comb entries selected by
    their magnitudes, acc (C, 4, 20, VR, 128). -> the same shape."""
    n_p, c, vr, _ = sdt.shape
    blk = tb * LANES * 4
    point = pl.BlockSpec((None, 4, _L, tb, LANES), lambda ci, v, p: (ci, 0, 0, v, 0))
    return pl.pallas_call(
        _comb_kernel,
        grid=(c, vr // tb, n_p),
        in_specs=[
            pl.BlockSpec((None, None, tb, LANES), lambda ci, v, p: (p, ci, v, 0)),
            pl.BlockSpec(
                (None, _AFF, None, tb, LANES), lambda ci, v, p: (p, 0, ci, v, 0)
            ),
            point,
        ],
        out_specs=point,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(_AFF * blk, 2 * 4 * _L * blk),
        ),
        name="stage2_comb",
        interpret=interpret,
    )(sdt, sel_t, acc)


def generic_scan(sdw, kdw, q_t, *, tb=SUBLANES, interpret=False):
    """[s]B + [k]Q a row: sdw, kdw (64, NR, 128) signed nibble digits,
    most significant window first; q_t (4, 20, NR, 128) the rows' Q.
    -> (4, 20, NR, 128) extended coordinates."""
    n_w, nr, _ = sdw.shape
    blk = tb * LANES * 4
    digits = pl.BlockSpec((None, tb, LANES), lambda v, w: (w, v, 0))
    point = pl.BlockSpec((4, _L, tb, LANES), lambda v, w: (0, 0, v, 0))
    return pl.pallas_call(
        _generic_kernel,
        grid=(nr // tb, n_w),
        in_specs=[digits, digits, point],
        out_specs=point,
        out_shape=jax.ShapeDtypeStruct(q_t.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((curve._TBL, 4, _L, tb, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(curve._TBL * 4 * _L * blk, 2 * 4 * _L * blk),
        ),
        name="generic_scan",
        interpret=interpret,
    )(sdw, kdw, q_t)


# -- the stage-2 body --------------------------------------------------------


def double_scalar_mul_slots_kernel(sd8, kd_signed, key_tables, *, tb=SUBLANES, interpret=False):
    """curve.double_scalar_mul_tabled for N = C*V rows in slot order
    against the set's (V, SPLITS, 8, 60) tables, V a multiple of
    tb * 128, with the point arithmetic in the two kernels above.
    Shares the digit recoding's outputs, the constants and the comb's
    MXU select with the XLA body, and no loop logic."""
    n, v = kd_signed.shape[0], key_tables.shape[0]
    c, vr = n // v, v // LANES
    # digit j = SPLIT_W*m + w -> [w, m, slot], most significant window first
    kdw = jnp.flip(
        jnp.transpose(kd_signed.reshape(n, curve.SPLITS, curve.SPLIT_W), (2, 1, 0)), axis=0
    ).reshape(curve.SPLIT_W, curve.SPLITS, c, vr, LANES)
    tables_t = jnp.transpose(key_tables, (1, 2, 3, 0)).reshape(
        curve.SPLITS, curve._TBL, _AFF, vr, LANES
    )
    acc = windows(kdw, tables_t, tb=tb, interpret=interpret)
    sel_t = jnp.transpose(curve._comb256_entries(jnp.abs(sd8)), (1, 2, 0)).reshape(
        _COMB_DIGITS, _AFF, c, vr, LANES
    )
    sdt = jnp.transpose(sd8).reshape(_COMB_DIGITS, c, vr, LANES)
    acc = comb(sdt, sel_t, acc, tb=tb, interpret=interpret)
    # (C, 4, 20, VR, 128) -> four (N, 20)
    out = jnp.transpose(acc, (1, 0, 3, 4, 2)).reshape(4, n, _L)
    return curve.Point(out[0], out[1], out[2], out[3])


def double_scalar_mul_slots(sd8, kd_signed, key_tables) -> curve.Point:
    """[s]B + [k]Q for rows in slot order: the kernel form where the
    program is lowered for a TPU and the table operand tiles onto whole
    blocks (kernel_form), else the XLA body — every CPU path, and sets
    off the rule until a cell holds them."""
    if not kernel_form(key_tables.shape[0], "tpu"):
        return curve.double_scalar_mul_tabled(sd8, kd_signed, key_tables)
    return jax.lax.platform_dependent(
        sd8, kd_signed, key_tables,
        tpu=double_scalar_mul_slots_kernel,
        default=curve.double_scalar_mul_tabled,
    )


def double_scalar_mul_rows_kernel(sd, kd, q, *, tb=SUBLANES, interpret=False):
    """curve.double_scalar_mul_signed for N rows, N a multiple of
    tb * 128, with the table build and the 64 windows in generic_scan.
    Row r lies at [r // 128, r % 128]."""
    n = sd.shape[0]
    nr = n // LANES

    def windows_first(d):  # (N, 64) -> (64, NR, 128), window 63 first
        return jnp.flip(jnp.transpose(d), axis=0).reshape(64, nr, LANES)

    q_t = jnp.transpose(jnp.stack(q), (0, 2, 1)).reshape(4, _L, nr, LANES)
    acc = generic_scan(windows_first(sd), windows_first(kd), q_t, tb=tb, interpret=interpret)
    out = jnp.transpose(acc, (0, 2, 3, 1)).reshape(4, n, _L)
    return curve.Point(out[0], out[1], out[2], out[3])


def double_scalar_mul_rows(sd, kd, q) -> curve.Point:
    """[s]B + [k]Q a row for the generic family: the kernel form where
    the program is lowered for a TPU and the rows tile onto whole blocks
    (kernel_form: the 1,024-row buckets and up), else the XLA body —
    every CPU path and the small buckets."""
    if not kernel_form(sd.shape[0], "tpu"):
        return curve.double_scalar_mul_signed(sd, kd, q)
    return jax.lax.platform_dependent(
        sd, kd, q,
        tpu=double_scalar_mul_rows_kernel,
        default=curve.double_scalar_mul_signed,
    )
