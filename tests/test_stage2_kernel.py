"""The rows-on-lanes kernel form of the slot-order stage 2
(ops/stage2_kernel.py) against the XLA body it must equal bit for bit:
the list-form field arithmetic and the point bodies as plain jnp, the
two Pallas kernels in interpret mode under jit, and the rule that picks
the body (the platform of lowering and V's shape, nothing else).

Interpret-mode kernels are compiled with XLA:CPU's fusion off
(_NO_FUSION): fused, the ~10^4 small elementwise ops of one point
addition make one LLVM function that compiles for minutes.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import curve  # noqa: E402
from tendermint_tpu.ops import ed25519 as E  # noqa: E402
from tendermint_tpu.ops import field as F  # noqa: E402
from tendermint_tpu.ops import field_kernel as FK  # noqa: E402
from tendermint_tpu.ops import stage2_kernel as K  # noqa: E402

L = F.LIMBS
ROWS = 64
_NO_FUSION = {
    "xla_backend_optimization_level": 0,
    "xla_disable_hlo_passes": "algsimp,fusion,cpu-instruction-fusion,cpu_instruction_fusion",
}


def _weak(rng, *shape):
    return rng.integers(0, F.WEAK_MAX + 1, size=shape + (L,), dtype=np.int32)


def _lists(a):
    """(..., 20) -> list of 20 (...,) arrays."""
    return [jnp.asarray(a[..., k]) for k in range(L)]


def _stack(limbs):
    return np.stack([np.asarray(x) for x in limbs], axis=-1)


_INPUTS = {
    "random": lambda rng: _weak(rng, ROWS),
    "weak_max": lambda rng: np.full((ROWS, L), F.WEAK_MAX, dtype=np.int32),
    "zeros": lambda rng: np.zeros((ROWS, L), dtype=np.int32),
    "p_minus_1": lambda rng: np.broadcast_to(F.to_limbs(F.P - 1), (ROWS, L)).copy(),
}
_OPS = {
    "mul": (K.mul, F.mul, 2),
    "square": (K.square, F.square, 1),
    "add": (K.add, F.add, 2),
    "sub": (K.sub, F.sub, 2),
    "neg": (K.neg, F.neg, 1),
}


@pytest.mark.parametrize("kind", sorted(_INPUTS))
@pytest.mark.parametrize("op", sorted(_OPS))
def test_list_form_field_op_is_bit_equal(op, kind):
    """Each op on limb lists, as plain jnp outside any kernel, against
    ops/field.py: the given input against itself and against random
    weak limbs."""
    rng = np.random.default_rng(36)
    lists_fn, field_fn, arity = _OPS[op]
    a = _INPUTS[kind](rng)
    for b in (a, _weak(rng, ROWS)):
        args = (a, b)[:arity]
        got = _stack(lists_fn(*[_lists(x) for x in args]))
        want = np.asarray(field_fn(*[jnp.asarray(x) for x in args]))
        np.testing.assert_array_equal(got, want)
        assert got.min() >= 0 and got.max() <= F.WEAK_MAX


def _point_lists(p):
    return tuple(_lists(c) for c in p)


def _assert_point(got, want):
    for g, w, name in zip(got, want, "xyzt"):
        np.testing.assert_array_equal(_stack(g), np.asarray(w), err_msg=name)


@pytest.mark.parametrize("want_t", [True, False])
def test_point_bodies_match_curve(want_t):
    rng = np.random.default_rng(37)
    p, q = _weak(rng, 4, ROWS), _weak(rng, 3, ROWS)
    pt = curve.Point(*(jnp.asarray(c) for c in p))
    _assert_point(K.double(_point_lists(p), want_t=want_t), curve.double(pt, want_t=want_t))
    if want_t:  # the kernels' madd always computes T (the comb zeroes its last one)
        _assert_point(
            K.madd(_point_lists(p), _point_lists(q)),
            curve.madd(pt, curve.AffineCached(*(jnp.asarray(c) for c in q))),
        )


_GENERIC_OPS = {
    "add": lambda p, q: (K.point_add(p, q), curve.add(_pt(p), _pt(q))),
    "to_cached": lambda p, q: (K.to_cached(p), curve.to_cached(_pt(p))),
    "add_cached_t": lambda p, q: (
        K.add_cached(p, q), curve.add_cached(_pt(p), curve.CachedPoint(*_jnp(q)))
    ),
    "add_cached_no_t": lambda p, q: (
        K.add_cached(p, q, want_t=False),
        curve.add_cached(_pt(p), curve.CachedPoint(*_jnp(q)), want_t=False),
    ),
}


def _jnp(p):
    return tuple(jnp.stack(c, axis=-1) for c in p)


def _pt(p):
    return curve.Point(*_jnp(p))


@pytest.mark.parametrize("op", sorted(_GENERIC_OPS))
def test_generic_point_ops_match_curve(op):
    """The generic family's point operations on limb lists against
    ops/curve.py's, on random weak points and on the identity."""
    rng = np.random.default_rng(43)
    ident = np.stack([np.asarray(c) for c in curve.identity((ROWS,))])
    for p in (_weak(rng, 4, ROWS), ident):
        got, want = _GENERIC_OPS[op](_point_lists(p), _point_lists(_weak(rng, 4, ROWS)))
        _assert_point(got, want)


def test_constant_operand_folds_and_stays_bit_equal():
    """A field multiplication by a constant of Python-int limbs (2d,
    the base table's 2Z = 2) equals ops/field.py's by the same constant
    as an array, zero limbs folded away at trace time."""
    rng = np.random.default_rng(44)
    a = _weak(rng, ROWS)
    for c in (K._D2, K._BASE_CACHED[3][2 * L : 3 * L]):
        got = _stack(K.mul(_lists(a), list(c)))
        want = np.asarray(F.mul(jnp.asarray(a), jnp.asarray(np.asarray(c, np.int32))))
        np.testing.assert_array_equal(got, want)


_SIGNED_DIGITS = {  # what signed_digits gives: [-8, 8)
    "zero": lambda rng, n: np.zeros(n, dtype=np.int32),
    "minus8": lambda rng, n: np.full(n, -8, dtype=np.int32),
    "plus7": lambda rng, n: np.full(n, 7, dtype=np.int32),
    "mixed": lambda rng, n: rng.integers(-8, 8, size=n).astype(np.int32),
}


@pytest.mark.parametrize("kind", sorted(_SIGNED_DIGITS))
def test_cached_select_matches_select_signed(kind):
    """The 80-limb cached select — a table a row, and the constant base
    table whose shared limbs fold — against curve._select_signed."""
    rng = np.random.default_rng(45)
    table = rng.integers(0, F.WEAK_MAX + 1, size=(ROWS, 8, 4 * L), dtype=np.int32)
    digit = _SIGNED_DIGITS[kind](rng, ROWS)
    d, mag = jnp.asarray(digit), jnp.abs(jnp.asarray(digit))
    base = np.asarray(curve._BASE_TABLE, np.int32).reshape(8, 4 * L)
    for entry, want in (
        (lambda e, l: jnp.asarray(table[:, e, l]), curve._select_signed(jnp.asarray(table), d)),
        (lambda e, l: K._BASE_CACHED[e][l], curve._select_signed(jnp.asarray(base), d)),
    ):
        got = K.signed_operand(K.tree_select(entry, mag, 4 * L), d)
        for g, w, name in zip(got, want, ("ypx", "ymx", "z2", "t2d")):
            g = [jnp.broadcast_to(x, (ROWS,)) for x in g]
            np.testing.assert_array_equal(_stack(g), np.asarray(w), err_msg=name)


def test_doubling_run_matches_window_doublings():
    """What the window kernel does before a window's first split: three
    doublings without T, one with — on the identity too."""
    rng = np.random.default_rng(38)
    for p in (_weak(rng, 4, ROWS), np.stack([np.asarray(c) for c in curve.identity((ROWS,))])):
        acc = _point_lists(p)
        for _ in range(3):
            acc = K.double(acc, want_t=False)
        _assert_point(
            K.double(acc), curve._window_doublings(curve.Point(*(jnp.asarray(c) for c in p)))
        )


_DIGITS = {
    "zero": lambda rng, n: np.zeros(n, dtype=np.int32),
    "plus8": lambda rng, n: np.full(n, 8, dtype=np.int32),
    "minus8": lambda rng, n: np.full(n, -8, dtype=np.int32),
    "negative": lambda rng, n: np.full(n, -3, dtype=np.int32),
    "mixed": lambda rng, n: rng.integers(-8, 9, size=n).astype(np.int32),
}


@pytest.mark.parametrize("kind", sorted(_DIGITS))
def test_select_matches_select_affine(kind):
    """The 7-`where` tree and the zero and sign handling against
    curve._select_affine on a table a row."""
    rng = np.random.default_rng(39)
    table = rng.integers(0, F.WEAK_MAX + 1, size=(ROWS, 8, 3 * L), dtype=np.int32)
    digit = _DIGITS[kind](rng, ROWS)
    sel = K.tree_select(lambda e, l: jnp.asarray(table[:, e, l]), jnp.abs(jnp.asarray(digit)))
    got = K.signed_operand(sel, jnp.asarray(digit))
    want = curve._select_affine(jnp.asarray(table), jnp.asarray(digit))
    for g, w, name in zip(got, want, ("ypx", "ymx", "t2d")):
        np.testing.assert_array_equal(_stack(g), np.asarray(w), err_msg=name)


def _interpret(fn, *args):
    """fn under jit on the CPU with the kernels interpreted."""
    return jax.jit(fn).lower(*args).compile(compiler_options=_NO_FUSION)(*args)


def _to_lanes(a, c):
    """(C*V, 20) -> (C, 20, V/128, 128)."""
    n = a.shape[0]
    return np.asarray(a).reshape(c, n // c // K.LANES, K.LANES, L).transpose(0, 3, 1, 2)


def test_window_kernel_interpreted_matches_xla_scan():
    """stage2_window at 2 commits x 128 validators, 2 windows x 2
    splits, against the XLA scan's own steps on the same digits —
    digit 0, +-8 and negative digits among them: the grid's order, the
    resident accumulator over the commit axis, one table a validator
    read by both commits."""
    rng = np.random.default_rng(40)
    c, v, n_w, n_m = 2, 128, 2, 2
    n = c * v
    tables = rng.integers(0, F.WEAK_MAX + 1, size=(v, n_m, 8, 3 * L), dtype=np.int32)
    kd = rng.integers(-8, 9, size=(n_w, n, n_m)).astype(np.int32)
    kd[:, :4], kd[:, 4:8], kd[:, 8:12] = 0, -8, 8
    acc = curve.identity((n,))
    for w in range(n_w):
        acc = curve._window_doublings(acc)
        for m in range(n_m):
            acc = curve.madd(
                acc, curve._select_affine(jnp.asarray(tables[:, m]), jnp.asarray(kd[w, :, m]))
            )
    kdw = kd.transpose(0, 2, 1).reshape(n_w, n_m, c, v // K.LANES, K.LANES)
    tables_t = tables.transpose(1, 2, 3, 0).reshape(n_m, 8, 3 * L, v // K.LANES, K.LANES)
    got = _interpret(lambda a, b: K.windows(a, b, tb=1, interpret=True), kdw, tables_t)
    for i, name in enumerate("xyzt"):
        np.testing.assert_array_equal(np.asarray(got)[:, i], _to_lanes(acc[i], c), err_msg=name)


def test_comb_kernel_interpreted_matches_xla_madds():
    """stage2_comb over two digit positions: the sign handling of the
    MXU-selected entries inside the kernel, T kept by the first
    addition and zero after the last."""
    rng = np.random.default_rng(41)
    c, v, n_p = 2, 128, 2
    n = c * v
    acc0 = _weak(rng, 4, n)
    sd = rng.integers(-128, 128, size=(n, n_p)).astype(np.int32)
    sd[:4], sd[4:8], sd[8:12] = 0, -128, 127
    sd32 = np.pad(sd, ((0, 0), (0, 32 - n_p)))  # the comb table holds 32 positions
    sel = np.asarray(jax.jit(lambda d: curve._comb256_entries(jnp.abs(d)))(sd32))
    combs = jax.jit(curve._select_comb256)(sd32)
    acc = curve.Point(*(jnp.asarray(x) for x in acc0))
    for p in range(n_p):
        acc = curve.madd(
            acc, curve.AffineCached(combs.ypx[:, p], combs.ymx[:, p], combs.t2d[:, p]),
            want_t=p < n_p - 1,
        )
    lanes = (c, v // K.LANES, K.LANES)
    got = _interpret(
        lambda a, b, d: K.comb(a, b, d, tb=1, interpret=True),
        sd.T.reshape((n_p,) + lanes),
        sel[:, :n_p].transpose(1, 2, 0).reshape((n_p, 3 * L) + lanes),
        np.stack([_to_lanes(x, c) for x in acc0], axis=1),
    )
    for i, name in enumerate("xyzt"):
        np.testing.assert_array_equal(np.asarray(got)[:, i], _to_lanes(acc[i], c), err_msg=name)


def _stage2_inputs(rng, v, c):
    n = v * c
    tables = rng.integers(0, F.WEAK_MAX + 1, size=(v, curve.SPLITS, 8, 3 * L), dtype=np.int32)
    kd = rng.integers(-8, 8, size=(n, 64)).astype(np.int32)
    sd = rng.integers(-128, 128, size=(n, 32)).astype(np.int32)
    kd[:4], kd[4:8], sd[:4], sd[4:8] = 0, -8, 0, -128
    return sd, kd, tables


@pytest.mark.slow
def test_whole_stage_interpreted_matches_xla_body():
    """The kernel-form body end to end (both kernels, the digit and
    table hand-over, the way back to (N, 20)) at 2 commits x 128
    validators against curve.double_scalar_mul_tabled. Minutes on the
    CPU: the one test of this file outside tier-1."""
    sd, kd, tables = _stage2_inputs(np.random.default_rng(42), 128, 2)
    want = jax.jit(curve.double_scalar_mul_tabled)(sd, kd, tables)
    got = _interpret(
        lambda a, b, t: K.double_scalar_mul_slots_kernel(a, b, t, tb=1, interpret=True),
        sd, kd, tables,
    )
    for g, w, name in zip(got, want, "xyzt"):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_kernel_form_rule():
    assert K.kernel_form(1024, "tpu") and K.kernel_form(10240, "tpu")
    assert not K.kernel_form(1024, "cpu") and not K.kernel_form(10240, "gpu")
    assert not K.kernel_form(128, "tpu") and not K.kernel_form(1000, "tpu")


def test_generic_buckets_on_the_rule():
    """The generic family's buckets: 1,024 rows and up take the kernel
    form on a TPU, the three below it keep the XLA body."""
    from tendermint_tpu.models.verifier import _BUCKETS

    assert [b for b in _BUCKETS if K.kernel_form(b, "tpu")] == [1024, 4096, 10240, 16384]
    assert not any(K.kernel_form(b, "cpu") for b in _BUCKETS)


def _lowered(fn, platform, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,)).as_text()


def test_body_is_chosen_by_lowering_platform_and_shape():
    """t-scan-s at a V on the rule holds the two Mosaic calls when it is
    lowered for a TPU and none when lowered for the CPU — one trace,
    two lowerings, no knob; off the rule it IS the XLA body, lowered for a
    TPU too: the same StableHLO text as curve.double_scalar_mul_tabled's
    own lowering."""
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    table = lambda v: S((v, curve.SPLITS, 8, 3 * L), i32)  # noqa: E731
    v = FK.BLOCK_ROWS
    traced = jax.jit(E.verify_stage_scan_tabled_slots).trace(
        S((v, 32), i32), S((v, 64), i32), table(v), S((v,), jnp.bool_)
    )
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 2
    assert "stage2_window" in tpu and "stage2_comb" in tpu
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in cpu and "stage2_" not in cpu

    def xla_body(sd, kd, tables):
        return curve.double_scalar_mul_tabled(sd, kd, tables)

    def slots_body(sd, kd, tables):
        return K.double_scalar_mul_slots(sd, kd, tables)

    args = S((32, 32), i32), S((32, 64), i32), table(16)
    got = _lowered(slots_body, "tpu", *args).replace("slots_body", "xla_body")
    assert got == _lowered(xla_body, "tpu", *args)


def test_gathered_sharded_and_generic_programs_hold_no_kernel():
    """The other stage-2 families keep the XLA body whatever platform
    they are lowered for, and the generic one off its rule (16 rows)."""
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    n = 16
    sd, kd, idx = S((n, 32), i32), S((n, 64), i32), S((n,), i32)
    tables, a_ok = S((n, curve.SPLITS, 8, 3 * L), i32), S((n,), jnp.bool_)
    limbs = S((n, L), i32)
    for fn, args in (
        (E.verify_stage_scan_tabled, (sd, kd, tables, a_ok, idx)),
        (E.verify_stage_scan_tabled_sharded, (sd, kd, a_ok, idx, (tables, tables))),
        (E.verify_stage_scan, (kd, kd, limbs, limbs, limbs, limbs)),
    ):
        assert "tpu_custom_call" not in _lowered(fn, "tpu", *args), fn.__name__


def _scan_args(n):
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    return (S((n, 64), i32),) * 2 + (S((n, L), i32),) * 4


def test_generic_body_is_chosen_by_lowering_platform_and_shape():
    """verify_stage_scan at 1,024 rows holds exactly one Mosaic call,
    generic_scan, lowered for a TPU, and none lowered for the CPU; at 16
    rows, lowered for a TPU, it IS the XLA body: the same StableHLO text
    as curve.double_scalar_mul_signed's own lowering."""
    traced = jax.jit(E.verify_stage_scan).trace(*_scan_args(FK.BLOCK_ROWS))
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 1 and "generic_scan" in tpu
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in cpu and "generic_scan" not in cpu

    def xla_body(sd, kd, nx, ny, nz, nt):
        p = curve.double_scalar_mul_signed(sd, kd, curve.Point(nx, ny, nz, nt))
        return p.x, p.y, p.z, p.t

    def verify_stage_scan(*args):
        return E.verify_stage_scan(*args)

    got = _lowered(verify_stage_scan, "tpu", *_scan_args(16)).replace("verify_stage_scan", "xla_body")
    assert got == _lowered(xla_body, "tpu", *_scan_args(16))


def test_generic_kernel_rows_follow_the_rule(monkeypatch):
    """What a generic launch adds to kernel_rows: its rows where each
    device's share is on the rule, else 0 — on the CPU always 0."""
    from tendermint_tpu.models import verifier as mv

    class Mesh4:
        shape = {"batch": 4}

    m = mv.VerifierModel(block_on_compile=True)
    assert [m._kernel_rows(b) for b in (256, 1024, 16384)] == [0, 0, 0]
    monkeypatch.setattr(mv.jax, "default_backend", lambda: "tpu")
    assert [m._kernel_rows(b) for b in (256, 1024, 4096, 16384)] == [0, 1024, 4096, 16384]
    m.mesh = Mesh4()
    assert [m._kernel_rows(b) for b in (1024, 4096, 10240, 16384)] == [0, 4096, 0, 16384]


def test_kernel_slots_stay_zero_on_the_cpu():
    """A whole commit in slot order through the model on the CPU: the
    rows are counted as slot rows and none as kernel slots, and the
    counter is published beside the others."""
    from tendermint_tpu.crypto.batch import TABLED_COUNTS
    from tendermint_tpu.models.verifier import VerifierModel
    from tendermint_tpu.utils.metrics import CryptoMetrics
    from tests.tabled_helpers import arrs, sign_rows

    pk, mg, sg = arrs(*sign_rows(16, msg_len=160, seed=23))
    before = TABLED_COUNTS.snapshot()
    ok = VerifierModel(block_on_compile=True).verify_rows_cached(
        b"kernel-slots", pk, np.arange(16, dtype=np.int32), mg, sg
    )
    assert ok is not None and ok.all()
    after = TABLED_COUNTS.snapshot()
    assert after["tabled_slot_rows"] - before["tabled_slot_rows"] == 16
    assert after["tabled_kernel_slots"] == before["tabled_kernel_slots"] == 0
    assert ("tabled_kernel_slots", "tabled_kernel_slots") in CryptoMetrics._COUNTERS
    assert ("generic_kernel_rows", "generic_kernel_rows") in CryptoMetrics._COUNTERS
