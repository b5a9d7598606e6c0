"""The per-row exponentiation chains as one Pallas kernel
(``ops/field_kernel.field_chain``), interpreted on the CPU, against the
XLA chains ``F.pow22523`` and ``F.invert``, which it must equal bit for
bit; then ``curve.decompress`` and ``curve.encode`` with the kernel in
the chain's place against their XLA form, on stage-1 and stage-2 rows of
a signed batch with every witness kind of the batch cell's generator.

Two blocks of 128 rows at tb = 1. The interpreted kernel is compiled
once a tail and called on concrete rows (decompress and encode run
eagerly around it), because its body takes about a minute to compile
(``tests/test_stage2_kernel.py``'s _NO_FUSION); in its own file for
that reason.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from perfbench.generators import independent_batch as gen  # noqa: E402
from tendermint_tpu.ops import curve  # noqa: E402
from tendermint_tpu.ops import ed25519 as E  # noqa: E402
from tendermint_tpu.ops import field as F  # noqa: E402
from tendermint_tpu.ops import field_kernel as FK  # noqa: E402
from tests.test_stage2_kernel import _NO_FUSION  # noqa: E402

L = F.LIMBS
ROWS, SIGNED = 256, 200
_XLA = {"pow22523": F.pow22523, "invert": F.invert}
_EXPONENT = {"pow22523": 2**252 - 3, "invert": F.P - 2}


@functools.lru_cache(maxsize=None)
def _kernel(tail):
    """The interpreted kernel for (ROWS, 20) rows, compiled once a tail."""
    fn = jax.jit(lambda z: FK.chain_rows_kernel(z, tail, tb=1, interpret=True))
    return fn.lower(jax.ShapeDtypeStruct((ROWS, L), jnp.int32)).compile(compiler_options=_NO_FUSION)


def _raw_limbs(x):
    """x in 20 limbs without reducing it mod p (p and 2^255-1 stay
    themselves: non-canonical, as a from_bytes of such bytes gives)."""
    return np.array([(x >> (F.SHIFT * i)) & F.MASK for i in range(L - 1)] + [x >> (F.SHIFT * (L - 1))], np.int32)


def _chain_inputs():
    rng = np.random.default_rng(40)
    z = rng.integers(0, F.WEAK_MAX + 1, size=(ROWS, L), dtype=np.int32)
    edges = [0, 1, F.P - 1, F.P, 2**255 - 1]
    for i, x in enumerate(edges):
        z[i] = z[128 + i] = _raw_limbs(x)  # in both blocks
    z[len(edges)] = F.WEAK_MAX  # every limb at the weak bound
    z[len(edges) + 1] = 0
    z[len(edges) + 1, ::3] = F.WEAK_MAX
    return z


@pytest.mark.parametrize("tail", sorted(_XLA))
def test_chain_kernel_interpreted_matches_the_xla_chain(tail):
    z = _chain_inputs()
    got = np.asarray(_kernel(tail)(z))
    want = np.asarray(jax.jit(_XLA[tail])(z))
    np.testing.assert_array_equal(got, want)
    assert got.min() >= 0 and got.max() <= F.WEAK_MAX
    assert not got[0].any() and not got[128].any()  # 0 -> 0 limb for limb, as ref10's invert(0)
    for r in (1, 2, 3, 4, 5, 6, 7, 131, 200):
        assert F.from_limbs(got[r]) == pow(F.from_limbs(z[r]), _EXPONENT[tail], F.P), r


@functools.lru_cache(maxsize=None)
def _batch():
    """Stage-1 rows of a signed batch with one row of each witness kind,
    zero rows as a bucket's padding."""
    config = {"rows_per_batch": SIGNED, "msg_len": 92, "key_type": "ed25519"}
    params = {"batches": 1, "window_rows": SIGNED, "witnesses": list(gen.KINDS)}
    b = gen.generate(config, params, 2**31 + 40)["batches"][0]
    assert {k for _, k in b["witnesses"]} == set(gen.KINDS)
    pad = lambda a: np.pad(a, ((0, ROWS - SIGNED), (0, 0)))  # noqa: E731
    return pad(b["pubkeys"]), pad(b["msgs"]), pad(b["sigs"]), dict(b["witnesses"])


def _assert_points(got, want):
    for g, w, name in zip(got, want, "xyzt"):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_decompress_kernel_form_matches_the_xla_form(monkeypatch):
    """Every key of the batch, the one with no square root and the
    padding's zero keys among them: the same point and the same ok."""
    pk, _, _, _ = _batch()
    want, want_ok = jax.jit(curve.decompress)(pk)
    monkeypatch.setattr(FK, "pow22523", _kernel("pow22523"))
    got, got_ok = curve.decompress(jnp.asarray(pk))
    _assert_points(got, want)
    np.testing.assert_array_equal(np.asarray(got_ok), np.asarray(want_ok))
    assert not np.asarray(got_ok)[:SIGNED].all() and np.asarray(got_ok)[SIGNED:].all()


def test_encode_kernel_form_matches_the_xla_form(monkeypatch):
    """Stage 3 on stage 2's points for the batch: the same bytes, and the
    verdicts the XLA form gives — every witness row rejected, every
    other signed row accepted."""
    pk, msgs, sigs, witnesses = _batch()
    sd, kd, nx, ny, nz, nt, a_ok, s_ok = jax.jit(E.verify_stage_prepare)(pk, msgs, sigs)
    p = jax.jit(curve.double_scalar_mul_signed)(sd, kd, curve.Point(nx, ny, nz, nt))
    want = np.asarray(jax.jit(curve.encode)(p))
    monkeypatch.setattr(FK, "invert", _kernel("invert"))
    got = np.asarray(curve.encode(p))
    np.testing.assert_array_equal(got, want)
    ok = (got == sigs[:, :32]).all(axis=1) & np.asarray(a_ok) & np.asarray(s_ok)
    assert sorted(np.nonzero(~ok[:SIGNED])[0]) == sorted(witnesses)


def test_encode_of_a_zero_z_is_zero_as_ref10(monkeypatch):
    """Z = 0 (no point; ref10 inverts 0 to 0): x = y = 0, so 32 zero
    bytes, in both forms."""
    rng = np.random.default_rng(41)
    x, y, t = (jnp.asarray(rng.integers(0, F.WEAK_MAX + 1, size=(ROWS, L), dtype=np.int32)) for _ in range(3))
    z = rng.integers(0, F.WEAK_MAX + 1, size=(ROWS, L), dtype=np.int32)
    z[::2] = 0
    p = curve.Point(x, y, jnp.asarray(z), t)
    want = np.asarray(jax.jit(curve.encode)(p))
    monkeypatch.setattr(FK, "invert", _kernel("invert"))
    got = np.asarray(curve.encode(p))
    np.testing.assert_array_equal(got, want)
    assert not got[::2].any() and got[1::2].any(axis=1).all()


def _lowered(fn, platform, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=(platform,)).as_text()


def _stage_args(n):
    S, u8, i32 = jax.ShapeDtypeStruct, jnp.uint8, jnp.int32
    limbs = S((n, L), i32)
    return {
        "prepare": (E.verify_stage_prepare, (S((n, 32), u8), S((n, 92), u8), S((n, 64), u8))),
        "finish": (
            E.verify_stage_finish,
            (limbs,) * 4 + (S((n, 64), u8), S((n,), jnp.bool_), S((n,), jnp.bool_)),
        ),
        "tables": (E.build_valset_tables, (S((n, 32), u8),)),
    }


@pytest.mark.parametrize("stage", ["prepare", "finish", "tables"])
def test_chain_is_chosen_by_lowering_platform(stage):
    """At 1,024 rows the generic prepare (decompression), the generic
    finish (the encode's inversion) and the table build (decompression)
    hold exactly one Mosaic call, field_chain, lowered for a TPU, and
    none lowered for the CPU: one trace, two lowerings, no knob."""
    fn, args = _stage_args(FK.BLOCK_ROWS)[stage]
    traced = jax.jit(fn).trace(*args)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert tpu.count("tpu_custom_call") == 1 and "field_chain" in tpu
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert "tpu_custom_call" not in cpu and "field_chain" not in cpu


@pytest.mark.parametrize("tail", sorted(_XLA))
def test_off_the_rule_the_chain_is_the_xla_chain(tail):
    """Rows off the rule (a 16-row bucket, 1,000 rows) and a batch of
    another rank, lowered for a TPU, ARE the XLA chain: the same
    StableHLO text as its own lowering."""
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    dispatch = getattr(FK, tail)

    def chain(z):
        return dispatch(z)

    def xla_chain(z):
        return _XLA[tail](z)

    for shape in ((16, L), (1000, L), (2, FK.BLOCK_ROWS, L)):
        got = _lowered(chain, "tpu", S(shape, i32)).replace("chain", "xla_chain")
        assert got == _lowered(xla_chain, "tpu", S(shape, i32)), shape


def test_tabled_finish_keeps_the_blocked_inversion():
    """The tabled finish inverts by the blocked Montgomery form, whose
    one width-g chain is F.invert itself: no kernel on the rule either."""
    S, u8, i32 = jax.ShapeDtypeStruct, jnp.uint8, jnp.int32
    n = 16 * FK.BLOCK_ROWS
    limbs = S((n, L), i32)
    args = (limbs,) * 4 + (S((n, 64), u8), S((n,), jnp.bool_), S((n,), jnp.bool_))
    assert "tpu_custom_call" not in _lowered(E.verify_stage_finish_blocked, "tpu", *args)
