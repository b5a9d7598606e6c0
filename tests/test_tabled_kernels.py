"""Per-valset cached-table verify path (round 3): the stage kernels.

The tabled pipeline (ops/ed25519.verify_stage_*_tabled +
curve.build_split_tables) must accept EXACTLY the signatures the generic
kernel and the host reference accept — it is an optimization of the
same Go x/crypto acceptance (crypto/ed25519/ed25519.go:151), keyed on
the fact that validator pubkeys are stable across heights
(types/validator_set.go:641 re-verifies the same keys every block).

One of four files (with test_tabled_verify.py, the model's cached
path, test_tabled_templated.py, the commit-shaped path, and
test_tabled_batches.py, sharded tables and cross-height batches): under
the tier-1 run's --dist loadfile a file is one worker's, and the
families' programs compile side by side.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import curve, ed25519 as E, field as F, ref_ed25519 as ref
from tests.tabled_helpers import arrs, sign_rows


# Module-level jitted wrappers: a fresh jax.jit() per call would retrace
# every time; one wrapper per stage keeps the whole file to one compile
# per distinct shape.
_BUILD = jax.jit(E.build_valset_tables)
_S1 = jax.jit(E.verify_stage_prepare_tabled)
_S2 = jax.jit(E.verify_stage_scan_tabled)
_S3 = jax.jit(E.verify_stage_finish_blocked)


def _tabled_ok(pk, mg, sg, idx=None, tables=None, a_ok=None):
    pk, mg, sg = jnp.asarray(pk), jnp.asarray(mg), jnp.asarray(sg)
    if tables is None:
        tables, a_ok = _BUILD(pk)
    if idx is None:
        idx = jnp.arange(pk.shape[0], dtype=jnp.int32)
    sd, kd, s_ok = _S1(pk, mg, sg)
    px, py, pz, pt, aok = _S2(sd, kd, tables, a_ok, jnp.asarray(idx))
    return np.asarray(_S3(px, py, pz, pt, sg, aok, s_ok))


def test_invert_blocked_matches_fermat():
    rng = np.random.default_rng(3)
    vals = [int(rng.integers(1, 2**62)) ** 2 % F.P for _ in range(48)]
    vals[5] = 0
    vals[17] = F.P - 1
    z = jnp.asarray(np.stack([F.to_limbs(v) for v in vals]))
    inv = np.asarray(jax.jit(F.invert_blocked)(z))
    for i, v in enumerate(vals):
        assert F.from_limbs(inv[i]) == (pow(v, F.P - 2, F.P) if v else 0)


def test_split_tables_are_reference_multiples():
    q_ref = ref.pt_mul(11, ref.pt_from_affine(*ref.BASE))
    qx, qy = ref.pt_to_affine(q_ref)
    pt = curve.Point(
        jnp.asarray(F.to_limbs(qx))[None],
        jnp.asarray(F.to_limbs(qy))[None],
        jnp.asarray(F.to_limbs(1))[None],
        jnp.asarray(F.to_limbs(qx * qy % ref.P))[None],
    )
    tbl = np.asarray(jax.jit(curve.build_split_tables)(pt))
    for m in (0, 3, curve.SPLITS - 1):
        for i in (0, 7):
            want = ref.pt_to_affine(
                ref.pt_mul((i + 1) * 16 ** (curve.SPLIT_W * m), q_ref)
            )
            got = tbl[0, m, i].reshape(3, F.LIMBS)
            assert F.from_limbs(got[0]) == (want[1] + want[0]) % ref.P
            assert F.from_limbs(got[1]) == (want[1] - want[0]) % ref.P
            assert F.from_limbs(got[2]) == 2 * ref.D * want[0] * want[1] % ref.P


def test_tabled_matches_generic_and_reference():
    pks, msgs, sigs = sign_rows(16)
    # corruptions across every rejection class
    sigs[1] = sigs[1][:5] + bytes([sigs[1][5] ^ 0x40]) + sigs[1][6:]  # bad R
    sigs[2] = sigs[2][:33] + bytes([sigs[2][33] ^ 1]) + sigs[2][34:]  # bad s
    sigs[4] = sigs[4][:32] + (
        int.from_bytes(sigs[4][32:], "little") + ref.L
    ).to_bytes(32, "little")  # non-canonical s
    msgs[6] = msgs[6][:-1] + bytes([msgs[6][-1] ^ 1])  # wrong msg
    pk, mg, sg = arrs(pks, msgs, sigs)
    want = np.array([ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)])
    assert not want.all() and want.any()
    generic = np.asarray(
        jax.jit(E.verify_core)(jnp.asarray(pk), jnp.asarray(mg), jnp.asarray(sg))
    )
    tabled = _tabled_ok(pk, mg, sg)
    np.testing.assert_array_equal(generic, want)
    np.testing.assert_array_equal(tabled, want)


def test_tabled_gather_subset_and_duplicates():
    pks, msgs, sigs = sign_rows(16, seed=9)
    pk, mg, sg = arrs(pks, msgs, sigs)
    tables, a_ok = _BUILD(jnp.asarray(pk))
    # subset with a duplicate validator index (trusting-path shape);
    # length 16 keeps the stage shapes shared with the other tests
    idx = np.array([3, 3, 8, 15, 0, 12, 1, 2, 4, 5, 6, 7, 9, 10, 11, 14], dtype=np.int32)
    ok = _tabled_ok(pk[idx], mg[idx], sg[idx], idx=idx, tables=tables, a_ok=a_ok)
    assert ok.all()
    # same subset, one row signed by the WRONG validator's key
    sg2 = sg[idx].copy()
    sg2[2] = sg[1]
    want = np.ones(16, dtype=bool)
    want[2] = False
    ok2 = _tabled_ok(pk[idx], mg[idx], sg2, idx=idx, tables=tables, a_ok=a_ok)
    np.testing.assert_array_equal(ok2, want)


def test_tabled_rejects_non_decompressible_key():
    pks, msgs, sigs = sign_rows(16, seed=11)
    bad_y = next(c for c in range(2, 100) if ref._recover_x(c, 0) is None)
    pks[0] = bad_y.to_bytes(32, "little")
    pk, mg, sg = arrs(pks, msgs, sigs)
    ok = _tabled_ok(pk, mg, sg)
    want = np.array([ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)])
    assert not want[0]
    np.testing.assert_array_equal(ok, want)
