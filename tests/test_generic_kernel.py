"""The generic family's stage 2 as one Pallas kernel
(``ops/stage2_kernel.generic_scan``), interpreted on the CPU, against
``curve.double_scalar_mul_signed``, which it must equal bit for bit.

The rows are what stage 1 makes of a batch: signed rows under their own
keys with the batch's witnesses (a key with no square root among them),
zero rows as a bucket's padding, and rows whose digits are all zero or
sit at the ends of the signed range. Two blocks of 128 rows at tb = 1,
all 64 windows: the table built anew for each block, the accumulator
resident over the windows. In its own file because the interpreted body
takes minutes to compile (``tests/test_stage2_kernel.py``'s _NO_FUSION).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from perfbench.generators import independent_batch as gen  # noqa: E402
from tendermint_tpu.ops import curve  # noqa: E402
from tendermint_tpu.ops import ed25519 as E  # noqa: E402
from tendermint_tpu.ops import stage2_kernel as K  # noqa: E402
from tests.test_stage2_kernel import _interpret  # noqa: E402

ROWS, SIGNED = 256, 200


def _stage1_rows():
    config = {"rows_per_batch": SIGNED, "msg_len": 92, "key_type": "ed25519"}
    params = {"batches": 1, "window_rows": SIGNED, "witnesses": list(gen.KINDS)}
    b = gen.generate(config, params, 2**31 + 38)["batches"][0]
    assert "key_no_root" in {k for _, k in b["witnesses"]}
    pad = lambda a: np.pad(a, ((0, ROWS - SIGNED), (0, 0)))  # noqa: E731
    sd, kd, nx, ny, nz, nt, a_ok, _ = jax.jit(E.verify_stage_prepare)(
        pad(b["pubkeys"]), pad(b["msgs"]), pad(b["sigs"])
    )
    assert not np.asarray(a_ok).all()  # the witness key did not decompress
    sd, kd = np.array(sd), np.array(kd)
    sd[:2], kd[:2] = 0, 0
    sd[2:4], kd[2:4] = -8, 7
    sd[4:6], kd[4:6] = 7, -8
    sd[130], kd[131] = 0, -8  # in the second block too
    return sd, kd, curve.Point(nx, ny, nz, nt)


def test_generic_kernel_interpreted_matches_the_xla_scan():
    sd, kd, q = _stage1_rows()
    want = jax.jit(curve.double_scalar_mul_signed)(sd, kd, q)
    got = _interpret(
        lambda a, b, c: K.double_scalar_mul_rows_kernel(a, b, c, tb=1, interpret=True), sd, kd, q
    )
    for g, w, name in zip(got, want, "xyzt"):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
