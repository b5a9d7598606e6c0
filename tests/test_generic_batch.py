"""The generic verify family on a bare batch of independent rows, through
the node's provider stack (``PipelinedVerifier`` over ``TPUBatchVerifier``):
a batch past ``MAX_DEVICE_ROWS`` streams as full windows and a bucketed
tail, every row's verdict equals ``cryptography``'s, and the family's
counters (``crypto/batch.GENERIC_COUNTS``, ``H2D_COUNTS``) and
``generic.launch`` spans (each around its ``launch.stage`` and
``launch.dispatch``) say what was launched. ``MAX_DEVICE_ROWS`` is shrunk to 16, so 40 rows
are 2 windows and an 8-row tail padded to the same 16-row bucket: one
compile, at the payments message width of 92 bytes.
"""

import numpy as np
import pytest

from perfbench.generators import independent_batch as gen
from perfbench.reference import batch as ref

ROWS, WINDOW = 40, 16
KINDS = list(gen.KINDS)


@pytest.fixture(scope="module")
def batch():
    config = {"rows_per_batch": ROWS, "msg_len": 92, "key_type": "ed25519"}
    params = {"batches": 1, "window_rows": WINDOW, "witnesses": KINDS}
    b = gen.generate(config, params, 2**31 + 37)["batches"][0]
    want = ref.batch_answer(b["pubkeys"], b["msgs"], b["sigs"])["rows"]
    return b, want


@pytest.fixture(scope="module")
def provider():
    import tendermint_tpu.models.verifier as mv
    from tendermint_tpu.crypto.batch import TPUBatchVerifier
    from tendermint_tpu.crypto.pipeline import PipelinedVerifier, SigCache

    mp = pytest.MonkeyPatch()
    mp.setattr(mv, "MAX_DEVICE_ROWS", WINDOW)
    with PipelinedVerifier(TPUBatchVerifier(), cache=SigCache()) as pv:
        yield pv
    mp.undo()


def test_the_batch_carries_four_rejected_rows_in_each_window_and_the_tail(batch):
    b, want = batch
    assert len(set(b["pubkeys"][:, 5].tolist())) > 1 and b["msgs"].shape == (ROWS, 92)
    rejected = np.flatnonzero(~want)
    assert sorted(r for r, _ in b["witnesses"]) == rejected.tolist()
    for lo, hi in ((0, 16), (16, 32), (32, 40)):
        assert sorted(k for r, k in b["witnesses"] if lo <= r < hi) == sorted(KINDS)


def test_windowed_verdicts_equal_the_reference_and_counters_say_what_ran(batch, provider):
    from tendermint_tpu.crypto.batch import GENERIC_COUNTS
    from tendermint_tpu.utils import trace

    b, want = batch
    before = provider.engine_stats()["counters"]
    assert set(GENERIC_COUNTS.snapshot()) <= set(before)
    old = trace.get_tracer()
    tracer = trace.set_tracer(trace.Tracer(enabled=True))
    try:
        got = provider.verify_batch(b["pubkeys"], b["msgs"], b["sigs"])
    finally:
        trace.set_tracer(old)
    np.testing.assert_array_equal(np.asarray(got, dtype=bool), want)

    after = provider.engine_stats()["counters"]
    grew = {
        k: after[k] - before[k]
        for k in ("generic_rows", "generic_pad_rows", "generic_windows", "generic_launches", "generic_kernel_rows")
    }
    # the CPU lowers stage 2 to the XLA body: no row reaches the kernel form
    assert grew == {
        "generic_rows": 40, "generic_pad_rows": 8, "generic_windows": 2, "generic_launches": 3,
        "generic_kernel_rows": 0,
    }
    assert provider.stats()["generic_launches"] == after["generic_launches"]

    launches = [e for e in tracer._snapshot() if e[1] == "generic.launch"]
    assert [(e[5]["rows"], e[5]["bucket"]) for e in launches] == [(16, 16), (16, 16), (8, 16)]
    # each launch stages and dispatches inside its generic.launch span; the
    # windows' verdicts are read back once, the tail's once
    inner = [
        (e[1], e[5].get("parent")) for e in tracer._snapshot()
        if e[1].startswith("launch.") and e[5].get("parent") == "generic.launch"
    ]
    assert inner == [("launch.stage", "generic.launch"), ("launch.dispatch", "generic.launch")] * 3
    assert sum(e[1] == "launch.readback" for e in tracer._snapshot()) == 2
    # every row copied to the device, pad rows included: 48 rows of key, message, signature
    assert after["h2d_bytes"] - before["h2d_bytes"] == 48 * (32 + 92 + 64)


def test_the_launch_span_costs_nothing_with_the_tracer_off(batch, provider):
    from tendermint_tpu.utils import trace

    b, want = batch
    off = trace.Tracer(enabled=False)
    old = trace.set_tracer(off)
    try:
        assert trace.span("generic.launch", rows=1, bucket=16) is trace.NOOP_SPAN
        got = provider.verify_batch(b["pubkeys"], b["msgs"], b["sigs"])
    finally:
        trace.set_tracer(old)
    np.testing.assert_array_equal(np.asarray(got, dtype=bool), want)
    assert off.recorded == 0 and not off._snapshot()

