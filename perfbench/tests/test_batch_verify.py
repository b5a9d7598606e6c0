"""The bare-batch deployment: its generator, its plain reference, its
control, its cell rehearsed on a copy at 40 rows, and its two per-layer
readers on a canned run.
"""

import ast
import hashlib
import json
import os

import numpy as np
import pytest

from perfbench import trace
from perfbench.generators import independent_batch as gen
from perfbench.layer_metrics import generic_pad_pct, generic_us_per_sig
from perfbench.reference import batch as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = json.load(open(os.path.join(BENCH, "traffic", "batch-stream-distinct.json")))
CONFIG = {"rows_per_batch": 40, "msg_len": 92, "key_type": "ed25519"}
PARAMS = {**MIX["generator"]["params"], "window_rows": 16}


def _digest(d) -> str:
    return hashlib.sha256(repr([{k: v.tobytes() if isinstance(v, np.ndarray) else v
                                 for k, v in b.items()} for b in d["batches"]]).encode()).hexdigest()


def test_generator_is_a_function_of_the_seed():
    big = 2**31 + 4242  # seeds pass 32 signed bits
    a, b, c = (gen.generate(CONFIG, PARAMS, s) for s in (big, big, big + 1))
    assert _digest(a) == _digest(b) != _digest(c)


def test_every_window_and_the_tail_hold_one_witness_of_each_kind_and_nothing_else_is_rejected():
    d = gen.generate(CONFIG, PARAMS, 2**31 + 7)
    assert [(lo, hi) for lo, hi in gen.windows(40, 16)] == [(0, 16), (16, 32), (32, 40)]
    first, second = d["batches"]
    assert len({bytes(k) for k in first["pubkeys"]}) == 40  # a key a row
    assert (first["msgs"][:, 36:44] != second["msgs"][:, 36:44]).any()  # the next nonce
    for b in d["batches"]:
        want = ref.batch_answer(b["pubkeys"], b["msgs"], b["sigs"])
        assert want["verdict"] == ref.ACCEPT
        assert sorted(r for r, _ in b["witnesses"]) == np.flatnonzero(~want["rows"]).tolist()
        for lo, hi in gen.windows(40, 16):
            assert sorted(k for r, k in b["witnesses"] if lo <= r < hi) == sorted(gen.KINDS)


def test_the_full_size_places_its_witnesses_as_stated():
    """At 100,000 rows (the placement alone; no row is signed here):
    6 windows of 16,384 and a tail of 1,696, four witnesses each."""
    spans = gen.windows(100_000, 16_384)
    assert len(spans) == 7 and spans[-1] == (98_304, 100_000)
    n = 100_000
    rng = np.random.default_rng(1)
    pk, mg, sg = (np.zeros((n, w), dtype=np.uint8) for w in (32, 92, 64))
    mg[:, 4:8] = np.arange(n, dtype=">u4").view(np.uint8).reshape(n, 4)
    planted = gen._plant(rng, pk, mg, sg, 16_384, gen.KINDS)
    assert len(planted) == 28
    for lo, hi in spans:
        assert sorted(k for r, k in planted if lo <= r < hi) == sorted(gen.KINDS)


def test_off_curve_keys_have_no_point():
    for enc in gen.no_root_keys(4):
        y = int.from_bytes(enc, "little")
        u, v = (y * y - 1) % gen.P, (gen.D * y * y + 1) % gen.P
        x2 = u * pow(v, gen.P - 2, gen.P) % gen.P
        assert y < gen.P and pow(x2, (gen.P - 1) // 2, gen.P) == gen.P - 1


def test_reference_and_generator_import_nothing_of_the_program():
    for rel in ("reference/batch.py", "generators/independent_batch.py"):
        tree = ast.parse(open(os.path.join(BENCH, rel)).read())
        names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)] + [
            a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names
        ]
        assert not [n for n in names if n.startswith(("tendermint_tpu", "jax"))], rel


# -- the cell on a copy, at 40 rows ----------------------------------------------------


@pytest.fixture
def batch_copy(bench_copy):
    body = json.load(open(os.path.join(BENCH, "configs", "batchverify-100k.json")))
    body.update(rows_per_batch=40, crypto_provider="cpu")
    body.pop("name")
    body["window_limits"] = {"compiles_in_window": 0, "fallback_serial": 0}  # the CPU provider serves on the host
    bench_copy.add_config("tiny-batch", body)
    bench_copy.write("traffic/tiny-batch-stream.json", json.dumps({**MIX, "name": "tiny-batch-stream",
                                                                  "generator": {**MIX["generator"], "params": PARAMS}}))
    bench_copy.add_cell("tiny-batch-cell", "tiny-batch", "tiny-batch-stream")
    for metric in bench_copy.manifest["per_layer"]:
        if "batchverify-100k-stream" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-batch-cell")
    bench_copy._save()
    return bench_copy


@pytest.mark.parametrize("seed", [5, 2**31 + 99])
def test_the_cell_rehearses_and_reads_correct(batch_copy, seed):
    rc, line, err = batch_copy.run("tiny-batch-cell", seed=seed, seconds=0.5)
    assert rc == 0, err
    assert line["correct"] is True, line["check"]
    assert line["check"]["rows_compared"]["value"] == 40 * line["check"]["requests_compared"]["value"]
    assert set(line["rehearsal_metrics"]) == {"request_p50_ms", "sigs_per_s", "setup_s"}  # no p95 in this cell


def test_the_control_reads_not_correct(batch_copy):
    rc, line, err = batch_copy.run("tiny-batch-cell", seed=3, seconds=0.3, script="control_batch.py")
    assert rc == 0, err  # a control script exits 0 only when the comparison caught it
    assert line["correct"] is False and line["control"] == "control_full_windows_only"
    assert line["check"]["row_mismatches"]["value"] == 4 * line["check"]["requests_compared"]["value"]


def test_the_traced_rehearsal_leaves_out_what_it_cannot_read(batch_copy):
    """On the CPU provider no generic launch runs and no trace is read:
    the device readers and the generic counters' reader print nothing."""
    rc, line, err = batch_copy.run("tiny-batch-cell", seed=3, seconds=0.5, trace=1)
    assert rc == 0, err
    got = line["rehearsal_metrics"]
    assert set(got) == {"seam_host_ms", "host_rows_pct"}
    assert got["host_rows_pct"]["value"] == 100.0


# -- the readers on a canned run -------------------------------------------------------


def _run(counters0=None, counters1=None, module_s=None, traced_rows=300_000):
    reduced = trace.Reduced(window_s=3.0, busy_s=2.9, chips=1, requests=3, module_s=module_s or {},
                            module_runs={k: 7 for k in (module_s or {})})
    stats = [{"device_rows": 0.0, "host_rows": 0.0}, {"device_rows": 1.0, "host_rows": 0.0}]
    if counters0 is not None:
        stats[0]["counters"], stats[1]["counters"] = counters0, counters1
    return {"engine_stats": tuple(stats), "trace": reduced, "traced_rows": traced_rows}


GENERIC0 = {"generic_rows": 40, "generic_pad_rows": 8, "generic_windows": 2, "generic_launches": 3}


def test_generic_padding_is_the_windows_growth():
    per = {"generic_rows": 100_000, "generic_pad_rows": 2_400, "generic_windows": 6, "generic_launches": 7}
    after = {k: GENERIC0[k] + 20 * per[k] for k in GENERIC0}
    assert generic_pad_pct.read(_run(GENERIC0, after)) == pytest.approx(100.0 * 2_400 / 102_400)
    assert generic_pad_pct.read(_run(GENERIC0, dict(GENERIC0))) is None  # nothing generic in the window
    assert generic_pad_pct.read(_run({}, {"tabled_slot_rows": 9})) is None  # a program without these counters
    assert generic_pad_pct.read(_run()) is None


def test_generic_time_reads_the_three_generic_modules_exactly():
    modules = {
        "jit_verify_stage_prepare": 0.2, "jit_verify_stage_scan": 2.4, "jit_verify_stage_finish": 0.1,
        "jit_verify_stage_scan_tabled_slots": 5.0, "jit_verify_stage_prepare_tabled_slots": 5.0,
    }
    assert generic_us_per_sig.read(_run(module_s=modules)) == pytest.approx(1e6 * 2.7 / 300_000)
    assert generic_us_per_sig.read(_run(module_s={"jit_verify_stage_scan_tabled_slots": 5.0})) is None
    assert generic_us_per_sig.read({**_run(module_s=modules), "trace": None}) is None
    assert generic_us_per_sig.read(_run(module_s=modules, traced_rows=0)) is None
