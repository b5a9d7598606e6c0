"""The deployment whose validator set changes at every height: its
generator, its plain reference, its two controls, its cell rehearsed on
a copy at a small width, and its three per-layer readers on a canned run.
"""

import ast
import hashlib
import json
import os

import numpy as np
import pytest

from perfbench import trace
from perfbench.generators import signed_chain_sets
from perfbench.layer_metrics import slot_pad_pct, table_slab_roofline, tabled_rows_pct
from perfbench.reference import chain_sets, encoding as enc
from perfbench.rooflines import table_slab

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["TPU v5 lite"]

CONFIG = {
    "validators": 16, "voting_power": 10, "key_type": "ed25519", "sign_bytes_len": 160,
    "heights": 8, "valset_change_per_height": 1, "chain_id": "pb-sets", "block_time_ns": 10**9,
}
PARAMS = json.load(open(os.path.join(BENCH, "traffic", "seq-chain-128-var1.json")))["generator"]["params"]


def _digest(d) -> str:
    return hashlib.sha256(repr(d).encode()).hexdigest()


def test_generator_is_a_function_of_the_seed():
    big = 2**31 + 4242  # the driver's seeds pass 32 signed bits
    a, b, c = (signed_chain_sets.generate(CONFIG, PARAMS, s) for s in (big, big, big + 1))
    assert _digest(a) == _digest(b) != _digest(c)
    assert sorted(a["rows"][1:]) == sorted(c["rows"][1:])  # the same commits in another order


@pytest.mark.parametrize("churn", [1, 3])
def test_sets_change_as_the_deployment_says_and_headers_link_them(churn):
    d = signed_chain_sets.generate({**CONFIG, "valset_change_per_height": churn}, PARAMS, 7)
    sets = [s["pubkeys"] for s in d["sets"]]
    assert len(sets) == len(d["chain"]) == 9 and d["validators"] == d["sets"][0]
    for j, (old, new) in enumerate(zip(sets, sets[1:])):
        assert len(new) == 16 and len(set(old) & set(new)) == 16 - churn  # the size stays
        assert [enc.address(k) for k in new] == sorted(enc.address(k) for k in new)  # held in address order
        h, nxt = d["chain"][j]["header"], d["chain"][j + 1]["header"]
        assert h["validators_hash"] == enc.validator_set_hash(old, [10] * 16)
        assert h["next_validators_hash"] == nxt["validators_hash"] != h["validators_hash"]
    distinct = {k for s in sets for k in s}
    assert len(distinct) == 16 + 8 * churn  # one key table a validator, not one a set
    gone = [k for k in sets[0] if k not in sets[1]]
    assert all(k not in s for k in gone for s in sets[1:])  # a key that left stays out


def test_reference_accepts_the_chain_and_names_every_forged_row():
    d = signed_chain_sets.generate(CONFIG, PARAMS, 2**31 + 5)
    commits = chain_sets.commit_answers(d["sets"][1:], d["chain_id"], [el["commit"] for el in d["chain"][1:]])
    answer = chain_sets.chain_answer(d["sets"], d["chain_id"], d["chain"], commits, 10**18, d["now_ns"])
    assert answer["verdict"] == chain_sets.ACCEPT
    assert len(answer["rows"]) == sum(d["rows"][1:]) and int((~answer["rows"]).sum()) == 2  # one in every 4 of 8


def _foreign_link(d):
    """Link 3 and its set taken from the chain the same seed makes under
    another churn: a sound header and commit of the right height and
    time, whose set is not the one header 3 announced."""
    other = signed_chain_sets.generate({**CONFIG, "valset_change_per_height": 3}, PARAMS, 11)
    d["chain"][3], d["sets"][3] = other["chain"][3], other["sets"][3]


@pytest.mark.parametrize(
    "name,edit,said",
    [
        ("a set handed over that is not the header's", lambda d: d["sets"].__setitem__(3, d["sets"][2]),
         "validators do not match those supplied"),
        ("a header whose set the one before did not announce", _foreign_link,
         "old header next validators do not match"),
    ],
)
def test_reference_rejects_a_broken_link_between_sets(name, edit, said):
    d = signed_chain_sets.generate(CONFIG, PARAMS, 11)
    edit(d)
    answer = chain_sets.chain_answer(d["sets"], d["chain_id"], d["chain"], [], 10**18, d["now_ns"])
    assert answer["verdict"] == ("invalid_header", 2, said)


def test_reference_and_generator_import_nothing_of_the_program():
    for rel in ("reference/chain_sets.py", "generators/signed_chain_sets.py", "rooflines/table_slab.py"):
        tree = ast.parse(open(os.path.join(BENCH, rel)).read())
        names = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)] + [
            a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names
        ]
        assert not [n for n in names if n.startswith(("tendermint_tpu", "jax"))], rel


# -- the cell on a copy, at a small width ---------------------------------------------


@pytest.fixture
def dyn_copy(bench_copy):
    body = json.load(open(os.path.join(BENCH, "configs", "light-1k-dynamic.json")))
    body.update(validators=16, heights=12, crypto_provider="cpu", chain_id="pb-tinydyn")
    body.pop("name")
    body["window_limits"] = {"compiles_in_window": 0, "fallback_serial": 0, "table_keys_built": 0}
    bench_copy.add_config("tiny-dyn", body)
    bench_copy.add_cell("tiny-dyn-cell", "tiny-dyn", "seq-chain-128-var1")
    for metric in bench_copy.manifest["per_layer"]:
        if "light-1k-dynamic-seq128" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-dyn-cell")
    bench_copy._save()
    return bench_copy


@pytest.mark.parametrize("seed", [5, 2**31 + 99])
def test_the_cell_rehearses_and_reads_correct(dyn_copy, seed):
    rc, line, err = dyn_copy.run("tiny-dyn-cell", seed=seed, seconds=0.5)
    assert rc == 0, err
    assert line["correct"] is True, line["check"]
    assert line["check"]["rows_compared"]["value"] % sum(
        signed_chain_sets.generate({**CONFIG, "heights": 12, "chain_id": "pb-tinydyn"}, PARAMS, seed)["rows"][1:]
    ) == 0
    assert line["check"]["table_keys_built"] == {"value": 0, "limit": 0}
    assert set(line["rehearsal_metrics"]) == {"request_p50_ms", "sigs_per_s", "setup_s"}  # no p95 in this cell


def test_the_traced_rehearsal_leaves_out_what_it_cannot_read(dyn_copy):
    """On the CPU provider nothing rides the tables and no trace is
    read: the new readers return nothing, or 0, and do not raise."""
    rc, line, err = dyn_copy.run("tiny-dyn-cell", seed=3, seconds=0.5, trace=1)
    assert rc == 0, err
    got = line["rehearsal_metrics"]
    assert "table_slab_roofline" not in got and "slot_pad_pct" not in got
    assert "tabled_rows_pct" not in got or got["tabled_rows_pct"]["value"] == 0.0


@pytest.mark.parametrize("script, control", [
    ("control_sets.py", "control_first_set_only"), ("control.py", "control_quorum_only"),
])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_both_controls_read_not_correct(dyn_copy, script, control, seed):
    rc, line, err = dyn_copy.run("tiny-dyn-cell", seed=seed, seconds=0.3, script=script)
    assert rc == 0, err  # a control script exits 0 only when the comparison caught it
    assert line["correct"] is False and line["control"] == control
    if control == "control_first_set_only":
        # rows past the first shifted index sit under another validator's key
        assert line["check"]["row_mismatches"]["value"] > 0 and line["check"]["verdict_mismatches"]["value"] > 0
        assert line["check"]["rows_compared"]["value"] > 0


# -- the readers on a canned run -------------------------------------------------------


def _run(counters0=None, counters1=None, device_rows=(1000.0, 122_000.0), module_s=None, module_runs=None):
    reduced = trace.Reduced(
        window_s=1.0, busy_s=0.9, chips=1, requests=2,
        module_s=module_s or {}, module_runs=module_runs or {},
    )
    stats = [{"device_rows": r, "host_rows": 0.0} for r in device_rows]
    if counters0 is not None:
        stats[0]["counters"], stats[1]["counters"] = counters0, counters1
    return {"engine_stats": tuple(stats), "trace": reduced, "peaks": PEAKS, "traced_rows": 241_920}


TABLED0 = {"tabled_slot_rows": 500, "tabled_slot_pad": 40, "tabled_gathered_rows": 500}


def test_tabled_share_and_slot_padding_are_the_windows_growth():
    after = {"tabled_slot_rows": 500 + 120_960, "tabled_slot_pad": 40 + 10_112, "tabled_gathered_rows": 500}
    run = _run(TABLED0, after, device_rows=(1000.0, 121_960.0))
    assert tabled_rows_pct.read(run) == 100.0
    assert slot_pad_pct.read(run) == pytest.approx(100.0 * 10_112 / 131_072)
    generic = _run(TABLED0, dict(TABLED0), device_rows=(1000.0, 121_960.0))  # the parent in the new cell
    assert tabled_rows_pct.read(generic) == 0.0 and slot_pad_pct.read(generic) is None
    half = dict(after, tabled_slot_rows=500 + 60_480, tabled_slot_pad=40)
    assert tabled_rows_pct.read(_run(TABLED0, half, device_rows=(1000.0, 121_960.0))) == 50.0


def test_tabled_readers_fall_back_to_the_process_or_to_nothing():
    """An adapter whose engine_stats carries no counters: the process's
    totals where the program keeps them (never above 100 for a process
    whose rows all rode the tables), nothing where there is no device."""
    assert tabled_rows_pct.read({"engine_stats": ({}, {})}) is None
    assert slot_pad_pct.read({"engine_stats": ({}, {})}) is None
    assert tabled_rows_pct.read(_run(device_rows=(0.0, 0.0))) is None
    from tendermint_tpu.crypto.batch import TABLED_COUNTS

    TABLED_COUNTS.add(slot_rows=90, slot_pad=10)
    snap = TABLED_COUNTS.snapshot()
    rows = float(snap["tabled_slot_rows"] + snap["tabled_gathered_rows"])
    assert tabled_rows_pct.read(_run(device_rows=(0.0, rows))) == 100.0
    assert 0.0 < slot_pad_pct.read(_run(device_rows=(0.0, rows))) < 100.0


def test_slab_work_is_the_columns_read_and_written():
    assert table_slab.BYTES_PER_COLUMN == 30_720 + 33
    w = table_slab.work(1024)
    assert w == {"ops": 0, "bytes": 2 * 1024 * 30_753}
    least = table_slab.least_seconds(1024, PEAKS)
    assert least["bound"] == "hbm_bytes" and 7e-5 < least["seconds"] < 8e-5  # ~63 MB over 819 GB/s


@pytest.mark.parametrize("slab_us", [80.0, 150.0, 400.0, 2000.0])
def test_slab_share_for_any_time_the_chip_could_reach_is_a_share(slab_us):
    c0 = {"table_slabs": 16, "table_slab_columns": 16 * 1024}
    c1 = {"table_slabs": 16 + 400, "table_slab_columns": (16 + 400) * 1024}
    run = _run(c0, c1, module_s={"jit_table_slab": 16 * slab_us * 1e-6, "jit_verify_stage_finish_blocked": 0.1},
               module_runs={"jit_table_slab": 16, "jit_verify_stage_finish_blocked": 16})
    share = table_slab_roofline.read(run)
    assert 0.0 < share <= 105.0
    assert share == pytest.approx(100.0 * table_slab.least_seconds(1024, PEAKS)["seconds"] / (slab_us * 1e-6))


def test_slab_share_is_silent_where_no_slab_ran():
    c = {"table_slabs": 0, "table_slab_columns": 0}
    assert table_slab_roofline.read(_run(c, c, module_s={"jit_verify_stage_scan_tabled_slots": 0.6},
                                         module_runs={"jit_verify_stage_scan_tabled_slots": 24})) is None
    assert table_slab_roofline.read(_run()) is None  # a program without a key pool: no counters
    assert table_slab_roofline.read({**_run(), "trace": None}) is None
