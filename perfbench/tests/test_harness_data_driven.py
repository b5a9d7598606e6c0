"""A later PR adds a cell as files and manifest entries, and edits
nothing that is there: a dummy deployment, mix, generator, entry adapter
and per-layer reader written into a copy of ``perfbench/`` are found by
name and run. Also: the generator is a function of the seed, and every
name in ``BENCHMARK.json`` keeps to the contract's characters."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

from perfbench.generators import signed_chain

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DUMMY_GENERATOR = '''
import hashlib

def generate(config, params, seed):
    blobs = [hashlib.sha256(f"{seed}-{i}".encode()).digest() * config["blob_words"] for i in range(params["pool"])]
    return {"blobs": blobs, "rows": [config["blob_words"]] * len(blobs)}
'''

DUMMY_ENTRY = '''
import hashlib
from perfbench.spans import PlainRecorder, RequestRecord

class Entry:
    """A system that is not the verifier at all: it hashes blobs."""
    def __init__(self, dataset, config, mix, annotate=False):
        self.blobs, self.rows = dataset["blobs"], dataset["rows"]
        self.recorder, self.digests, self.calls = PlainRecorder(), {}, 0
    def fresh_request(self, i):
        k = i % len(self.blobs)
        return k, RequestRecord(i, k, self.rows[k])
    def call(self, k):
        self.calls += 1
        self.digests[k] = hashlib.sha256(self.blobs[k]).digest()
    def answer(self, rec):
        import numpy as np
        return {"verdict": ("digest", self.digests[rec.pool_index].hex()), "rows": np.ones(rec.rows, bool)}
    def reference_answers(self, quorum_only=False, workers=1):
        import numpy as np
        return [{"verdict": ("digest", hashlib.sha256(b).hexdigest()), "rows": np.ones(r, bool)}
                for b, r in zip(self.blobs, self.rows)]
    def engine_stats(self):
        return {"calls": self.calls}
    def close(self):
        pass
'''

DUMMY_READER = '''
def read(run):
    before, after = run["engine_stats"]
    return float(after["calls"] - before["calls"])
'''


def test_a_new_cell_is_files_and_entries_only(bench_copy):
    b = bench_copy
    b.write("generators/blobs.py", DUMMY_GENERATOR)
    b.write("entries/hash_blob.py", DUMMY_ENTRY)
    b.write("layer_metrics/dummy_calls.py", DUMMY_READER)
    b.write("traffic/blob-stream.json", json.dumps({
        "generator": {"name": "blobs", "params": {"pool": 3}},
        "loop": {"kind": "closed", "callers": 1}, "warmup_requests": 1,
        "trace": {"start_after_s": 0.0, "seconds": 0.1},
    }))
    b.add_config("blob-store", {"blob_words": 64, "entry": "hash_blob"})
    b.add_cell("blob-cell", "blob-store", "blob-stream")
    b.add_layer_metric("dummy_calls", "calls", "sigs_per_s", ["blob-cell"])

    rc, line, err = b.run("blob-cell", seed=3, seconds=0.3, trace=0)
    assert rc == 0, err
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"] == {}, "a run off the chip prints no device metric"
    assert set(line["rehearsal_metrics"]) == {"request_p50_ms", "sigs_per_s", "setup_s"}
    assert list(line)[-1] == "check"

    rc, line, err = b.run("blob-cell", seed=3, seconds=0.3, trace=1)
    assert rc == 0, err
    # only the reader that finds something to read reports; the cell is in no other metric's list
    assert line["rehearsal_metrics"]["dummy_calls"]["value"] == line["attempted"]
    assert "device_idle_pct" not in line["rehearsal_metrics"]


def test_the_shipped_cells_run_from_a_new_config_file_alone(bench_copy):
    for cell in ("tiny-commit", "tiny-chain"):
        rc, line, err = bench_copy.run(cell, seed=2**31 + 11, seconds=0.5)
        assert rc == 0, err
        assert line["correct"] is True, line["check"]
        assert line["check"]["rows_compared"]["value"] > 0
        assert line["device"]["platform"] == "cpu" and line["metrics"] == {}


def test_the_nodes_pipelined_chain_path_is_an_entry_a_config_can_name(bench_copy):
    b = bench_copy
    b.add_config("tiny-light-node", {**b.tiny, "chain_id": "pb-tinynode", "entry": "verify_chain_pipelined",
                                     "heights": 4, "trusting_period_ns": 10800000000000})
    b.add_cell("tiny-chain-node", "tiny-light-node", "seq-chain-128")
    for metric in b.manifest["per_layer"]:
        if metric["name"] == "queue_wait_mean_ms":
            metric["workloads"].append("tiny-chain-node")
    b._save()
    rc, line, err = b.run("tiny-chain-node", seed=8, seconds=0.3, trace=1)
    assert rc == 0, err
    assert line["correct"] is True, line["check"]
    assert "queue_wait_mean_ms" in line["rehearsal_metrics"]  # the pipeline is in the timed call


def test_traced_rehearsal_reports_host_metrics_and_no_device_metric(bench_copy):
    for metric in bench_copy.manifest["per_layer"]:
        if metric["name"] == "queue_wait_mean_ms":  # a metric that lists its cells: the new cell joins the list
            metric["workloads"].append("tiny-commit")
    bench_copy._save()
    rc, line, err = bench_copy.run("tiny-commit", seed=5, seconds=0.5, trace=1)
    assert rc == 0, err
    got = set(line["rehearsal_metrics"])
    assert {"seam_host_ms", "queue_wait_mean_ms", "host_rows_pct"} <= got
    assert not got & {"verify_roofline", "scan_us_per_sig", "device_idle_pct", "launches_per_request"}
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_no_chip_and_no_rehearse_prints_no_result(bench_copy):
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    p = subprocess.run(
        [sys.executable, os.path.join(bench_copy.bench, "run.py"), "--workload", "tiny-commit",
         "--seed", "1", "--seconds", "0.2", "--trace", "0"],
        cwd=bench_copy.root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""


def _digest(dataset) -> str:
    h = hashlib.sha256()
    for el in dataset["chain"]:
        c = el["commit"]
        h.update(c["block_hash"] + bytes(c["flags"]) + b"".join(c["signatures"]))
        h.update(repr(c["timestamps"]).encode())
    h.update(b"".join(dataset["validators"]["pubkeys"]))
    return h.hexdigest()


GEN_CONFIG = {"validators": 24, "voting_power": 10, "chain_id": "pb-t", "block_time_ns": 10**9,
              "key_type": "ed25519", "sign_bytes_len": 160}


def _shipped_params(mix: str) -> dict:
    return json.load(open(os.path.join(REPO, "perfbench", "traffic", mix + ".json")))["generator"]["params"]


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 777000111])
def test_forged_rows_cover_every_window_and_both_halves_of_the_chain_batch(seed):
    """The shipped chain mix at its own depth (fewer validators: the
    proportions are what count): the rows the reference rejects fall in
    each of the 8 windows the batch is verified in, and the chain is
    still accepted."""
    from perfbench.reference import verify as ref

    config = {**GEN_CONFIG, "heights": 128}
    d = signed_chain.generate(config, _shipped_params("seq-chain-128"), seed)
    commits = ref.commit_answers(d["validators"], d["chain_id"], [el["commit"] for el in d["chain"][1:]])
    answer = ref.chain_answer(ref.ValidatorKeys(**d["validators"]), d["chain_id"], d["chain"], commits,
                              10800 * 10**9, d["now_ns"])
    assert answer["verdict"] == ("accept",)
    bad = np.flatnonzero(~answer["rows"])
    assert len(bad) == 32 and len(answer["rows"]) == sum(d["rows"][1:])
    # the cell's window is 16,384 of 120,960 rows, a little over an eighth
    windows = np.histogram(bad, bins=8, range=(0, len(answer["rows"])))[0]
    assert windows.min() >= 2, windows


def _kinds_by_size(seed):
    from perfbench.reference import verify as ref

    d = signed_chain.generate(GEN_CONFIG, _shipped_params("commit-stream-partial"), seed)
    answers = ref.commit_answers(d["validators"], d["chain_id"], [el["commit"] for el in d["chain"]])
    return sorted(
        (rows, a["verdict"][0], int((~a["rows"]).sum()), sum(f == 2 for f in el["commit"]["flags"]))
        for rows, a, el in zip(d["rows"], answers, d["chain"])
    )


def test_every_seed_carries_the_same_commits_in_another_order():
    """Size, verdict, rejected rows and for-block signers of each commit
    of the pool: the same multiset whatever the seed."""
    assert _kinds_by_size(41) == _kinds_by_size(2**31 + 42) == _kinds_by_size(43)


@pytest.mark.parametrize("seed", [6, 2**31 + 10])
def test_commit_stream_holds_each_witness_once(seed):
    """The shipped commit mix: a forged row before the quorum point
    (rejected), one after it (accepted), for-block power one signer over
    2/3 (accepted) and one short (rejected, with the tally in the verdict)."""
    from perfbench.reference import verify as ref

    d = signed_chain.generate(GEN_CONFIG, _shipped_params("commit-stream-partial"), seed)
    answers = ref.commit_answers(d["validators"], d["chain_id"], [el["commit"] for el in d["chain"]])
    kinds = sorted(a["verdict"][0] for a in answers)
    assert kinds == ["accept"] * 14 + ["invalid_signature", "not_enough_power"]
    need = 24 * 10 * 2 // 3
    assert [a["verdict"] for a in answers if a["verdict"][0] == "not_enough_power"] == [("not_enough_power", 160, need)]
    bad_rows = sorted(int((~a["rows"]).sum()) for a in answers)
    assert bad_rows == [0] * 14 + [1, 1]
    for_block = sorted(sum(f == 2 for f in el["commit"]["flags"]) for el in d["chain"])
    assert for_block[:2] == [16, 17] and for_block[2] >= 20  # the edge commits, then the honest ones


def test_generator_is_a_function_of_the_seed():
    config = GEN_CONFIG
    params = {"heights": 4, "absent_share": [0.01, 0.10], "nil_share": 0.05,
              "tampered": [{"where": "after_quorum"}]}
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = (signed_chain.generate(config, params, s) for s in (big, big, big + 1))
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)
    # every seed carries the same set of sizes, dealt in another order
    assert sorted(a["rows"]) == sorted(c["rows"])


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_manifest_keeps_to_the_contract():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    m = json.load(open(path))
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in m["paths"]) and PATH.match(c["file"])
        body = json.load(open(os.path.join(REPO, c["file"])))
        assert body["reduced"] == c["reduced"] and all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert os.path.exists(os.path.join(REPO, "perfbench", "traffic", w["traffic"] + ".json"))
        used.add(w["config"])
        names.append(w["name"])
    assert used == {c["name"] for c in m["configs"]}, "every configuration is used by some cell"
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] in ("host_clock", "device_trace")
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert p["moves"] in e2e
        moved_in = set(e2e[p["moves"]].get("workloads", cells))
        assert set(p.get("workloads", cells)) <= moved_in
        assert os.path.exists(os.path.join(REPO, "perfbench", "layer_metrics", p["name"] + ".py"))
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert set(x.get("workloads", ())) <= cells
        names.append(x["name"])
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for text in [w["why"] for w in m["workloads"]] + [c["why"] for c in m["configs"]] + [
        c["source"] for c in m["configs"]] + [p["layer"] for p in m["per_layer"]] + m["command"]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for root, _, files in os.walk(os.path.join(REPO, "perfbench")):
        for f in files:
            if "__pycache__" not in root:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
